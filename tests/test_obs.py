"""Telemetry fabric tests (repro.obs, DESIGN.md §13).

The load-bearing property is **bitwise neutrality**: turning
``ACOConfig.metrics`` on must not change a single bit of any solve —
tours, lengths, tau, PRNG keys — on any route (solo scan, batched engine,
streaming pool, sharded mesh, sparse representation).  Metrics are
read-only reductions over intermediates the step already computes; these
tests pin that contract.

Host-side surfaces (registry / tracer / event log) are tested for their
bounded-memory guarantees: exact counts and means survive window
eviction, dropped records are counted, and the Chrome-trace export is
well-formed (Perfetto-loadable) JSON.
"""
import json
import os
import subprocess
import sys
import textwrap

import dataclasses

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import aco, tsp
from repro.obs import metrics as obs_metrics
from repro.obs.registry import Histogram
from repro.solver import engine, streaming
from repro.solver.service import SolverService

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _leaves_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------- registry
def test_registry_instruments_and_snapshot():
    r = obs.Registry()
    c = r.counter("fills")
    c.inc()
    c.inc(3)
    assert r.counter("fills") is c and c.value == 4
    g = r.gauge("occ")
    g.set(0.5)
    h = r.histogram("lat", window=4)
    for v in range(1, 11):                       # window keeps only 7..10
        h.observe(float(v))
    # exact aggregates survive window eviction...
    assert h.count == 10 and h.total == 55.0
    assert h.mean() == 5.5 and h.max() == 10.0
    # ...while percentiles cover the recent window only
    assert h.percentile(0) == 7.0 and h.percentile(100) == 10.0
    snap = r.snapshot()
    assert snap["counters"] == {"fills": 4}
    assert snap["gauges"] == {"occ": 0.5}
    s = snap["histograms"]["lat"]
    assert s["count"] == 10 and s["mean"] == 5.5 and s["max"] == 10.0
    assert json.loads(json.dumps(snap)) == snap  # JSON-ready


def test_histogram_empty_and_bad_window():
    h = Histogram(window=2)
    assert h.mean() == 0.0 and h.max() == 0.0 and h.percentile(50) == 0.0
    with pytest.raises(ValueError, match="window"):
        Histogram(window=0)


# ------------------------------------------------------------------ tracer
def test_tracer_chrome_trace_format():
    t = obs.Tracer()
    with t.span("phase", process="dev0", thread="b16", k=1) as args:
        args["done"] = 2                         # an arg known at the end
    t.complete("req0", 10.0, 25.0, process="dev0", thread="b16/s0")
    ch = t.to_chrome()
    evs = ch["traceEvents"]
    assert json.loads(json.dumps(ch))            # serializable
    # metadata names every (process, thread) track exactly once
    meta = [e for e in evs if e["ph"] == "M"]
    assert {(m["name"], m["args"]["name"]) for m in meta} == {
        ("process_name", "dev0"), ("thread_name", "b16"),
        ("thread_name", "b16/s0")}
    spans = [e for e in evs if e["ph"] == "X"]
    assert {s["name"] for s in spans} == {"phase", "req0"}
    for s in spans:
        assert s["dur"] >= 0 and "pid" in s and "tid" in s
    assert [s["args"] for s in spans if s["name"] == "phase"] == [
        {"k": 1, "done": 2}]
    # interning is stable: same (process, thread) -> same ids
    assert t.track("dev0", "b16") == t.track("dev0", "b16")
    assert {e["ph"] for e in evs} == {"M", "X"}


def test_tracer_bounded():
    t = obs.Tracer(max_events=3)
    for i in range(3):
        with t.span(f"s{i}"):
            pass
    for i in range(2):
        t.complete(f"c{i}", float(i), 1.0)
    assert t.dropped == 2
    evs = t.to_chrome()["traceEvents"]
    assert len(evs) == 3 + 2                     # 3 kept + 2 meta
    assert [e["name"] for e in evs if e["ph"] == "X"] == ["s2", "c0", "c1"]


def test_eventlog_bounded_and_file_mirror(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = obs.EventLog(path, max_records=3)
    for i in range(5):
        log.emit("tick", i=i)
    log.close()
    assert log.dropped == 2
    assert [r["i"] for r in log.records()] == [2, 3, 4]  # most recent kept
    lines = [json.loads(l) for l in open(path)]          # mirror keeps all
    assert [r["i"] for r in lines] == list(range(5))
    assert all(r["kind"] == "tick" and "t" in r for r in lines)


# ----------------------------------------------------- in-jit neutrality
@pytest.mark.parametrize("variant", ["as", "mmas", "acs"])
def test_metrics_neutral_solo_scan(variant):
    """run_scan with metrics on: identical final state bitwise, plus a
    stacked convergence curve with coherent fields."""
    inst = tsp.random_instance(14, seed=3)
    cfg = aco.ACOConfig(iterations=6, variant=variant, selection="gumbel")
    prob = aco.make_problem(inst, cfg.nn_k)
    st0 = aco.init_colony(inst, cfg)

    ref, it_best = aco.run_scan(prob, st0, cfg, 6)
    got, (it_best_m, m) = aco.run_scan(
        prob, st0, dataclasses.replace(cfg, metrics=True), 6)
    _leaves_equal(ref, got)
    np.testing.assert_array_equal(np.asarray(it_best),
                                  np.asarray(it_best_m))
    curve = {f: np.asarray(v) for f, v in zip(m._fields, m)}
    assert curve["it_best_len"].shape == (6,)
    assert np.all(curve["mean_len"] >= curve["it_best_len"] - 1e-3)
    assert np.all(curve["best_len"] <= curve["it_best_len"] + 1e-3)
    # the scan carry stamps stagnation: 0 on improving iterations
    assert np.all(curve["stagnation"][curve["improved"] == 1] == 0)
    assert np.all((curve["clamp_lo"] >= 0) & (curve["clamp_lo"] <= 1))
    if variant == "mmas":
        assert np.any(curve["clamp_lo"] > 0)     # MMAS floors fresh tau
    else:
        assert np.all(curve["clamp_lo"] == 0)    # no clamp outside MMAS


def test_metrics_neutral_batched_mixed_budgets():
    """Batched engine with heterogeneous budgets: bitwise-identical stacked
    states, and each metrics row frozen at its instance's last iteration
    (best_len row == state best_len)."""
    insts = [tsp.random_instance(n, seed=n) for n in (10, 13, 16)]
    cfg = aco.ACOConfig(iterations=7, variant="mmas")
    cfg_m = dataclasses.replace(cfg, metrics=True)
    its, seeds = [5, 7, 3], [1, 2, 3]

    ref, _ = engine.solve_instances(insts, cfg, iterations=its, seeds=seeds)
    got, b = engine.solve_instances(insts, cfg_m, iterations=its,
                                    seeds=seeds)
    _leaves_equal(ref, got)

    states = engine.init_states(insts, cfg_m, seeds, b.n_pad)
    budgets = np.asarray(its, np.int32)
    out = engine.run_batch(b.problem, states, jax.numpy.asarray(budgets),
                           cfg_m, 7)
    assert len(out) == 3
    st, since, mets = out
    for i in range(3):
        row = obs_metrics.to_host(mets, i)
        assert row["best_len"] == pytest.approx(
            float(np.asarray(st.best_len)[i]), rel=1e-6)
        assert set(row) == set(obs_metrics.FIELDS)


def test_metrics_neutral_sparse():
    """Sparse route: paged tau / overflow store bitwise identical, and the
    overflow churn counters are populated (dense rows report 0)."""
    from repro.sparse import run_sparse
    inst = tsp.random_instance(24, seed=7)
    cfg = aco.ACOConfig(iterations=5, variant="mmas", selection="gumbel",
                        sparse=True, sparse_k=8, sparse_overflow=2)
    ref = run_sparse(inst, cfg)
    got = run_sparse(inst, dataclasses.replace(cfg, metrics=True))
    _leaves_equal(ref, got)


def test_metrics_ls_accept_bounded():
    inst = tsp.random_instance(16, seed=9)
    cfg = aco.ACOConfig(iterations=4, local_search="2opt", ls_rounds=4,
                        metrics=True)
    prob = aco.make_problem(inst, cfg.nn_k)
    _, (_, m) = aco.run_scan(prob, aco.init_colony(inst, cfg), cfg, 4)
    acc = np.asarray(m.ls_accept)
    assert np.all((acc >= 0) & (acc <= 1))
    assert np.any(acc > 0)          # 2-opt improves something on random16


# ------------------------------------------------------ service routes
def _stream_solve(cfg, insts, tel=None, **kw):
    svc = streaming.StreamingSolverService(cfg, max_batch=2, min_bucket=16,
                                           chunk=2, telemetry=tel, **kw)
    for i, inst in enumerate(insts):
        svc.submit(inst, iterations=4 + i, seed=50 + i)
    res = sorted(svc.run_until_drained(),
                 key=lambda r: r.request_id)
    return svc, res


def test_metrics_neutral_streaming_with_rows():
    insts = [tsp.random_instance(n, seed=n) for n in (10, 12, 14)]
    cfg = aco.ACOConfig(iterations=8, variant="mmas")
    _, ref = _stream_solve(cfg, insts)
    _, got = _stream_solve(dataclasses.replace(cfg, metrics=True),
                           insts)
    for a, b in zip(ref, got):
        assert a.best_len == b.best_len
        np.testing.assert_array_equal(a.best_tour, b.best_tour)
        assert a.metrics is None
        assert set(b.metrics) == set(obs_metrics.FIELDS)
        assert b.metrics["best_len"] == pytest.approx(b.best_len, rel=1e-6)


def test_streaming_lifecycle_events_spans_stats(tmp_path):
    """One shared Telemetry records the full slot lifecycle as events,
    chunk dispatches + per-request residency spans on device/bucket
    tracks, and registry-backed stats with exact counts."""
    insts = [tsp.random_instance(n, seed=n) for n in (10, 12, 14)]
    cfg = aco.ACOConfig(iterations=8, metrics=True)
    tel = obs.Telemetry(events_path=str(tmp_path / "e.jsonl"))
    svc, res = _stream_solve(cfg, insts, tel=tel, snapshot_every=1e-6)
    tel.close()

    by_kind = {}
    for e in tel.events.records():
        by_kind.setdefault(e["kind"], []).append(e)
    ids = {r.request_id for r in res}
    assert {e["request_id"] for e in by_kind["submit"]} == ids
    assert {e["request_id"] for e in by_kind["admit"]} == ids
    assert {e["request_id"] for e in by_kind["harvest"]} == ids
    for e in by_kind["harvest"]:                 # metrics ride the events
        assert set(e["metrics"]) == set(obs_metrics.FIELDS)
    snaps = by_kind["stats_snapshot"]
    assert snaps and all("stats" in e and "resident_metrics" in e
                         for e in snaps)
    # the file mirror replays the same records
    mirror = [json.loads(l) for l in open(tmp_path / "e.jsonl")]
    assert len(mirror) == len(tel.events.records())

    st = svc.stats
    assert st["submitted"] == st["completed"] == len(insts)
    assert svc._h_latency.count == len(insts)
    assert 0 < st["occupancy_mean"] <= 1
    assert st["latency_max_s"] >= st["latency_p50_s"] > 0

    names = [e.get("name") for e in tel.tracer.to_chrome()["traceEvents"]]
    assert "chunk_dispatch" in names
    for rid in ids:
        assert f"req{rid}" in names              # residency span per request


def test_streaming_reject_counted():
    cfg = aco.ACOConfig(iterations=2)
    svc = streaming.StreamingSolverService(cfg, max_batch=2, max_waiting=1)
    svc.submit(tsp.random_instance(8, seed=0))
    with pytest.raises(streaming.AdmissionError):
        svc.submit(tsp.random_instance(8, seed=1))
    assert svc.stats["rejected"] == 1
    assert any(e["kind"] == "reject" for e in svc.tel.events.records())


def test_metrics_neutral_drain_service_with_checkpoint(tmp_path):
    """Drain scheduler with the Supervisor-checkpointed path: the
    checkpointed carry gains a metrics element, and results stay bitwise
    the plain metrics-off run."""
    insts = [tsp.random_instance(n, seed=n) for n in (10, 12, 14)]

    def drain(cfg, **kw):
        svc = SolverService(cfg, max_batch=2, **kw)
        for i, inst in enumerate(insts):
            svc.submit(inst, iterations=4 + i, seed=50 + i)
        return svc.run()

    ref = drain(aco.ACOConfig(iterations=8))
    got = drain(aco.ACOConfig(iterations=8, metrics=True),
                checkpoint_dir=str(tmp_path), ckpt_chunk=3)
    for a, b in zip(ref, got):
        assert a.best_len == b.best_len
        np.testing.assert_array_equal(a.best_tour, b.best_tour)
        assert a.metrics is None
        assert set(b.metrics) == set(obs_metrics.FIELDS)


# --------------------------------------------------------------- sharded
def test_metrics_neutral_sharded_subprocess():
    """Mesh route with 8 forced host devices and uneven B: metrics rows
    shard/pad/slice with the instances and the states stay bitwise."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    body = textwrap.dedent("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import aco, tsp
        from repro.solver import batch as batch_mod
        from repro.solver import engine, placement

        insts = [tsp.circle_instance(n, seed=n) for n in (10, 13, 12)]
        cfg = aco.ACOConfig(iterations=6, variant="mmas",
                            selection="gumbel")
        cfg_m = dataclasses.replace(cfg, metrics=True)
        b = batch_mod.make_batch(insts, 16, cfg.nn_k)
        budgets = jnp.asarray([6, 3, 5], jnp.int32)
        mesh = placement.data_mesh(8)     # B=3 over D=8: phantom padding

        def run(c):
            return engine.run_batch(
                b.problem, engine.init_states(insts, c, [1, 2, 3], 16),
                budgets, c, 6, mesh=mesh)

        ref = run(cfg)
        got = run(cfg_m)
        assert len(ref) == 2 and len(got) == 3
        for x, y in zip(jax.tree.leaves(ref), jax.tree.leaves(got[:2])):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        mets = got[2]
        assert mets.best_len.shape == (3,)       # sliced back to B
        np.testing.assert_allclose(np.asarray(mets.best_len),
                                   np.asarray(got[0].best_len), rtol=1e-6)
        print("SHARDED OBS OK")
    """)
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARDED OBS OK" in out.stdout

"""The solver's phases on the profiler's clock: named scopes in the colony
step's device operations, and the streaming service's host spans in a live
``jax.profiler`` capture (DESIGN.md §13)."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aco, tsp
from repro.solver import batch as batch_mod
from repro.solver import engine, streaming

BUCKET, SLOTS = 16, 2


def _scopes_in_program(cfg: aco.ACOConfig, kind: str) -> set[str]:
    """Path components of every ``op_name`` in the compiled batch program
    a streaming pool of this bucket dispatches."""
    insts = [tsp.circle_instance(BUCKET, seed=0)] * SLOTS
    if kind == "sparse":
        b = batch_mod.make_sparse_batch(insts, cfg.sparse_k, BUCKET)
        states = engine.init_sparse_states(insts, cfg, [0, 1], BUCKET)
        ewt = b.ewt
    else:
        b = batch_mod.make_batch(insts, BUCKET, cfg.nn_k)
        states = engine.init_states(insts, cfg, [0, 1], BUCKET)
        ewt = "EUC_2D"
    zeros = jnp.zeros((SLOTS,), jnp.int32)
    text = engine.aot_lower(b.problem, states, zeros, cfg, 2, 0, zeros,
                            kind=kind, ewt=ewt).compile().as_text()
    return {part for path in re.findall(r'op_name="([^"]*)"', text)
            for part in path.split("/")}


@pytest.mark.parametrize("kind,cfg,want", [
    ("dense", aco.ACOConfig(), {"choice", "construct", "deposit"}),
    ("dense", aco.ACOConfig(variant="mmas", local_search="2opt"),
     {"choice", "construct", "local_search", "deposit"}),
    # the fused kernel and the sparse route weigh choices inside their
    # construction loop (no separate choice phase); sparse runs no LS
    ("dense", aco.ACOConfig(use_pallas=True), {"construct", "deposit"}),
    ("sparse", aco.ACOConfig(variant="mmas", sparse=True, sparse_k=8),
     {"construct", "deposit"}),
], ids=["dense-as", "dense-mmas-2opt", "pallas-as", "sparse-mmas"])
def test_colony_step_phases_named_in_compiled_program(kind, cfg, want):
    assert want <= _scopes_in_program(cfg, kind)


def _capture(directory, fn):
    """Run ``fn`` inside a profiler capture; returns the ``aco.*`` host
    events as (name, start_ns, end_ns, stats) in start order."""
    jax.profiler.start_trace(str(directory))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(directory / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("aco."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                {str(k): v for k, v in ev.stats}))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _service():
    svc = streaming.StreamingSolverService(
        aco.ACOConfig(iterations=4), max_batch=SLOTS, min_bucket=BUCKET,
        chunk=2)
    # prep at admission, so a tick holds every phase
    svc.prep_ahead = 0
    return svc


def _requests():
    return [tsp.random_instance(n, seed=n) for n in (12, 14)]


def _two_ticks(svc, results):
    for inst in _requests():
        svc.submit(inst, seed=inst.n)
    results += svc.step()          # admit + prep, dispatch, harvest none
    results += svc.step()          # dispatch, harvest both


def test_capture_reads_service_phases_with_scalar_args(tmp_path):
    svc = _service()
    svc.submit(tsp.random_instance(12, seed=0))
    svc.run_until_drained()                       # compile outside
    results = []
    evs = _capture(tmp_path, lambda: _two_ticks(svc, results))
    assert len(results) == 2
    by = {}
    for name, s, e, stats in evs:
        by.setdefault(name, []).append((s, e, stats))
    assert set(by) == {"aco.step", "aco.admit", "aco.prep",
                       "aco.chunk_dispatch", "aco.harvest"}
    assert [st for _, _, st in by["aco.step"]] == [
        {"resident": 0, "waiting": 2}, {"resident": 2, "waiting": 0}]
    assert [st for _, _, st in by["aco.admit"]] == [{"admitted": 2}]
    assert sorted((st["n"], st["bucket"]) for _, _, st in by["aco.prep"]) \
        == [(12, BUCKET), (14, BUCKET)]
    # list args (request_ids) stay in the Chrome trace
    assert [st for _, _, st in by["aco.chunk_dispatch"]] == [
        {"occupied": 2, "slots": SLOTS, "bucket": BUCKET, "cities": 26,
         "chunk": 2}] * 2
    assert [st for _, _, st in by["aco.harvest"]] == [
        {"bucket": BUCKET, "harvested": 0}, {"bucket": BUCKET,
                                             "harvested": 2}]
    # prep nests in admit, and every phase in its tick
    (a0, a1, _), = by["aco.admit"]
    assert all(a0 <= s and e <= a1 for s, e, _ in by["aco.prep"])
    ticks = [(s, e) for s, e, _ in by["aco.step"]]
    for name in ("aco.admit", "aco.chunk_dispatch", "aco.harvest"):
        for s, e, _ in by[name]:
            assert any(t0 <= s and e <= t1 for t0, t1 in ticks), name


def test_capture_leaves_results_bitwise_equal(tmp_path):
    plain, traced = [], []
    _two_ticks(_service(), plain)
    _capture(tmp_path, lambda: _two_ticks(_service(), traced))
    assert [r.request_id for r in plain] == [r.request_id for r in traced]
    for a, b in zip(plain, traced):
        assert a.best_len == b.best_len and a.iterations == b.iterations
        np.testing.assert_array_equal(a.best_tour, b.best_tour)

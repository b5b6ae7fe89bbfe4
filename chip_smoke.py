"""Bring-up smoke test: drive the solver's main paths once on a TPU chip.

    python chip_smoke.py               # every phase, one chip
    python chip_smoke.py --four-chip   # only the data-mesh phase, four chips

One process holds the chip for the whole run, and the run stops at the first
failed check.  Each phase prints one line; the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

Phases (one chip):

- device    a TPU is present, kernels are compiled (not interpreted), and
            a donated buffer that fails to alias is an error;
- kernels   every Pallas kernel through ``kernels/ops.py`` at paper widths
            (n = m = 1002, and 2392), against its ``kernels/ref.py`` oracle;
- deposits  every pheromone deposit strategy against ``scatter``;
- instance  ``aco.run`` on a 1002-city instance, pure-JAX and Pallas routes,
            tour lengths recomputed on the host; MMAS + 2-opt on circle256;
- serve     ``solve_serve`` in-process: a warmed stream, a warmed Pallas
            drain and a sparse drain at n = 2392.

``--four-chip`` runs the served drain and stream workloads on a 4-device
data mesh and compares them bitwise, per instance, with one device.

Instances come from seeds.  Without a TPU, or without the repository's
sources beside this file, the script exits non-zero and prints no result.
The ``solve_serve`` reports and event logs go to ``chiprun_out/chip_smoke/``.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time
import traceback
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

PAPER_N = 1002          # largest of the paper's Table 2 sizes
LARGE_N = 2392          # the sparse bench's size (TSPLIB pr2392)
SPARSE_K = 32


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# ----------------------------------------------------------------- device
def phase_device(require_count: int = 1) -> dict:
    import jax
    from repro.kernels import ops
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU: JAX found no usable backend ({e})")
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"no TPU: JAX found {len(devices)} {d0.platform} device(s) "
             f"and this smoke test runs on a TPU only")
    check(len(devices) >= require_count,
          f"needs {require_count} TPU chips, JAX found {len(devices)}")
    check(ops.INTERPRET is False, "kernels.ops.INTERPRET is not False")
    # A donated buffer that cannot alias is a silent copy on every call.
    warnings.filterwarnings("error",
                            message="Some donated buffers were not usable")
    print(f"device: {d0.platform} {d0.device_kind} x{len(devices)}, "
          f"kernels compiled (INTERPRET=False), donation warnings are errors",
          flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------- kernels
def _equal(name: str, got, want) -> None:
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    bad = int(np.sum(got != want))
    check(bad == 0, f"{name}: {bad} of {want.size} entries differ from "
                    f"the oracle")


def _close(name: str, got, want, rtol: float) -> None:
    import numpy as np
    try:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=rtol, atol=0)
    except AssertionError as e:
        raise SmokeFailure(f"{name}: not within rtol {rtol}: {e}") from None


def phase_kernels(small: int = PAPER_N, large: int = LARGE_N,
                  k: int = SPARSE_K) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    f32 = np.float32
    checks = []
    for n in (small, large):
        m = n
        tau = jnp.asarray(rng.uniform(0.05, 1.0, (n, n)).astype(f32))
        eta = jnp.asarray(rng.uniform(0.05, 1.0, (n, n)).astype(f32))
        cur = jnp.asarray(rng.integers(0, n, m).astype(np.int32))
        vis = jnp.asarray(rng.random((m, n)) < 0.3)
        rand = jnp.asarray(rng.uniform(1e-6, 1.0, (m, n)).astype(f32))

        _close(f"choice_info n={n}", ops.choice_info(tau, eta, 1.0, 2.0),
               ref.choice_info(tau, eta, 1.0, 2.0), 1e-6)
        _equal(f"fused_select n={n}",
               ops.fused_select(tau, eta, cur, vis, rand),
               ref.fused_select(tau, eta, cur, vis, rand))
        tau_rows = jnp.asarray(rng.uniform(0.05, 1.0, (m, k)).astype(f32))
        eta_rows = jnp.asarray(rng.uniform(0.05, 1.0, (m, k)).astype(f32))
        cand = jnp.asarray(rng.integers(0, n, (m, k)).astype(np.int32))
        got = ops.sparse_select(tau_rows, eta_rows, cand, vis, rand)
        want = ref.sparse_select(tau_rows, eta_rows, cand, vis, rand)
        _equal(f"sparse_select k={k} n={n} pos", got[0], want[0])
        _equal(f"sparse_select k={k} n={n} have", got[1], want[1])
        checks += ["choice_info", "fused_select", "sparse_select"]

        if n != small:
            continue
        q = jnp.asarray(rng.integers(1, 128, (n, n)).astype(np.int8))
        scale = jnp.asarray(rng.uniform(1e-3, 1e-2, (n, 1)).astype(f32))
        _equal(f"fused_select int8 n={n}",
               ops.fused_select(q, eta, cur, vis, rand, tau_scale=scale),
               ref.fused_select_quant(q, scale, eta, cur, vis, rand))
        rows = ref.choice_info(tau, eta, 1.0, 2.0)[cur]
        _equal(f"tour_select n={n}", ops.tour_select(rows, vis, rand),
               ref.tour_select(rows, vis, rand))
        # ties: greedy over constant weights must pick the first unvisited
        ones = jnp.ones((n, n), jnp.float32)
        _equal(f"tour_select greedy ties n={n}",
               ops.tour_select(ones, vis, rand, "greedy"),
               ref.tour_select(ones, vis, rand, "greedy"))
        _equal(f"fused_select greedy ties n={n}",
               ops.fused_select(ones, ones, cur, vis, rand, mode="greedy"),
               ref.fused_select(ones, ones, cur, vis, rand, mode="greedy"))
        tours = jnp.asarray(np.stack([rng.permutation(n)
                                      for _ in range(m)]).astype(np.int32))
        w = jnp.asarray(rng.uniform(1e-4, 1e-3, m).astype(f32))
        frm = tours.ravel()
        to = jnp.roll(tours, -1, axis=-1).ravel()
        wrep = jnp.repeat(w, n)
        want = ref.pheromone_update(tau, jnp.concatenate([frm, to]),
                                    jnp.concatenate([to, frm]),
                                    jnp.concatenate([wrep, wrep]), 0.5)
        _close(f"pheromone_update n={n}",
               ops.pheromone_update(tau, tours, w, 0.5), want, 1e-6)
        moves = n * 8
        a1, a2, r1, r2 = (jnp.asarray(rng.uniform(0, 100, (m, moves))
                                      .astype(f32)) for _ in range(4))
        valid = jnp.asarray(rng.random((m, moves)) < 0.9)
        for mode in ("best", "first"):
            got = ops.two_opt_best(a1, a2, r1, r2, valid, mode=mode)
            want = ref.two_opt_best(a1, a2, r1, r2, valid, mode=mode)
            _equal(f"two_opt_best {mode} n={n} idx", got[1], want[1])
            _equal(f"two_opt_best {mode} n={n} delta", got[0], want[0])
        checks += ["fused_select int8", "tour_select", "tour_select ties",
                   "fused_select ties", "pheromone_update", "two_opt_best x2"]
    print(f"kernels: {len(checks)} oracle checks passed — choice_info, "
          f"fused_select, sparse_select (k={k}) at n=m={small},{large}; "
          f"fused_select int8, greedy ties, tour_select, pheromone_update, "
          f"two_opt_best (best, first) at n=m={small} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)


# --------------------------------------------------------------- deposits
def phase_deposits(n: int = PAPER_N) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.core import pheromone

    t0 = time.perf_counter()
    rng = np.random.default_rng(1)
    tours = jnp.asarray(np.stack([rng.permutation(n)
                                  for _ in range(n)]).astype(np.int32))
    w = jnp.asarray(rng.uniform(1e-4, 1e-3, n).astype(np.float32))
    want = np.asarray(pheromone.deposit(n, tours, w, "scatter"))
    for strategy in pheromone.STRATEGIES:
        _close(f"deposit {strategy} n={n}",
               pheromone.deposit(n, tours, w, strategy), want, 1e-5)
    print(f"deposits: {', '.join(pheromone.STRATEGIES)} match scatter to "
          f"rtol 1e-5 at n=m={n} ({time.perf_counter() - t0:.1f} s)",
          flush=True)


# --------------------------------------------------------------- instance
def _host_length(inst, tour) -> float:
    import numpy as np
    d = inst.distances().astype(np.float64)
    return float(d[tour, np.roll(tour, -1)].sum())


def phase_instance(n: int = PAPER_N, iterations: int = 10,
                   circle_n: int = 256, ls_iterations: int = 20) -> None:
    import numpy as np
    from repro.core import aco, tsp

    t0 = time.perf_counter()
    inst = tsp.random_instance(n, seed=0)
    lens = {}
    for use_pallas in (False, True):
        route = "pallas" if use_pallas else "jax"
        cfg = aco.ACOConfig(variant="mmas", iterations=iterations,
                            use_pallas=use_pallas)
        st = aco.run(inst, cfg)
        tour = np.asarray(st.best_tour)
        best = float(st.best_len)
        check(tsp.is_valid_tour(tour) and tour.shape == (n,),
              f"{route} route: best tour is not a permutation of {n}")
        host = _host_length(inst, tour)
        check(abs(host - best) <= 1e-5 * host,
              f"{route} route: best_len {best} but the host recomputes "
              f"{host}")
        lens[route] = best

    circle = tsp.circle_instance(circle_n, seed=11)
    cfg = aco.ACOConfig(iterations=ls_iterations, variant="mmas",
                        selection="gumbel", m=64, local_search="2opt",
                        ls_tours="iteration_best", ls_rounds=128)
    st = aco.run(circle, cfg)
    tour = np.asarray(st.best_tour)
    check(tsp.is_valid_tour(tour), "2-opt run: best tour is not a "
                                   "permutation")
    gap = float(st.best_len) / circle.known_optimum - 1.0
    check(gap <= 0.01, f"MMAS+2opt on {circle.name}: gap {gap:.4%} > 1%")
    print(f"instance: {inst.name} MMAS m=n {iterations} it best_len "
          f"jax={lens['jax']:.1f} pallas={lens['pallas']:.1f} "
          f"(host-recomputed, valid tours); {circle.name} MMAS+2opt "
          f"{ls_iterations} it gap={gap:.4%} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)


# ------------------------------------------------------------------ serve
def _serve(tag: str, argv: list[str]):
    """Run solve_serve in-process; its JSON report and event log go to
    OUT_DIR.  Returns (service, results, events)."""
    from repro.launch import solve_serve
    os.makedirs(OUT_DIR, exist_ok=True)
    events_path = os.path.join(OUT_DIR, f"{tag}.events.jsonl")
    report = io.StringIO()
    try:
        with contextlib.redirect_stdout(report):
            svc, results = solve_serve.main(
                argv + ["--events-out", events_path])
    except SystemExit as e:
        raise SmokeFailure(f"{tag}: solve_serve exited {e.code}") from None
    finally:
        with open(os.path.join(OUT_DIR, f"{tag}.report.json"), "w") as f:
            f.write(report.getvalue())
    with open(events_path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    return svc, results, events


def _check_served(tag: str, svc, results, events, submitted: int,
                  warmed: bool) -> dict:
    from repro.core import tsp
    stats = svc.stats
    check(len(results) == submitted,
          f"{tag}: completed {len(results)} of {submitted} submitted")
    for r in results:
        check(not r.expired, f"{tag}: request {r.request_id} expired")
        check(tsp.is_valid_tour(r.best_tour) and len(r.best_tour) == r.n,
              f"{tag}: request {r.request_id} returned an invalid tour")
    kinds = {e["kind"] for e in events}
    check("warmup" in kinds, f"{tag}: no warmup event in the log")
    for bad in ("aot_dispatch_fallback", "warmup_error"):
        check(bad not in kinds, f"{tag}: {bad} event logged")
    programs = stats.get("programs", {})
    if warmed:
        check(not programs.get("warm_errors"),
              f"{tag}: warmup errors {programs.get('warm_errors')}")
        check(programs.get("misses") == 0,
              f"{tag}: jit_cache_miss={programs.get('misses')} after warmup "
              f"(missed {programs.get('missed_signatures')})")
    return stats


SERVE_COMMON = ["--warmup", "--min-n", "64", "--max-n", "1000",
                "--num-instances", "24", "--max-batch", "8", "--chunk", "4",
                "--iterations", "20", "--variant", "mmas"]
SPARSE_DRAIN = ["--sparse", "--sparse-k", str(SPARSE_K), "--warmup",
                "--min-n", str(LARGE_N), "--max-n", str(LARGE_N),
                "--num-instances", "4", "--max-batch", "4",
                "--iterations", "10", "--ants", "256", "--variant", "mmas"]


def _arg(argv: list[str], flag: str) -> int:
    return int(argv[argv.index(flag) + 1])


def phase_serve(common: list[str] = SERVE_COMMON,
                sparse: list[str] = SPARSE_DRAIN) -> None:
    t0 = time.perf_counter()
    parts = []
    for tag, argv in (("stream", ["--stream"] + common),
                      ("drain_pallas", ["--use-pallas"] + common),
                      ("drain_sparse", sparse)):
        t1 = time.perf_counter()
        svc, results, events = _serve(tag, argv)
        stats = _check_served(tag, svc, results, events,
                              _arg(argv, "--num-instances"), warmed=True)
        parts.append(f"{tag} {len(results)}/{_arg(argv, '--num-instances')}"
                     f" hits={stats['programs']['hits']} misses=0 "
                     f"{time.perf_counter() - t1:.1f}s")
    print(f"serve: {'; '.join(parts)} — tours valid, no warmup errors, no "
          f"fallbacks ({time.perf_counter() - t0:.1f} s)", flush=True)


# ------------------------------------------------------------- four chips
def _by_id(results) -> dict:
    return {r.request_id: r for r in results}


def phase_four_chip(common: list[str] = SERVE_COMMON,
                    devices: int = 4) -> None:
    import jax
    import numpy as np

    t0 = time.perf_counter()
    parts = []
    for tag, mode in (("drain", []), ("stream", ["--stream"])):
        argv = mode + common
        submitted = _arg(argv, "--num-instances")
        one_svc, one, one_ev = _serve(f"{tag}_1dev", argv)
        _check_served(f"{tag}_1dev", one_svc, one, one_ev, submitted,
                      warmed=False)
        mesh_svc, many, many_ev = _serve(
            f"{tag}_{devices}dev", argv + ["--shard", "--devices",
                                           str(devices)])
        _check_served(f"{tag}_{devices}dev", mesh_svc, many, many_ev,
                      submitted, warmed=False)
        a, b = _by_id(one), _by_id(many)
        check(a.keys() == b.keys(), f"{tag}: request ids differ")
        diff = [i for i in a
                if a[i].best_len != b[i].best_len
                or not np.array_equal(a[i].best_tour, b[i].best_tour)
                or a[i].iterations != b[i].iterations]
        check(not diff, f"{tag}: {len(diff)} of {len(a)} instances differ "
                        f"between 1 and {devices} devices: ids {diff}")
        if mode:
            pools = 0
            for bucket, ps in mesh_svc._pools.items():
                check(len(ps) == devices,
                      f"bucket {bucket}: {len(ps)} pools for {devices} "
                      f"devices")
                for j, pool in enumerate(ps):
                    want = {mesh_svc._devices[j]}
                    for leaf in jax.tree.leaves((pool.problem, pool.states,
                                                 pool.budgets, pool.since)):
                        check(leaf.devices() == want,
                              f"bucket {bucket} pool {j}: a resident leaf "
                              f"sits on {leaf.devices()}, not {want}")
                    pools += 1
            parts.append(f"{tag} {len(a)} instances bitwise equal, "
                         f"{pools} pools each on its own device")
        else:
            parts.append(f"{tag} {len(a)} instances bitwise equal")
    print(f"four-chip: data_mesh({devices}) vs one device — "
          f"{'; '.join(parts)} ({time.perf_counter() - t0:.1f} s)",
          flush=True)


# ------------------------------------------------------------------- main
def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-device data-mesh phase")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repository's sources are not beside this file "
             f"(no {os.path.join(SRC, 'repro')}); run it from a checkout")
    sys.path.insert(0, SRC)
    t0 = time.perf_counter()
    device = phase_device(4 if args.four_chip else 1)
    from repro.solver import compile_cache_dir, enable_persistent_cache
    enable_persistent_cache(compile_cache_dir())
    phases = ([phase_four_chip] if args.four_chip else
              [phase_kernels, phase_deposits, phase_instance, phase_serve])
    for phase in phases:
        try:
            phase()
        except Exception as e:        # noqa: BLE001 — report, then fail
            traceback.print_exc()
            fail(f"FAILED in {phase.__name__}: {type(e).__name__}: {e}")
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

"""First-request cold-start latency: jit-on-demand vs persistent cache vs warmup.

The serving cold-start problem (DESIGN.md §16): the first request that
needs a (bucket, batch, config) program pays the full XLA compile on the
serving critical path.  This bench measures the first-request latency of a
streaming service under the three mitigation levels solver/programs.py
provides, each trial in a **fresh subprocess** so the in-process jit cache
really is cold:

- ``cold``     plain service: the first request compiles the chunk program;
- ``persist``  persistent XLA compilation cache, in the directory
               ``programs.compile_cache_dir`` picks (pre-primed): the
               compile is replaced by an executable cache load;
- ``warmed``   ``warm_programs`` AOT-compiles the bucket before the
               request: the request dispatches a cached executable.

The headline is the p99 over ``--repeats`` trials per mode and the
``warmed_over_cold`` ratio, floor-asserted (a warmed first request must be
at most ``--max-ratio`` of the cold one — the whole point of the warmup
ladder) and regression-guarded via benchmarks/regress.py.

Emits ``BENCH_coldstart.json`` at the repo root.

    PYTHONPATH=src python benchmarks/coldstart.py [--smoke|--dry]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(_ROOT, "BENCH_coldstart.json")

CASE = dict(n=24, batch=4, chunk=3, iterations=6, variant="mmas", seed=0,
            repeats=3, max_ratio=0.5)
# --dry/--smoke: one repeat, looser floor (single-sample wall clock on a
# loaded CI container) — still proves warmed < cold by a wide margin.
SMOKE_CASE = dict(n=24, batch=4, chunk=3, iterations=6, variant="mmas",
                  seed=0, repeats=1, max_ratio=0.8)


def _child(case: dict, mode: str) -> dict:
    """One trial, run inside this (fresh) process: build the service,
    apply the mode's mitigation, then time the first request end to end
    (submit -> result).  Prints one JSON line on stdout."""
    t_import0 = time.perf_counter()
    import jax
    from repro.core import aco, tsp
    from repro.solver import (ProgramCache, StreamingSolverService,
                              compile_cache_dir, enable_persistent_cache)
    import_s = time.perf_counter() - t_import0

    if mode == "persist":
        enable_persistent_cache(compile_cache_dir())
    else:
        # JAX turns the cache on by itself when JAX_COMPILATION_CACHE_DIR
        # is set; the other modes must compile for real.
        jax.config.update("jax_enable_compilation_cache", False)
    cfg = aco.ACOConfig(variant=case["variant"],
                        iterations=case["iterations"], seed=case["seed"])
    programs = ProgramCache() if mode == "warmed" else None
    svc = StreamingSolverService(cfg, max_batch=case["batch"],
                                 chunk=case["chunk"], programs=programs)
    warm_s = 0.0
    if mode == "warmed":
        t0 = time.perf_counter()
        svc.warm_programs(case["n"], case["n"])
        warm_s = time.perf_counter() - t0

    inst = tsp.random_instance(case["n"], seed=case["seed"])
    t0 = time.perf_counter()
    svc.submit(inst, iterations=case["iterations"], seed=case["seed"])
    results = svc.run_until_drained()
    first_request_s = time.perf_counter() - t0
    assert len(results) == 1 and np.isfinite(results[0].best_len)
    return {"mode": mode, "first_request_s": first_request_s,
            "warm_s": warm_s, "import_s": import_s,
            "best_len": float(results[0].best_len),
            "hits": programs.stats()["hits"] if programs else 0}


def _spawn(case: dict, mode: str) -> dict:
    """Run one trial in a fresh interpreter (cold in-process jit cache)."""
    payload = json.dumps({"case": case, "mode": mode})
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", payload],
        capture_output=True, text=True, env=env, cwd=_ROOT, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"coldstart child ({mode}) failed:\n"
                           f"{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _percentiles(samples: list[float]) -> dict:
    a = np.asarray(samples, np.float64)
    return {"p50_s": float(np.percentile(a, 50)),
            "p99_s": float(np.percentile(a, 99)),
            "mean_s": float(a.mean()), "samples": [round(s, 4)
                                                   for s in samples]}


def main(case: dict, out_path: str = DEFAULT_OUT) -> dict:
    # Prime the persistent cache once (this run's compile populates the
    # directory; it is *not* timed as a persist sample).
    _spawn(case, "persist")

    rows = {}
    for mode in ("cold", "persist", "warmed"):
        trials = [_spawn(case, mode)
                  for _ in range(case["repeats"])]
        rows[mode] = _percentiles([t["first_request_s"] for t in trials])
        rows[mode]["warm_s_mean"] = float(
            np.mean([t["warm_s"] for t in trials]))
        print(f"coldstart: {mode:8s} first-request "
              f"p99={rows[mode]['p99_s']:.3f}s "
              f"(p50={rows[mode]['p50_s']:.3f}s)", file=sys.stderr)

    warmed_over_cold = rows["warmed"]["p99_s"] / rows["cold"]["p99_s"]
    persist_over_cold = rows["persist"]["p99_s"] / rows["cold"]["p99_s"]
    payload = {
        "schema": "repro.bench_coldstart/v1",
        "unix_time": int(time.time()),
        "case": case,
        "rows": rows,
        "warmed_over_cold": warmed_over_cold,
        "persist_over_cold": persist_over_cold,
        "max_ratio_required": case["max_ratio"],
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"coldstart: warmed/cold={warmed_over_cold:.3f} "
          f"persist/cold={persist_over_cold:.3f} -> {out_path}",
          file=sys.stderr)
    # The floor assertion: a warmup ladder that doesn't beat cold-start
    # compile latency is a regression in the tentpole claim itself.
    assert warmed_over_cold <= case["max_ratio"], (
        f"warmed first-request p99 is {warmed_over_cold:.2f}x cold "
        f"(required <= {case['max_ratio']})")
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single-repeat quick case")
    ap.add_argument("--dry", action="store_true",
                    help="CI smoke: single repeat, write to a temp file "
                         "(the committed BENCH file is untouched)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        spec = json.loads(args.child)
        print(json.dumps(_child(spec["case"], spec["mode"])))
        sys.exit(0)
    case = SMOKE_CASE if (args.smoke or args.dry) else CASE
    out = args.out or (os.path.join(tempfile.mkdtemp(prefix="coldstart_"),
                                    "BENCH_coldstart.json")
                       if args.dry else DEFAULT_OUT)
    main(case, out)

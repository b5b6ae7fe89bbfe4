"""Benchmark entry point — one process per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only table2,...]

Each table runs as its own ``python -m benchmarks.<module>`` process, one
after another, and this parent never imports JAX: a TPU chip belongs to one
process at a time, so a parent holding it would starve every table (and the
device children that ``sharded_throughput`` spawns). Prints CSV blocks per
table. --full uses the paper's larger instances; default sizes keep the
whole suite ~2-4 min on a CPU.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

from . import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# table -> (module, argv by default, argv with --full)
TABLES = {
    "table2": ("table2_tour_construction", [], ["--full"]),
    "table3": ("table3_pheromone", [], ["--full"]),
    "fig4": ("fig4_overall", [], ["--full"]),
    "fig5": ("fig5_pheromone", [], []),
    "quality": ("quality", [], []),
    "local_search": ("local_search", [], ["--full"]),
    "construction": ("construction_profile", [], ["--full"]),
    "solver": ("solver_throughput", ["--smoke"], []),
    "streaming": ("streaming_throughput", ["--smoke"], []),
    "sharded": ("sharded_throughput", ["--smoke"], []),
    "roofline": ("roofline", [], []),
    "sparse": ("sparse_scale", ["--dry"], []),
    "obs": ("obs_overhead", ["--smoke"], []),
}


def run_table(name: str, full: bool) -> int:
    """Run one table in a fresh interpreter; returns its exit code."""
    module, argv, full_argv = TABLES[name]
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), env.get("PYTHONPATH")) if p)
    cmd = [sys.executable, "-m", f"benchmarks.{module}",
           *(full_argv if full else argv)]
    return subprocess.run(cmd, cwd=_ROOT, env=env).returncode


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(TABLES))
    ap.add_argument("--no-manifest", action="store_true",
                    help="skip refreshing BENCH_manifest.json at the end")
    args = ap.parse_args()
    names = list(TABLES) if not args.only else args.only.split(",")
    failed = []
    for name in names:
        if name not in TABLES:
            print(f"unknown table {name}", file=sys.stderr)
            failed.append(name)
            continue
        t0 = time.time()
        print(f"==== {name} " + "=" * 50, flush=True)
        rc = run_table(name, args.full)
        if rc != 0:
            failed.append(name)
        print(f"---- {name} {'done' if rc == 0 else f'FAILED (exit {rc})'}"
              f" in {time.time()-t0:.1f}s\n", flush=True)
    if not args.no_manifest:
        # fold whatever BENCH_*.json files now exist into the manifest so
        # benchmarks/regress.py sees a consistent index (DESIGN.md §14)
        print(f"manifest refreshed: {manifest.write_manifest()}")
    if failed:
        sys.exit(f"benchmarks failed: {','.join(failed)}")


if __name__ == "__main__":
    main()

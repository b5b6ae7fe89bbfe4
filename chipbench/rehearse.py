"""Rehearse a cell on the CPU at a tiny size: the same code path as
``run.py``, with each configuration's and traffic's ``rehearse`` overrides,
and four virtual devices for a four-chip cell.  Prints the numbers the
check compares and no metric (a CPU run measures no chip).

    python3 chipbench/rehearse.py --workload <name> [--seed N] [--seconds S]
        [--trace 0|1]
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import run  # noqa: E402
from chipbench.harness import cells, check, session  # noqa: E402


def tiny(cell: cells.Cell) -> cells.Cell:
    """The cell with its files' ``rehearse`` overrides applied."""
    cell.config = dict(cell.config, **cell.config.get("rehearse", {}))
    cell.traffic = dict(cell.traffic, **cell.traffic.get("rehearse", {}))
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = tiny(cells.resolve(cells.load_bench(), args.workload))
    session.add_program_to_path()
    devs = session.devices(cell.chips, allow_cpu=True)
    rec, correct, rows = run.run_cell(cell, args.seed, args.seconds,
                                      bool(args.trace), devs, T_START)
    for s in check.report_lines(rows):
        print(s)
    print(f"rehearsal {args.workload}: correct={correct} "
          f"attempted={rec.attempted} failed={rec.failed} "
          f"devices={rec.device['count']} (CPU: no metric)")
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once, on the chip, and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip(s) for the whole run: it builds the cell's
inputs from the seed, sets up and warms the service, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line as the last line of standard output
(the numbers compared, each with its limit, are also the last lines of
standard error).  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, from a profiler trace of part of the
window.  Without a TPU, or with fewer chips than the cell asks for, it
exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.harness import cells, check, session  # noqa: E402
from chipbench.harness.result import result_line  # noqa: E402

DRIVERS = {"closed_loop": "chipbench.harness.single",
           "open_loop": "chipbench.harness.serve"}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell, seed: int, seconds: float, trace: bool, devs,
             t_start: float, evaluate=None):
    """Set up, measure and check one cell; returns (record, correct,
    rows)."""
    import importlib
    driver = importlib.import_module(DRIVERS[cell.traffic["kind"]])
    rec = driver.run(cell, seed, seconds, trace, devs, t_start,
                     evaluate=evaluate)
    correct, rows = check.compare(rec.numbers, cell.config["limits"])
    return rec, correct, rows


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = cells.load_bench()
        cell = cells.resolve(bench, args.workload)
        session.add_program_to_path()
    except (OSError, KeyError, ValueError) as e:
        print(f"chipbench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    try:
        devs = session.devices(cell.chips)
    except session.NoChip as e:
        print(f"chipbench: no chip: {e}", file=sys.stderr)
        return 3
    session.enable_compile_cache()
    rec, correct, rows = run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), devs, T_START)
    line = result_line(rec, cell, bool(args.trace), correct, rows)
    for s in check.report_lines(rows):
        print(s, file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

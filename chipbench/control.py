"""Readings for the limits of ``correct``: the program's numbers and the
control's, on several seeds, in one process on the chip.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \
        [--seconds S] [--dtype bfloat16]

For each seed it runs the cell as ``run.py`` does (a short window) and
prints the program's numbers, then the control's: for ``closed_loop``
traffic the reference Ant System at the cell's size in ``--dtype``
(``harness/control.py``), for ``open_loop`` traffic the same run's served
tours with their lengths summed in bfloat16.  Each reading is one JSON
line; the benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import run  # noqa: E402
from chipbench.harness import cells, check, generator, session  # noqa: E402


def control_numbers(cell: cells.Cell, seed: int, dtype: str,
                    fault=None) -> dict:
    from chipbench.harness import control, single
    conf = cell.config
    if conf["variant"] != "as":
        raise ValueError("the reference colony is Ant System only; "
                         f"no control for variant {conf['variant']!r}")
    if cell.traffic["kind"] == "closed_loop":
        req = generator.single_instance(conf, seed)
        answer, deposit = control.reference_colony(
            req.coords, conf["instance"]["edge_weight_type"],
            int(conf["m"]), float(conf["alpha"]), float(conf["beta"]),
            float(conf["rho"]), float(conf.get("q", 1.0)),
            int(cell.traffic["iterations"]), int(conf["service"]["chunk"]),
            seed, dtype, fault)
        return single.numbers([answer], deposit, conf)
    raise ValueError("open_loop controls are read from the run's answers")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--fault", default=None,
                    help="plant a fault in the reference (harness/control."
                         "FAULTS) instead of lowering its precision")
    ap.add_argument("--program", type=int, choices=(0, 1), default=1,
                    help="also run the program on each seed")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes (the rehearse overrides)")
    args = ap.parse_args(argv)
    cell = cells.resolve(cells.load_bench(), args.workload)
    if args.rehearse:
        from chipbench import rehearse
        cell = rehearse.tiny(cell)
    session.add_program_to_path()
    devs = session.devices(cell.chips, allow_cpu=args.rehearse)
    session.enable_compile_cache()
    limits = cell.config["limits"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        if cell.traffic["kind"] == "open_loop":
            from chipbench.harness import control
            seen = {}

            def evaluate(answers, conf):
                seen["control"] = check.served_numbers(
                    answers, lengths=control.bf16_length, conf=conf)
                seen["random_tour"] = check.served_numbers(
                    [control.random_tour(a, seed + i)
                     for i, a in enumerate(answers)], conf=conf)
                return check.served_numbers(answers, conf=conf)
            rec, ok, rows = run.run_cell(cell, seed, args.seconds, False,
                                         devs, session.now(), evaluate)
            print(json.dumps({"seed": seed, "who": "program", "correct": ok,
                              "numbers": rec.numbers,
                              "e2e": rec.e2e}), flush=True)
            cok, _ = check.compare(
                dict(rec.numbers, **seen["control"]), limits)
            print(json.dumps({"seed": seed, "who": "control",
                              "correct": cok,
                              "numbers": seen["control"]}), flush=True)
            print(json.dumps({"seed": seed, "who": "fault-random_tour",
                              "numbers": seen["random_tour"]}), flush=True)
            continue
        if args.program:
            rec, ok, rows = run.run_cell(cell, seed, args.seconds, False,
                                         devs, session.now())
            print(json.dumps({"seed": seed, "who": "program", "correct": ok,
                              "numbers": rec.numbers, "e2e": rec.e2e}),
                  flush=True)
        t = time.perf_counter()
        nums = control_numbers(cell, seed, args.dtype, args.fault)
        cok, _ = check.compare(nums, limits)
        who = f"fault-{args.fault}" if args.fault else f"control-{args.dtype}"
        print(json.dumps({"seed": seed, "who": who,
                          "correct": cok, "numbers": nums,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

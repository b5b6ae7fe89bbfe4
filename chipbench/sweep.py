"""Find an open-loop cell's knee: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload <name> --rates 4,6,8 \
        [--seconds 8] [--seed N]

One process sets the cell's service up once, then offers each rate in turn
(a warm-up of 3 s, a window of ``--seconds``, a drain until the window's
requests are back) and prints one JSON line per rate.  A rate is sustained
when the window's completions keep pace with its arrivals (at least 0.95
of them), nothing is missing, and the backlog does not grow (the median
latency of the window's last third is at most 1.5 times the first
third's, plus 0.2 s).  The last line names the knee and 0.8 of it, the
rate a cell below the knee is offered.
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

import numpy as np  # noqa: E402

from chipbench.harness import cells, generator, serve, session  # noqa: E402


def sustained(row: dict) -> bool:
    return (row["solved_per_s"] >= 0.95 * row["offered"]
            and row["missing"] == 0
            and row["p50_last_third"] <= 1.5 * row["p50_first_third"] + 0.2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=777)
    args = ap.parse_args(argv)
    cell = cells.resolve(cells.load_bench(), args.workload)
    session.add_program_to_path()
    try:
        devs = session.devices(cell.chips)
    except session.NoChip as e:
        print(f"chipbench: no chip: {e}", file=sys.stderr)
        return 3
    session.enable_compile_cache()
    svc = serve.build(cell, devs, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dict(cell.traffic, rate_per_s=rate, warm_s=3.0,
                       drain_s=30.0)
        sched = generator.open_loop_schedule(traffic, args.seed,
                                             args.seconds)
        s = serve.offer(svc, traffic, sched, args.seconds)
        win = np.asarray([r.index for r in sched if r.phase == "window"])
        lat = s.done_t[win] - s.due[win]
        third = max(1, len(win) // 3)
        row = {"rate": rate, "offered": len(win) / args.seconds,
               **serve.window_metrics(s, win, args.seconds, 30.0),
               "missing": int(np.isnan(s.done_t[win]).sum()),
               "p50_first_third": float(np.nanmedian(lat[:third])),
               "p50_last_third": float(np.nanmedian(lat[-third:])),
               "compiles": s.compiles}
        row["sustained"] = sustained(row)
        rows.append(row)
        print(json.dumps(row), flush=True)
        svc.run_until_drained(max_steps=500)
    ok = [r["rate"] for r in rows if r["sustained"]]
    knee = max(ok) if ok else None
    print(json.dumps({"knee": knee,
                      "rate_0.8": None if knee is None else
                      round(0.8 * knee, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

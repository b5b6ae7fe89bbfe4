"""Telemetry overhead: streaming throughput at three observability levels.

The acceptance bar for the telemetry fabric (DESIGN.md §13) is that full
telemetry costs ~nothing: the in-jit metrics are a handful of reductions
fused into an already-compiled chunk program, and the host-side events /
spans are bounded deque appends.  This benchmark replays the same Poisson
arrival trace through the StreamingSolverService at:

- ``off``     metrics off, in-memory telemetry only (the always-on
              bounded instruments every service run pays — the baseline);
- ``events``  metrics off, plus the JSON-lines event log mirrored to a
              file as records arrive (the --events-out path);
- ``full``    ``cfg.metrics=True`` (in-jit StepMetrics rows ride the
              resident state, every result carries a metrics row) plus
              the event-log file mirror and periodic stats snapshots;
- ``serving`` everything in ``full`` plus the serving observability
              plane (DESIGN.md §14): per-request tenant labels feeding
              the SLO tracker, and a live ``/metrics`` endpoint being
              scraped concurrently while the trace replays.

Each level replays best-of-``REPS`` (min wall) to damp scheduler noise;
the summary reports full/off and serving/off throughput and whether
each holds the <=5% overhead bar.  Emits ``BENCH_obs.json`` at the repo
root (``--smoke`` writes no JSON unless ``--out`` is given).

    PYTHONPATH=src python benchmarks/obs_overhead.py [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import tempfile
import threading
import time
import urllib.request

import numpy as np

from repro import obs
from repro.core import aco
from repro.solver import StreamingSolverService, streaming

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(_ROOT, "BENCH_obs.json")

CASE = dict(bucket=32, slots=4, requests=24, min_n=17, max_n=32,
            iters=(4, 4, 4, 24) * 5 + (4,) * 4, chunk=4, seed=0,
            pressure=0.2)
SMOKE_CASE = dict(bucket=32, slots=4, requests=12, min_n=17, max_n=32,
                  iters=(3, 3, 3, 15) * 2 + (3,) * 4, chunk=3, seed=0,
                  pressure=0.2)

REPS = 3
LEVELS = ("off", "events", "full", "serving")
TENANTS = ("tenant-a", "tenant-b")
SCRAPE_EVERY_S = 0.05


def _make_trace(case, rate: float) -> list[streaming.TraceItem]:
    return streaming.make_poisson_trace(
        case["requests"], rate, case["min_n"], case["max_n"],
        seed=case["seed"], iterations=case["iters"])


def _cfg(case, level: str) -> aco.ACOConfig:
    return aco.ACOConfig(iterations=max(case["iters"]), selection="gumbel",
                         metrics=(level in ("full", "serving")))


def _service(case, level: str, events_path: str) -> StreamingSolverService:
    tel = obs.Telemetry(
        events_path=events_path if level != "off" else None)
    return StreamingSolverService(
        _cfg(case, level), max_batch=case["slots"],
        min_bucket=case["bucket"], chunk=case["chunk"], telemetry=tel,
        snapshot_every=0.05 if level in ("full", "serving") else 0.0)


def _scraper(url: str, stop: threading.Event) -> threading.Thread:
    """Background thread hammering ``/metrics`` while the trace replays,
    so the serving level pays realistic concurrent-scrape cost."""
    def loop():
        while not stop.is_set():
            try:
                urllib.request.urlopen(url, timeout=1.0).read()
            except OSError:
                pass
            stop.wait(SCRAPE_EVERY_S)
    t = threading.Thread(target=loop, name="obs-bench-scraper", daemon=True)
    t.start()
    return t


def _warm(case, tmp: str) -> float:
    """Compile-warm both chunk programs (metrics on and off are distinct
    compiled shapes) and return the busy wall time for rate calibration."""
    probe = _make_trace(case, rate=1e9)
    busy = None
    for level in ("off", "full"):
        svc = _service(case, level, os.path.join(tmp, f"warm_{level}.jsonl"))
        for k, t in enumerate(probe):
            svc.submit(t.instance, iterations=t.iterations, seed=t.seed)
        t0 = time.perf_counter()
        svc.run_until_drained()
        wall = time.perf_counter() - t0
        if level == "off":
            busy = wall
        svc.tel.close()
    return busy


def run_case(case) -> list[dict]:
    tmp = tempfile.mkdtemp(prefix="obs_overhead_")
    busy_s = _warm(case, tmp)
    rate = case["requests"] / max(case["pressure"] * busy_s, 1e-3)
    trace = _make_trace(case, rate)

    # serving level: identical instances/seeds/budgets, plus tenant
    # labels (pure observability metadata — results must not change)
    serving_trace = [dataclasses.replace(t, tenant=TENANTS[i % len(TENANTS)])
                     for i, t in enumerate(trace)]

    rows = []
    for level in LEVELS:
        best = None
        for rep in range(REPS):
            svc = _service(case, level,
                           os.path.join(tmp, f"{level}_{rep}.jsonl"))
            server = stop = None
            if level == "serving":
                server = obs.MetricsServer(svc.tel, health_fn=svc.health,
                                           port=0)
                stop = threading.Event()
                _scraper(server.url("/metrics"), stop)
            t0 = time.perf_counter()
            res = streaming.replay_trace(
                svc, serving_trace if level == "serving" else trace)
            wall = time.perf_counter() - t0
            if server is not None:
                stop.set()
                server.close()
            svc.tel.close()
            assert len(res) == case["requests"]
            if level in ("full", "serving"):
                assert all(r.metrics is not None for r in res)
            if best is None or wall < best[1]:
                best = (res, wall, svc.stats["occupancy_mean"])
        res, wall, occ = best
        lat = [r.latency_s for r in res]
        rows.append({
            "level": level, "requests": len(res),
            "wall_s": round(wall, 4),
            "ips": round(len(res) / wall, 3),
            "lat_mean_s": round(float(np.mean(lat)), 4),
            "lat_p95_s": round(float(np.percentile(lat, 95)), 4),
            "occupancy_mean": round(occ, 4),
        })
    return rows


def main(case=CASE, out_path: str | None = DEFAULT_OUT):
    print("telemetry overhead on the streaming service "
          f"(bucket={case['bucket']}, slots={case['slots']}, "
          f"requests={case['requests']})")
    rows = run_case(case)
    hdr = list(rows[0])
    print(",".join(hdr))
    for r in rows:
        print(",".join(str(r[k]) for k in hdr))
    off = next(r for r in rows if r["level"] == "off")
    full = next(r for r in rows if r["level"] == "full")
    serving = next(r for r in rows if r["level"] == "serving")
    ratio = full["ips"] / off["ips"]
    sratio = serving["ips"] / off["ips"]
    summary = {
        "full_vs_off_ips": round(ratio, 4),
        "overhead_pct": round(100.0 * (1.0 - ratio), 2),
        "within_5pct": ratio >= 0.95,
        "serving_vs_off_ips": round(sratio, 4),
        "serving_overhead_pct": round(100.0 * (1.0 - sratio), 2),
        "within_5pct_serving": sratio >= 0.95,
    }
    print(f"full/off throughput: {summary['full_vs_off_ips']}x "
          f"({summary['overhead_pct']}% overhead; "
          f"<=5% bar {'held' if summary['within_5pct'] else 'MISSED'})")
    print(f"serving/off throughput: {summary['serving_vs_off_ips']}x "
          f"({summary['serving_overhead_pct']}% overhead; "
          f"<=5% bar {'held' if summary['within_5pct_serving'] else 'MISSED'})")
    if out_path:
        payload = {
            "benchmark": "obs_overhead",
            "schema": 1,
            "unix_time": int(time.time()),
            "case": {k: v for k, v in case.items()},
            "rows": rows,
            "summary": summary,
        }
        with open(out_path, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {os.path.abspath(out_path)}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small fast case, no JSON unless --out")
    ap.add_argument("--out", default=None,
                    help=f"output JSON path (default: {DEFAULT_OUT})")
    args = ap.parse_args()
    if args.smoke:
        main(SMOKE_CASE, args.out)
    else:
        main(CASE, args.out or DEFAULT_OUT)

"""Driver for ``open_loop`` traffic: a routing API under a fixed offered rate.

Set-up builds the streaming service (one pool per bucket per chip), warms
the chunk programs ahead of time, then runs ladder rounds that put k
requests on every pool at once for k = 1 .. slots, so every refill and
harvest shape is compiled before the window, on every chip.  Then the
schedule's warm-up phase is offered at the cell's rate, so the pools are
in steady state when the window opens.

The loop submits every request whose due time has passed, then steps the
service once; with nothing in the system it sleeps until the next due
time.  Latency runs from a request's due time in the schedule to the end
of the step that harvested it.  After the window closes the schedule goes
on (the drain phase) until every request due in the window has come back,
at most ``drain_s`` seconds.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Optional

import numpy as np

from . import cells, check, generator, phases, session
from .result import RunRecord


@dataclasses.dataclass
class Served:
    """What the loop saw of one schedule: per request, when it was due,
    submitted and harvested (perf_counter seconds), and its result."""
    due: np.ndarray
    submit_t: np.ndarray
    done_t: np.ndarray
    results: dict
    rid_to_idx: dict
    t0: float
    t1: float
    trace_span: tuple
    summary: object
    compiles: int
    data: object = None         # the capture, read again after the drain


def build(cell: cells.Cell, devs, seed: int):
    """The warmed service: AOT chunk programs, then every pool of every
    bucket through refills and harvests of 1 .. slots requests at once."""
    from repro.solver import placement
    from repro.solver import programs as programs_mod
    from repro.solver import streaming

    conf, traffic = cell.config, cell.traffic
    svc_conf = conf["service"]
    slots = int(svc_conf["max_batch"])
    min_bucket = int(svc_conf["min_bucket"])
    budget = int(traffic["iterations"])
    cfg = cells.aco_config(conf, iterations=budget)
    mesh = placement.data_mesh(len(devs)) if len(devs) > 1 else None
    svc = streaming.StreamingSolverService(
        cfg, max_batch=slots, min_bucket=min_bucket,
        chunk=int(svc_conf["chunk"]), mesh=mesh,
        programs=programs_mod.ProgramCache())
    buckets = generator.buckets_for(traffic, min_bucket)
    svc.warm_programs(0, 0, ladder=buckets)
    # A round takes ceil(budget / chunk) steps; a program that never
    # finishes a request still leaves set-up, and the check then fails it.
    steps = 2 * -(-budget // int(svc_conf["chunk"]))
    for rnd in generator.warm_ladder_requests(traffic, buckets, len(devs),
                                              slots, seed):
        for r in rnd:
            svc.submit(cells.instance(r.coords, traffic["edge_weight_type"],
                                      "ladder"),
                       iterations=budget, seed=r.solver_seed)
        svc.run_until_drained(max_steps=steps)
    return svc


def offer(svc, traffic: dict, schedule: list, seconds: float,
          trace: bool = False) -> Served:
    """Offer the schedule open loop, from its warm-up phase through the
    window and until every request due in the window has come back (or
    ``drain_s`` after the close).  With ``trace``, the first ``trace_s``
    of the window is captured by the profiler."""
    budget = int(traffic["iterations"])
    ewt = traffic["edge_weight_type"]
    insts = [cells.instance(r.coords, ewt, f"r{r.index}") for r in schedule]
    warm_s = float(traffic["warm_s"])
    drain_s = float(traffic["drain_s"])
    base = session.now()
    start = {"warm": base, "window": base + warm_s,
             "drain": base + warm_s + seconds}
    due = np.asarray([start[r.phase] + r.due for r in schedule])
    t0, t1 = start["window"], start["drain"]
    trace_s = min(seconds, float(traffic["trace_s"]))
    submit_t = np.full(len(schedule), np.nan)
    done_t = np.full(len(schedule), np.nan)
    rid_to_idx: dict[int, int] = {}
    results: dict[int, object] = {}
    left = {r.index for r in schedule if r.phase == "window"}

    counter = session.CompileCounter()
    capture = session.Capture() if trace else None
    traced: Optional[object] = None
    tr0 = tr1 = None
    summary = data = None
    nxt = 0
    while True:
        now = session.now()
        if capture is not None and tr0 is None and now >= t0:
            capture.start()
            traced = session.span("bench.traced")
            traced.__enter__()
            tr0 = session.now()
        elif traced is not None and now >= tr0 + trace_s:
            traced.__exit__(None, None, None)
            traced, tr1 = None, session.now()
            summary, data = _reduce(capture)
        counter.on = t0 <= now < t1
        if nxt < len(schedule) and due[nxt] <= now:
            with session.span("bench.submit"):
                while nxt < len(schedule) and due[nxt] <= now:
                    rid = svc.submit(insts[nxt], iterations=budget,
                                     seed=schedule[nxt].solver_seed)
                    submit_t[nxt] = session.now()
                    rid_to_idx[rid] = nxt
                    nxt += 1
        if now >= t1 and (not left or now >= t1 + drain_s):
            break
        if svc.busy:
            with session.span("bench.step", resident=svc.resident):
                out = svc.step()
            t_done = session.now()
            for res in out:
                i = rid_to_idx.get(res.request_id)
                if i is None:           # a set-up request, back late
                    continue
                done_t[i] = t_done
                results[i] = res
                left.discard(i)
        elif nxt < len(schedule):
            with session.span("bench.wait"):
                time.sleep(max(0.0, min(due[nxt] - session.now(), 0.05)))
        else:
            break
    counter.on = False
    if traced is not None:
        traced.__exit__(None, None, None)
        tr1 = session.now()
        summary, data = _reduce(capture)
    counter.close()
    return Served(due=due, submit_t=submit_t, done_t=done_t,
                  results=results, rid_to_idx=rid_to_idx, t0=t0, t1=t1,
                  trace_span=(tr0, tr1), summary=summary,
                  compiles=counter.count, data=data)


def _reduce(capture):
    """Stop the capture as soon as the traced part ends (a long capture
    overflows the device's trace buffers) and reduce it; returns the
    summary and the capture's data."""
    from . import xplane
    data = capture.stop()
    summary = xplane.reduce(xplane.extract(data))
    capture.cleanup()
    return summary, data


def window_metrics(s: Served, window_idx: np.ndarray, seconds: float,
                   drain_s: float) -> dict:
    """End-to-end metrics of one window: completions in it over its
    length, and latency quantiles over every request due in it (one
    that never came back counts with the latency it had at the end)."""
    done = s.done_t[window_idx]
    lat = np.where(np.isnan(done), (s.t1 + drain_s) - s.due[window_idx],
                   done - s.due[window_idx])
    completed = int(((s.done_t >= s.t0) & (s.done_t < s.t1)).sum())
    return {"solved_per_s": completed / seconds,
            "latency_p50_s": float(np.quantile(lat, 0.5)),
            "latency_p95_s": float(np.quantile(lat, 0.95))}


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, devs,
        t_start: float, evaluate=None) -> RunRecord:
    traffic = cell.traffic
    budget = int(traffic["iterations"])
    svc = build(cell, devs, seed)
    ladder_left = svc.waiting + svc.resident
    schedule = generator.open_loop_schedule(traffic, seed, seconds)
    s = offer(svc, traffic, schedule, seconds, trace)
    win = np.asarray([r.index for r in schedule if r.phase == "window"],
                     int)
    warm = np.asarray([r.index for r in schedule if r.phase == "warm"], int)

    rec = RunRecord(device=session.device_record(devs))
    rec.setup_s = s.t0 - t_start
    rec.window_s = seconds
    rec.e2e = window_metrics(s, win, seconds, float(traffic["drain_s"]))
    rec.attempted = len(win)
    rec.failed = int(np.isnan(s.done_t[win]).sum())
    rec.memory_peak_bytes = session.memory_peak_bytes(devs)

    events = svc.tel.events.records()
    tr0, tr1 = s.trace_span if s.trace_span[0] is not None else (s.t0, s.t1)
    sub_in = (s.submit_t >= tr0) & (s.submit_t < tr1)
    # queue waits of the requests admitted in the traced part (the stop of
    # the capture blocks the loop, so later admissions wait for it)
    waits = [ev["wait_s"] for ev in events
             if ev["kind"] == "admit" and ev["request_id"] in s.rid_to_idx
             and tr0 <= s.submit_t[s.rid_to_idx[ev["request_id"]]]
             + ev["wait_s"] < tr1]
    rec.layer_ctx = {
        "summary": s.summary, "chips": len(devs),
        "gen_late_s": list((s.submit_t - s.due)[sub_in]),
        "queue_wait_s": waits,
        "completed": int(((s.done_t >= tr0) & (s.done_t < tr1)).sum()),
        "window_compiles": s.compiles,
        **phases.layer_ctx(s.data, [svc.programs]),
    }

    # ---- answers of every request due in the window; then free the state
    win_set = set(win.tolist())
    devices_used = {ev["device"] for ev in events
                    if ev["kind"] == "harvest"
                    and s.rid_to_idx.get(ev["request_id"]) in win_set}
    misplaced = 0
    if svc.mesh is not None:
        for pools in svc._pools.values():      # private: pool placement
            for pool in pools:
                leaves = [pool.problem.dist, pool.states.tau,
                          pool.budgets, pool.since]
                if any(set(x.devices()) != {pool.device} for x in leaves):
                    misplaced += 1
    answers = [{"coords": schedule[i].coords,
                "edge_weight_type": traffic["edge_weight_type"],
                "tour": s.results[i].best_tour,
                "best_len": s.results[i].best_len,
                "iterations": s.results[i].iterations, "budget": budget}
               for i in win.tolist() if i in s.results]
    del svc, events
    nums = (evaluate or check.served_numbers)(answers, conf=cell.config)
    # every request due before the window closed is answered: those of
    # the window by the end of the drain, and those of set-up too
    nums["missing"] = float(rec.failed + ladder_left
                            + int(np.isnan(s.done_t[warm]).sum()))
    nums["devices_idle"] = float(len(devs) - len(devices_used))
    nums["misplaced"] = float(misplaced)
    rec.numbers = nums
    return rec

"""Read the program's own phases off a traced run of one cell.

    python3 chipbench/phases.py --workload <name> --seed <n> --seconds <s> \
        [--span-cost] [--fixture PATH] [--cpu]

The run is the one ``run.py --trace 1`` makes (the same set-up, window,
capture, reduction by ``harness/xplane.py`` and check), so its end-to-end
numbers are those of a traced run.  It reads the capture's phases that
the per-layer readers read (``harness/phases.py``: the solver's ``aco.*``
host spans and the colony step's named scopes, from the text of the
cell's compiled programs), further than the readers do.  The last line
of standard output is one JSON object: the traced run's end-to-end
numbers and per-layer metrics, the breakdown with the ``aco.*`` spans
painted in, device time per colony scope (ms per iteration where the
cell counts iterations) and what lies outside every scope, device idle
within each host phase's self time as a share of the resident time, and
slot occupancy and padding fill over the chunk dispatches.

``--span-cost`` first times one ``Tracer.span`` with no capture running
and inside a capture.  ``--fixture`` writes a 10 ms piece of the capture
in the format of ``chipbench/tests/data``, chosen to hold as many colony
scopes and host phases as a 10 ms piece can.  ``--cpu`` rehearses at the
cell's ``rehearse`` size on the CPU (no device metric).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

if "--cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

from chipbench import run  # noqa: E402
from chipbench.harness import cells, phases, session, xplane  # noqa: E402

SLICE_NS = 10_000_000


def span_cost(n: int = 20000) -> dict:
    """Microseconds per ``Tracer.span`` (and per bare profiler annotation)
    with no capture running and inside one."""
    import jax
    from repro import obs

    def per_span():
        t = obs.Tracer(max_events=n)
        t0 = time.perf_counter()
        for _ in range(n):
            with t.span("cost", process="dev0", thread="b256", occupied=8,
                        slots=8, bucket=256, cities=1200, chunk=4):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    def per_annotation():
        t0 = time.perf_counter()
        for _ in range(n):
            with jax.profiler.TraceAnnotation("aco.cost", occupied=8,
                                              slots=8, bucket=256,
                                              cities=1200, chunk=4):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    out = {"span_us_no_capture": per_span(),
           "annotation_us_no_capture": per_annotation()}
    cap = session.Capture(directory=session.TRACE_DIR + "-cost")
    cap.start()
    out["span_us_capture"] = per_span()
    out["annotation_us_capture"] = per_annotation()
    cap.stop()
    cap.cleanup()
    return out


def outside_scopes(summary, ph: phases.Phases, k: int = 8) -> list[list]:
    """Device time in the window outside every colony scope, by operation
    (seconds per device, with its op_name path), longest first."""
    lo, hi = summary.window_ns
    scopes = ph.scopes()
    tot: dict[tuple, float] = {}
    for d, o in ph.ops.items():
        per = scopes.get(d, {})
        inside = xplane.union(np.concatenate(
            [per[sc] for sc in per] or [np.zeros((0, 2))]), lo, hi)
        for (s, e), name, path, sc in zip(o.iv, o.names, o.paths,
                                          o.scopes):
            if sc is not None:
                continue
            iv = np.asarray([[max(s, lo), min(e, hi)]], np.float64)
            if iv[0, 1] <= iv[0, 0]:
                continue
            free = xplane.length(iv) - xplane.length(
                xplane.intersect(iv, inside))
            if free > 0:
                key = (name, path or "")
                tot[key] = tot.get(key, 0.0) + free
    nd = max(1, len(ph.ops))
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, path, ns * 1e-9 / nd] for (name, path), ns in top]


def numbers(summary, ph: phases.Phases, iterations) -> dict:
    """The phase numbers of one reduced capture."""
    out: dict = {}
    scopes = ph.scopes()
    busy = phases.scope_busy_s(summary, scopes)
    out["scope_busy_s"] = busy
    if iterations:
        out["scope_device_ms_per_iter"] = {
            sc: v * 1e3 / iterations for sc, v in busy.items()}
        out["iter_device_ms"] = summary.busy_s * 1e3 / iterations
    out["scoped_share"] = phases.scoped_share(summary, scopes)
    idle = phases.phase_idle(summary)
    out["phase_idle"] = idle
    if idle is not None:
        out["resident_idle"] = summary.idle_share_within(
            summary.resident_intervals())
        out["phase_idle_sum"] = sum(idle.values())
    occ, fill = phases.dispatch_fill(summary)
    out["slot_occupancy"], out["pad_fill"] = occ, fill
    lo, hi = summary.window_ns
    counts: dict[str, int] = {}
    for name, s, _, _ in phases.program_spans(summary):
        if lo <= s < hi:
            counts[name] = counts.get(name, 0) + 1
    out["spans_in_window"] = counts
    return out


def _piece(summary, ph: phases.Phases, a: float) -> xplane.Trace:
    b = a + SLICE_NS
    device_ops, op_ns = {}, {}
    for d, o in ph.ops.items():
        keep = (o.iv[:, 1] > a) & (o.iv[:, 0] < b)
        device_ops[d] = (o.iv[keep], [n for n, k in zip(o.names, keep)
                                      if k])
        tot: dict[str, float] = {}
        for (s, e), n in zip(o.iv[keep], device_ops[d][1]):
            tot[n] = tot.get(n, 0.0) + float(min(e, b) - max(s, a))
        op_ns[d] = tot
    spans = [(xplane.WINDOW_SPAN, int(a), int(b), {})] + [
        s for s in summary.spans
        if s[0] != xplane.WINDOW_SPAN and s[2] > a and s[1] < b]
    return xplane.Trace(device_ops=device_ops, spans=spans, op_ns=op_ns)


def fixture(summary, ph: phases.Phases, cell_name: str, device: dict
            ) -> dict:
    """The 10 ms piece of the capture that holds the most phases (colony
    scopes with device time, host phases with self time, and idle), as a
    recorded-trace fixture with the numbers its reduction gives."""
    lo, hi = summary.window_ns
    scopes = ph.scopes()
    starts = set()
    for per in scopes.values():
        for iv in per.values():
            for s, e in iv:
                for back in (1, 3, 5, 7):
                    starts.update((s - back * 1e6, e - back * 1e6))
    for _, s, _, _ in phases.program_spans(summary):
        starts.update((s - 1e6, s - 5e6))
    starts = sorted(a for a in starts if lo <= a <= hi - SLICE_NS) or [lo]

    def score(a):
        sub = xplane.reduce(_piece(summary, ph, a))
        parts = phases.self_time(phases.program_spans(sub))
        held = sum(1 for iv in parts.values()
                   if xplane.length(xplane.union(iv, *sub.window_ns)) > 0)
        return (len(phases.scope_busy_s(sub, scopes)) + held
                + (sub.idle_share > 0), -a)

    best = max(starts, key=score)
    trace = _piece(summary, ph, best)
    piece = xplane.reduce(trace)
    sub_scopes = {}
    for d, per in scopes.items():
        sub_scopes[d] = {sc: iv[(iv[:, 1] > best)
                                & (iv[:, 0] < best + SLICE_NS)]
                         for sc, iv in per.items()}
        sub_scopes[d] = {sc: iv for sc, iv in sub_scopes[d].items()
                         if len(iv)}
    obj = trace.to_json()
    obj["scopes"] = phases.scopes_to_json(sub_scopes)
    occ, fill = phases.dispatch_fill(piece)
    return {
        "source": (f"XLA Ops events of a trace of {cell_name} recorded on "
                   f"one {device.get('kind')}, 10 ms of the traced window, "
                   "with the solver's aco.* host spans and the colony "
                   "scopes of its operations; nested operations stand in "
                   "for program runs"),
        "trace": obj,
        "expect": {"busy_s": piece.busy_s, "idle_share": piece.idle_share},
        "expect_phases": {
            "scope_busy_s": phases.scope_busy_s(piece, sub_scopes),
            "phase_idle": phases.phase_idle(piece),
            "resident_idle": piece.idle_share_within(
                piece.resident_intervals()),
            "slot_occupancy": occ, "pad_fill": fill},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--span-cost", action="store_true")
    ap.add_argument("--fixture", default=None)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = cells.resolve(cells.load_bench(), args.workload)
    session.add_program_to_path()
    if args.cpu:
        from chipbench import rehearse
        cell = rehearse.tiny(cell)
    try:
        devs = session.devices(cell.chips, allow_cpu=args.cpu)
    except session.NoChip as e:
        print(f"chipbench: no chip: {e}", file=sys.stderr)
        return 3
    session.enable_compile_cache()
    out: dict = {"workload": args.workload, "seed": args.seed}
    if args.span_cost:
        out["span_cost"] = span_cost()

    rec, correct, _ = run.run_cell(cell, args.seed, args.seconds, True,
                                   devs, T_START)
    ctx = rec.layer_ctx
    summary = ctx.get("summary")
    out.update(correct=bool(correct), attempted=rec.attempted,
               device=rec.device, setup_s=rec.setup_s,
               traced_e2e=rec.e2e,
               per_layer={m["name"]: cells.reader(m["name"])(ctx)
                          for m in cell.per_layer})
    ph = ctx.get("phases")
    if summary is not None and ph is not None:
        out["busy_s"], out["window_s"] = summary.busy_s, summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(10),
                            "idle_gaps": summary.idle_gaps(12)}
        out.update(numbers(summary, ph, ctx.get("iterations")))
        out["outside_scopes"] = outside_scopes(summary, ph)
        if args.fixture:
            with open(args.fixture, "w") as f:
                json.dump(fixture(summary, ph, cell.name, rec.device), f)
    print(json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

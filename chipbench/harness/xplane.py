"""From a profiler trace to device busy time, idle share and a breakdown.

Two steps, kept apart so the second can be tested on a small recorded trace:

1. ``extract`` reads a ``jax.profiler.ProfileData`` into plain lists: the
   programs each device ran (start, end, name), each operation's device
   time in the window, and the host spans (``TraceAnnotation``s, with
   their arguments) of the benchmark (``bench.*``) and of the solver's
   streaming service (``aco.*``, read by ``phases.py``).
2. ``reduce`` turns those lists into the numbers the per-layer readers use.

Busy time is the union of the intervals in which a program ran on a
device (every operation runs inside one), clipped to the traced window
(the ``bench.traced`` span).  Idle is
the rest of the window.  A gap in a device's busy union is labelled by the
innermost host span that covers its midpoint, so the breakdown says what
the host was doing while the chip waited.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

# host spans kept: the benchmark's own, and the solver's (phases.PREFIX)
SPAN_PREFIXES = ("bench.", "aco.")
WINDOW_SPAN = "bench.traced"
WAIT_SPAN = "bench.wait"          # the host has no request to serve
# A TPU plane has one event per program run ("XLA Modules") and one per
# operation ("XLA Ops"), nested: the ops of a loop body lie inside the
# loop's own event.  Busy time is the union of the program runs; the
# breakdown sums operations by name.
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


@dataclasses.dataclass
class Trace:
    # device name -> (k, 2) int64 [start_ns, end_ns] of its program runs,
    # and k program names
    device_ops: dict[str, tuple[np.ndarray, list[str]]]
    # (name, start_ns, end_ns, args)
    spans: list[tuple[str, int, int, dict]]
    # device name -> {operation name: ns inside the traced window}
    op_ns: dict[str, dict[str, float]] = dataclasses.field(
        default_factory=dict)

    def to_json(self) -> dict:
        return {"device_ops": {d: {"iv": iv.tolist(), "names": names}
                               for d, (iv, names) in self.device_ops.items()},
                "spans": [list(s) for s in self.spans],
                "op_ns": self.op_ns}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(
            device_ops={d: (np.asarray(v["iv"], np.int64).reshape(-1, 2),
                            list(v["names"]))
                        for d, v in obj["device_ops"].items()},
            spans=[(s[0], int(s[1]), int(s[2]), dict(s[3]))
                   for s in obj["spans"]],
            op_ns=obj.get("op_ns", {}))


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def extract(data) -> Trace:
    """Program runs and operation totals of every device plane, and the
    ``bench.*`` and ``aco.*`` host spans, of a ProfileData."""
    spans: list[tuple[str, int, int, dict]] = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      {str(k): v for k, v in ev.stats}))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    lo, hi = (win[0][1], win[0][2]) if win else (-np.inf, np.inf)
    device_ops: dict[str, tuple[np.ndarray, list[str]]] = {}
    op_ns: dict[str, dict[str, float]] = {}
    for plane in data.planes:
        if not _is_device_plane(plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if MODULE_LINE not in lines:
            continue
        iv, names = [], []
        for ev in lines[MODULE_LINE].events:
            iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
            names.append(ev.name)
        device_ops[plane.name] = (
            np.asarray(iv, np.float64).astype(np.int64).reshape(-1, 2),
            names)
        tot: dict[str, float] = {}
        for ev in (lines[OP_LINE].events if OP_LINE in lines else ()):
            d = (min(ev.start_ns + ev.duration_ns, hi)
                 - max(ev.start_ns, lo))
            if d > 0:
                k = short_name(ev.name)
                tot[k] = tot.get(k, 0.0) + float(d)
        op_ns[plane.name] = tot
    return Trace(device_ops=device_ops, spans=spans, op_ns=op_ns)


def union(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Merged, sorted, disjoint intervals of ``iv`` clipped to [lo, hi]."""
    if len(iv) == 0:
        return np.zeros((0, 2), np.float64)
    iv = np.clip(np.asarray(iv, np.float64), lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if len(iv) == 0:
        return np.zeros((0, 2), np.float64)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    group = np.cumsum(new) - 1
    stops = np.zeros(len(starts))
    np.maximum.at(stops, group, iv[:, 1])
    return np.stack([starts, stops], axis=-1)


def length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval lists."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.float64).reshape(-1, 2)


def complement(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """[lo, hi] minus a disjoint sorted interval list."""
    out = []
    cur = lo
    for s, e in iv:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return np.asarray(out, np.float64).reshape(-1, 2)


@dataclasses.dataclass
class Summary:
    window_ns: tuple[float, float]
    busy: dict[str, np.ndarray]          # device -> busy union in window
    spans: list[tuple[str, int, int, dict]]
    trace: Trace

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def busy_s_per_device(self) -> dict[str, float]:
        return {d: length(iv) * 1e-9 for d, iv in self.busy.items()}

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the traced devices."""
        per = list(self.busy_s_per_device().values())
        return float(np.mean(per)) if per else 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def spans_named(self, name: str) -> list[tuple[str, int, int, dict]]:
        lo, hi = self.window_ns
        return [s for s in self.spans
                if s[0] == name and s[2] > lo and s[1] < hi]

    def wait_intervals(self) -> np.ndarray:
        iv = np.asarray([(s, e) for _, s, e, _ in self.spans_named(WAIT_SPAN)],
                        np.float64).reshape(-1, 2)
        return union(iv, *self.window_ns)

    def resident_intervals(self) -> np.ndarray:
        """The window less the spans in which the host had nothing to
        serve: the time in which some request was in the system."""
        return complement(self.wait_intervals(), *self.window_ns)

    def idle_share_within(self, iv: np.ndarray) -> Optional[float]:
        """Device idle share (mean over devices) over the intervals ``iv``;
        None when they have no length."""
        total = length(iv)
        if total <= 0 or not self.busy:
            return None
        busy = np.mean([length(intersect(b, iv)) for b in self.busy.values()])
        return 1.0 - float(busy) / total

    def top_ops(self, k: int = 10) -> list[list]:
        """Operations by device time in the window, in seconds per device
        (summed over devices and divided by their number).  Nested
        operations count inside their loop too."""
        tot: dict[str, float] = {}
        for ops in self.trace.op_ns.values():
            for name, ns in ops.items():
                tot[name] = tot.get(name, 0.0) + ns
        nd = max(1, len(self.trace.op_ns))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / nd] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Idle time in the window by the host span that covers each gap's
        midpoint (the innermost ``bench.*`` or ``aco.*`` span; ``no span``
        when none), summed over gaps and averaged over devices, longest
        first."""
        lo, hi = self.window_ns
        spans = [s for s in self.spans
                 if s[0] != WINDOW_SPAN and s[2] > lo and s[1] < hi]
        # Paint each gap's midpoint with the spans that cover it, longest
        # first, so the innermost span paints last.
        spans.sort(key=lambda s: -(s[2] - s[1]))
        labels = ["no span"] + [s[0] for s in spans]
        tot: dict[str, float] = {}
        for b in self.busy.values():
            gaps = complement(b, lo, hi)
            if not len(gaps):
                continue
            mids = 0.5 * (gaps[:, 0] + gaps[:, 1])
            order = np.argsort(mids)
            sm = mids[order]
            paint = np.zeros(len(mids), np.int64)
            for j, (_, s, e, _) in enumerate(spans):
                a, z = np.searchsorted(sm, [s, e], side="left")
                paint[order[a:z]] = j + 1
            for label_idx, g in zip(paint, gaps):
                name = labels[label_idx]
                tot[name] = tot.get(name, 0.0) + float(g[1] - g[0])
        nd = max(1, len(self.busy))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / nd] for name, ns in top]


def reduce(trace: Trace) -> Summary:
    """The traced window's summary over every device that ran a program."""
    win = [s for s in trace.spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = float(win[0][1]), float(win[0][2])
    names = [d for d, (iv, _) in trace.device_ops.items() if len(iv)]
    busy = {d: union(trace.device_ops[d][0], lo, hi) for d in names}
    return Summary(window_ns=(lo, hi), busy=busy, spans=trace.spans,
                   trace=trace)

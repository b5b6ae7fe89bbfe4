"""The program's own phases in a profiler capture: its ``aco.*`` host spans
and the colony step's named scopes on the device's operations.

The solver writes every host span of its streaming service (``step``,
``admit``, ``prep``, ``chunk_dispatch``, ``harvest``) into a live capture
as ``aco.<name>``, with its scalar arguments, and names the phases of a
colony iteration (``choice``, ``construct``, ``local_search``,
``deposit``) with ``jax.named_scope``, which XLA keeps in each
instruction's ``op_name`` metadata.  A TPU capture names each operation
(``%fusion.191 = ...``) but carries no ``op_name``, so the scope of an
operation is read from the text of the compiled module it ran in.  This
module reads both next to ``xplane``'s reduction, which it leaves as it
is:

1. ``extract`` reads a ``ProfileData`` and the compiled modules' text into
   the ``aco.*`` spans and the device's operations with the innermost
   colony scope of each; ``Phases.scopes`` gives each scope's busy union
   per device.
2. The helpers below turn a ``xplane.Summary`` whose spans include the
   ``aco.*`` ones, and those scope unions, into per-layer numbers: device
   time per scope, device idle within each span's self time, slot
   occupancy and padding fill.

Self time of a span is the span less its child ``aco.*`` spans.  The
program's spans come from one host thread and nest, so self times and the
time outside every ``aco.*`` span partition the window, and the idle
shares they carry add up to the resident idle share.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Optional

import numpy as np

from . import xplane

PREFIX = "aco."
SCOPES = ("choice", "construct", "local_search", "deposit")
OUTSIDE = "outside aco"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(path: str) -> Optional[str]:
    """The innermost colony scope of an ``op_name`` path, or None."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


@dataclasses.dataclass
class Ops:
    """One device's operations: (k, 2) [start_ns, end_ns], names, their
    ``op_name`` paths and the innermost colony scope of each (None where
    the capture has no path, or outside every scope)."""
    iv: np.ndarray
    names: list[str]
    paths: list[Optional[str]]
    scopes: list[Optional[str]]


@dataclasses.dataclass
class Phases:
    spans: list[tuple[str, int, int, dict]]      # the aco.* host spans
    ops: dict[str, Ops]                          # device -> operations

    def scopes(self) -> dict[str, dict[str, np.ndarray]]:
        """device -> {scope: busy union of its operations}."""
        out = {}
        for d, o in self.ops.items():
            out[d] = {}
            for sc in SCOPES:
                iv = o.iv[[s == sc for s in o.scopes]]
                if len(iv):
                    out[d][sc] = xplane.union(iv, -np.inf, np.inf)
        return out


_COMP = re.compile(r'^(?:ENTRY )?(%[\w.-]+) .*\{\s*$')
_INSTR = re.compile(r'^\s*(?:ROOT )?(%[\w.-]+) = ')
_CALLS = re.compile(r'\b(?:calls|to_apply)=(%[\w.-]+)')


def _computations(text: str) -> dict[str, list]:
    """Computation name -> its instructions as (name, own op_name path or
    None, names of the computations it calls)."""
    comps: dict[str, list] = {}
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMP.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
        elif line.strip() == "}":
            cur = None
        else:
            m = _INSTR.match(line)
            if m:
                p = _OP_NAME.search(line)
                cur.append((m.group(1), p.group(1) if p else None,
                            _CALLS.findall(line)))
    return comps


def hlo_paths(texts) -> dict[tuple[str, str], Optional[str]]:
    """(module name, instruction name) -> ``op_name`` path, from compiled
    modules' text.  An instruction with no path of its own (a fusion XLA
    built from several operations) takes the path of the scope most of
    the operations it calls belong to.  A name that means different paths
    in modules of one name maps to None."""
    out: dict[tuple[str, str], Optional[str]] = {}
    for text in texts:
        module = text.split(",", 1)[0].split()[-1]
        comps = _computations(text)
        memo: dict[str, Optional[str]] = {}

        def called(names: list) -> Optional[str]:
            paths = []
            for c in names:
                if c not in memo:
                    memo[c] = None          # a cycle reads as no path
                    inner = [p if p is not None else called(calls)
                             for _, p, calls in comps.get(c, ())]
                    memo[c] = _dominant([p for p in inner if p])
                if memo[c]:
                    paths.append(memo[c])
            return _dominant(paths)

        for instrs in comps.values():
            for name, path, calls in instrs:
                path = path if path is not None else called(calls)
                key = (module, name)
                if out.setdefault(key, path) != path:
                    out[key] = None
    return out


def _dominant(paths: list) -> Optional[str]:
    """The first path of the scope most paths fall in, else the first."""
    scoped = [p for p in paths if scope_of(p)]
    if not scoped:
        return paths[0] if paths else None
    counts: dict[str, int] = {}
    for p in scoped:
        counts[scope_of(p)] = counts.get(scope_of(p), 0) + 1
    best = max(counts, key=lambda sc: counts[sc])
    return next(p for p in scoped if scope_of(p) == best)


def _module_of(modules: list, starts: list, t: float) -> Optional[str]:
    """Name (less its ``(id)``) of the program run covering time ``t``;
    ``modules`` are a device's runs in start order, one at a time."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1]:
        return modules[i][2].split("(", 1)[0]
    return None


def extract(data, hlo_texts) -> Phases:
    """The ``aco.*`` host spans and every device operation with its
    colony scope, of a ProfileData; ``hlo_texts`` are the compiled
    modules the capture ran."""
    spans: list[tuple[str, int, int, dict]] = []
    ops: dict[str, Ops] = {}
    by_hlo = hlo_paths(hlo_texts)
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns),
                                      {str(k): v for k, v in ev.stats}))
        elif xplane._is_device_plane(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if xplane.OP_LINE not in lines:
                continue
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in (lines[xplane.MODULE_LINE].events
                           if xplane.MODULE_LINE in lines else ()))
            starts = [m[0] for m in modules]
            iv, names, paths = [], [], []
            for ev in lines[xplane.OP_LINE].events:
                name = xplane.short_name(ev.name)
                iv.append((ev.start_ns, ev.start_ns + ev.duration_ns))
                names.append(name)
                paths.append(by_hlo.get(
                    (_module_of(modules, starts, ev.start_ns), name)))
            ops[plane.name] = Ops(
                np.asarray(iv, np.float64).astype(np.int64).reshape(-1, 2),
                names, paths,
                [None if p is None else scope_of(p) for p in paths])
    spans.sort(key=lambda s: (s[1], -s[2]))
    return Phases(spans=spans, ops=ops)


def program_texts(caches) -> list[str]:
    """The text of every compiled program the ``ProgramCache``s hold (the
    cache keeps them private)."""
    return [c.as_text() for pc in caches for c in pc._programs.values()]


def layer_ctx(data, caches) -> dict:
    """What the per-layer readers take from a capture besides its
    summary: ``phases``, its spans and operations with their colony
    scopes read from the compiled programs of ``caches``, and ``scopes``,
    each device's busy union per scope; None for both without a
    capture."""
    if data is None:
        return {"phases": None, "scopes": None}
    ph = extract(data, program_texts(caches))
    return {"phases": ph, "scopes": ph.scopes()}


def scopes_to_json(scopes: dict[str, dict[str, np.ndarray]]) -> dict:
    return {d: {sc: iv.tolist() for sc, iv in per.items()}
            for d, per in scopes.items()}


def scopes_from_json(obj: dict) -> dict[str, dict[str, np.ndarray]]:
    return {d: {sc: np.asarray(iv, np.float64).reshape(-1, 2)
                for sc, iv in per.items()} for d, per in obj.items()}


# ------------------------------------------------------------ device time
def scope_busy_s(summary: xplane.Summary,
                 scopes: dict[str, dict[str, np.ndarray]]
                 ) -> dict[str, float]:
    """Seconds of the traced window in each scope's busy union, averaged
    over the devices that ran a program."""
    lo, hi = summary.window_ns
    out = {}
    for sc in SCOPES:
        per = [xplane.length(xplane.union(scopes.get(d, {}).get(
            sc, np.zeros((0, 2))), lo, hi)) for d in summary.busy]
        if any(per):
            out[sc] = float(np.mean(per)) * 1e-9
    return out


def scoped_share(summary: xplane.Summary,
                 scopes: dict[str, dict[str, np.ndarray]]
                 ) -> Optional[float]:
    """Share of the window's device busy time inside some colony scope
    (mean over devices); None when nothing ran."""
    lo, hi = summary.window_ns
    busy = np.mean([xplane.length(b) for b in summary.busy.values()]) \
        if summary.busy else 0.0
    if busy <= 0:
        return None
    inside = []
    for d in summary.busy:
        per = scopes.get(d, {})
        iv = np.concatenate([per[sc] for sc in per] or [np.zeros((0, 2))])
        inside.append(xplane.length(xplane.union(iv, lo, hi)))
    return float(np.mean(inside)) / busy


# ---------------------------------------------------------- host phases
def program_spans(summary: xplane.Summary
                  ) -> list[tuple[str, int, int, dict]]:
    return sorted((s for s in summary.spans if s[0].startswith(PREFIX)),
                  key=lambda s: (s[1], -s[2]))


def self_time(spans: list[tuple[str, int, int, dict]]
              ) -> dict[str, np.ndarray]:
    """Span name -> union of its spans' self time (each span less its
    child spans).  ``spans`` come from one thread and nest."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    children: list[list[tuple[float, float]]] = [[] for _ in spans]
    stack: list[int] = []
    for i, (_, s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            children[stack[-1]].append((s, min(e, spans[stack[-1]][2])))
        stack.append(i)
    pieces: dict[str, list[np.ndarray]] = {}
    for i, (name, s, e, _) in enumerate(spans):
        kids = xplane.union(np.asarray(children[i], np.float64)
                            .reshape(-1, 2), s, e)
        pieces.setdefault(name, []).append(xplane.complement(kids, s, e))
    return {name: xplane.union(np.concatenate(p), -np.inf, np.inf)
            for name, p in pieces.items()}


def phase_idle(summary: xplane.Summary) -> Optional[dict[str, float]]:
    """Device idle within each ``aco.*`` span name's self time, and
    outside every ``aco.*`` span (``OUTSIDE``), as shares of the resident
    time (the window less ``bench.wait``), averaged over devices.  The
    shares add up to ``summary.idle_share_within(resident)``."""
    res = summary.resident_intervals()
    total = xplane.length(res)
    if total <= 0 or not summary.busy:
        return None
    lo, hi = summary.window_ns
    spans = program_spans(summary)
    parts = self_time(spans)
    covered = xplane.union(np.asarray([(s, e) for _, s, e, _ in spans],
                                      np.float64).reshape(-1, 2), lo, hi)
    parts[OUTSIDE] = xplane.complement(covered, lo, hi)
    out = {name: 0.0 for name in parts}
    for b in summary.busy.values():
        idle = xplane.intersect(xplane.complement(b, lo, hi), res)
        for name, iv in parts.items():
            out[name] += xplane.length(xplane.intersect(
                idle, xplane.union(iv, lo, hi))) / total
    return {name: v / len(summary.busy) for name, v in out.items()}


def dispatch_fill(summary: xplane.Summary
                  ) -> tuple[Optional[float], Optional[float]]:
    """(slot occupancy, padding fill) over the ``aco.chunk_dispatch``
    spans that start in the window: sum of ``occupied`` over sum of
    ``slots``, and sum of ``cities`` over sum of ``occupied * bucket``."""
    lo, hi = summary.window_ns
    args = [a for name, s, _, a in summary.spans
            if name == PREFIX + "chunk_dispatch" and lo <= s < hi]
    slots = sum(float(a["slots"]) for a in args)
    padded = sum(float(a["occupied"]) * float(a["bucket"]) for a in args)
    occ = (sum(float(a["occupied"]) for a in args) / slots
           if slots else None)
    fill = sum(float(a["cities"]) for a in args) / padded if padded else None
    return occ, fill

"""Span timer, structured event log, and Chrome-trace (Perfetto) export.

Two host-side recording surfaces (DESIGN.md §13):

- ``Tracer`` — wall-clock spans on named (process, thread) tracks,
  exported as Chrome trace-event JSON (``{"traceEvents": [...]}``) that
  loads directly in Perfetto / ``chrome://tracing``.  The solver services
  map devices to processes and buckets / slots to threads, so a streaming
  run renders as per-device tracks of chunk dispatches with one span per
  resident request lifetime.  Every live span is also written to any
  running ``jax.profiler`` capture as ``aco.<name>`` (with its scalar
  args), on the profiler's host clock, the clock the device's operations
  are placed on: a capture shows which host phase each device gap falls
  in, whoever started the capture.
- ``EventLog`` — append-only JSON-lines records (``{"t": ..., "kind": ...,
  ...}``) for the slot lifecycle (submit → admit → chunk → harvest/evict)
  and periodic stats snapshots; greppable and cheap to tail.

Both are **bounded**: a fixed event capacity with an exact ``dropped``
count, so a long-lived service cannot leak memory through its own
observability (the same discipline registry.Histogram applies to
latency samples).

``profile_start``/``profile_stop`` wrap ``jax.profiler.start_trace``/
``stop_trace`` (``solve_serve --jax-profile-dir``).  Importing jax here
touches no device state.
"""
from __future__ import annotations

import json
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Optional

import jax

# Name prefix of the spans a live jax.profiler capture receives.
PROFILER_PREFIX = "aco."


def _scalars(args: dict) -> dict:
    return {k: v for k, v in args.items()
            if isinstance(v, (str, int, float))}


class Tracer:
    """Record spans on (process, thread) tracks."""

    def __init__(self, max_events: int = 200_000,
                 clock=time.perf_counter) -> None:
        self._clock = clock
        self._t0 = clock()
        self._events: deque[dict] = deque(maxlen=max_events)
        self._meta: list[dict] = []          # track-name metadata events
        self._pids: dict[str, int] = {}
        self._tids: dict[tuple[str, str], int] = {}
        self.dropped = 0

    # ------------------------------------------------------------- tracks
    def track(self, process: str = "main", thread: str = "main"
              ) -> tuple[int, int]:
        """Intern a (process, thread) pair into Chrome (pid, tid) ids and
        emit the name metadata the first time each is seen."""
        pid = self._pids.get(process)
        if pid is None:
            pid = self._pids[process] = len(self._pids)
            self._meta.append({"ph": "M", "name": "process_name",
                               "pid": pid, "tid": 0,
                               "args": {"name": process}})
        key = (process, thread)
        tid = self._tids.get(key)
        if tid is None:
            tid = self._tids[key] = sum(
                1 for (p, _) in self._tids if p == process)
            self._meta.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": tid,
                               "args": {"name": thread}})
        return pid, tid

    # -------------------------------------------------------------- clock
    def now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def to_us(self, t: float) -> float:
        """Convert a raw clock reading (same clock as this tracer's —
        time.perf_counter by default) to trace microseconds."""
        return (t - self._t0) * 1e6

    def _push(self, ev: dict) -> None:
        if len(self._events) == self._events.maxlen:
            self.dropped += 1
        self._events.append(ev)

    # ------------------------------------------------------------- events
    @contextmanager
    def span(self, name: str, process: str = "main", thread: str = "main",
             **args):
        """Complete-event span ("X") covering the with-block wall time.

        Yields the span's args dict: the block may add args known only at
        its end (a count of what it did), and they are recorded with it.

        While a ``jax.profiler`` capture runs, the block is also a host
        event ``aco.<name>`` in it, carrying the scalar args (ints, floats
        and strings); list args such as ``request_ids`` stay in the Chrome
        trace.  With no capture running the profiler sink costs one check
        (as a ``TraceMe`` does, a span that starts before the capture is
        not in it)."""
        pid, tid = self.track(process, thread)
        live = jax.profiler.TraceAnnotation.is_enabled()
        n = len(args)
        ann = (jax.profiler.TraceAnnotation(PROFILER_PREFIX + name,
                                            **_scalars(args))
               if live else nullcontext())
        ts = self.now_us()
        try:
            with ann:
                yield args
                if live and len(args) > n:     # args the block added
                    ann.set_metadata(**_scalars(dict(
                        list(args.items())[n:])))
        finally:
            self._push({"ph": "X", "name": name, "pid": pid, "tid": tid,
                        "ts": ts, "dur": self.now_us() - ts,
                        "args": args})

    def complete(self, name: str, ts_us: float, dur_us: float,
                 process: str = "main", thread: str = "main", **args) -> None:
        """Record an already-measured span (e.g. a slot's residency,
        stamped at harvest from its fill timestamp)."""
        pid, tid = self.track(process, thread)
        self._push({"ph": "X", "name": name, "pid": pid, "tid": tid,
                    "ts": ts_us, "dur": dur_us, "args": args})

    def request_chain(self, request_id) -> list[dict]:
        """Recover one request's span chain (DESIGN.md §14): every event
        whose args carry its ``request_id`` — the retroactive ``queued``
        span, the slot-residency span, each ``chunk_dispatch`` listing it
        resident — sorted by timestamp.  The same filter an operator runs
        in the Perfetto UI, as an API."""
        out = []
        for ev in self._events:
            args = ev.get("args") or {}
            if args.get("request_id") == request_id or \
                    request_id in (args.get("request_ids") or ()):
                out.append(ev)
        return sorted(out, key=lambda e: e.get("ts", 0.0))

    # ------------------------------------------------------------- export
    def to_chrome(self) -> dict:
        return {"traceEvents": self._meta + list(self._events),
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


class EventLog:
    """Bounded in-memory JSON-lines event record, optionally mirrored to a
    file as records arrive (line-buffered append)."""

    def __init__(self, path: Optional[str] = None,
                 max_records: int = 100_000) -> None:
        self._records: deque[dict] = deque(maxlen=max_records)
        self.dropped = 0
        self._fh = open(path, "a", buffering=1) if path else None

    def emit(self, kind: str, **fields) -> None:
        rec = {"t": time.time(), "kind": kind, **fields}
        if len(self._records) == self._records.maxlen:
            self.dropped += 1
        self._records.append(rec)
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")

    def records(self) -> list[dict]:
        return list(self._records)

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None


# --------------------------------------------------------- jax.profiler
def profile_start(log_dir: str) -> None:
    """Start a jax.profiler capture (XPlane/TensorBoard trace viewer)."""
    jax.profiler.start_trace(log_dir)


def profile_stop() -> None:
    jax.profiler.stop_trace()


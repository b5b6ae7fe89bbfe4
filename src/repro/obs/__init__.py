"""repro.obs — the solver telemetry fabric (DESIGN.md §13).

Three layers:

1. **In-jit metrics** (``metrics.StepMetrics``): a statically-gated
   pytree of per-iteration convergence scalars carried next to the
   ColonyState through every route; bitwise-neutral to the solve.
2. **Host-side spans + events** (``registry.Registry``, ``trace.Tracer``,
   ``trace.EventLog``): counters/gauges/bounded histograms the services'
   ``stats()`` read from, wall-clock spans on per-device/per-bucket
   tracks, and a JSON-lines slot-lifecycle event log.  The colony step
   names its phases with ``jax.named_scope`` (``choice``, ``construct``,
   ``local_search``, ``deposit``) in every device operation's metadata.
3. **Export surfaces**: Chrome-trace (Perfetto-loadable) timelines,
   ``repro.obs/v1`` metrics snapshots, and ``jax.profiler`` captures —
   every live span also lands in a running capture as ``aco.<name>`` on
   the device trace's clock — surfaced by ``launch.solve_serve
   --metrics-out/--trace-out/--events-out/--jax-profile-dir``.
4. **Serving plane** (``serving``, DESIGN.md §14): per-tenant SLO
   accounting (``SloTracker`` over labeled registry families), the
   Prometheus text renderer, and the ``MetricsServer`` background
   ``/metrics``+``/healthz``+``/snapshot`` endpoint — wired in by
   ``solve_serve --metrics-port``; ``validate`` holds the schema-level
   trace/event well-formedness checks tests and CI run.

``Telemetry`` bundles one registry + tracer + event log; services take an
optional instance and default to a private in-memory one, so telemetry is
always cheap and never required.
"""
from __future__ import annotations

from typing import Optional

from . import metrics, registry, serving, trace, validate
from .metrics import StepMetrics
from .registry import Registry
from .serving import MetricsServer, SloTracker, render_prometheus
from .trace import EventLog, Tracer

SCHEMA = "repro.obs/v1"


class Telemetry:
    """One run's bundled observability surfaces."""

    def __init__(self, events_path: Optional[str] = None,
                 max_events: int = 200_000,
                 jax_profile_dir: Optional[str] = None) -> None:
        self.registry = Registry()
        self.tracer = Tracer(max_events=max_events)
        self.events = EventLog(events_path, max_records=max_events)
        self.jax_profile_dir = jax_profile_dir
        self._profiling = False

    # ------------------------------------------------------- jax.profiler
    def profile_start(self) -> None:
        if self.jax_profile_dir and not self._profiling:
            trace.profile_start(self.jax_profile_dir)
            self._profiling = True

    def profile_stop(self) -> None:
        if self._profiling:
            trace.profile_stop()
            self._profiling = False

    # ------------------------------------------------------------ exports
    def snapshot(self, extra: Optional[dict] = None) -> dict:
        """The ``repro.obs/v1`` metrics snapshot (CLI ``--metrics-out``)."""
        out = {
            "schema": SCHEMA,
            "registry": self.registry.snapshot(),
            "events_dropped": self.events.dropped,
            "trace_dropped": self.tracer.dropped,
        }
        if extra:
            out.update(extra)
        return out

    def write_metrics(self, path: str, extra: Optional[dict] = None) -> None:
        import json
        with open(path, "w") as f:
            json.dump(self.snapshot(extra), f, indent=2, default=str)

    def write_trace(self, path: str) -> None:
        self.tracer.write(path)

    def close(self) -> None:
        self.profile_stop()
        self.events.close()


__all__ = ["Telemetry", "Registry", "Tracer", "EventLog", "StepMetrics",
           "MetricsServer", "SloTracker", "render_prometheus",
           "SCHEMA", "metrics", "registry", "serving", "trace", "validate"]

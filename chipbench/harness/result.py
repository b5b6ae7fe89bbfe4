"""One run's record and the result line it prints."""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Optional


@dataclasses.dataclass
class RunRecord:
    device: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    e2e: dict = dataclasses.field(default_factory=dict)
    numbers: dict = dataclasses.field(default_factory=dict)
    layer_ctx: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: Optional[int] = None


def _num(v: float) -> float:
    """JSON has no infinity: a number that could not be computed prints
    as 1e300, above any limit."""
    v = float(v)
    return v if math.isfinite(v) else 1e300


def result_line(rec: RunRecord, cell, trace: bool, correct: bool,
                rows: list[list]) -> str:
    from . import cells
    metrics = {}
    breakdown = None
    device = dict(rec.device)
    device["memory_peak_bytes"] = rec.memory_peak_bytes
    if trace:
        summary = rec.layer_ctx.get("summary")
        for m in cell.per_layer:
            v = cells.reader(m["name"])(rec.layer_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = {"device_ops": summary.top_ops(10),
                         "idle_gaps": summary.idle_gaps(10)}
    else:
        values = dict(rec.e2e, setup_s=rec.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(rec.attempted),
           "failed": int(rec.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {name: {"value": _num(v), "limit": lim}
                     for name, v, lim in rows}
    return json.dumps(out)

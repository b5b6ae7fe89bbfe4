"""Production mesh definitions.

Meshes are built by FUNCTIONS (never at import time) so importing this
module touches no jax device state — smoke tests keep seeing 1 CPU device;
only dryrun.py (which sets XLA_FLAGS first) materialises 256/512 devices.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_data_mesh(devices: int | None = None) -> Mesh:
    """1-D instance-sharding mesh over the host's first ``devices``
    accelerators — the solver fabric's topology (DESIGN.md §11): the
    placement layer (solver/placement.py) shards batch jobs' instance
    axes over its ``data`` axis, and the streaming service places one
    resident pool per device."""
    from repro.solver.placement import data_mesh
    return data_mesh(devices)


def make_mesh_for(devices: int | None = None, model_parallel: int = 1,
                  pods: int = 1) -> Mesh:
    """Elastic mesh: whatever devices exist, factored (pods, dp, mp)."""
    n = devices or len(jax.devices())
    assert n % (model_parallel * pods) == 0, (n, model_parallel, pods)
    dp = n // (model_parallel * pods)
    if pods > 1:
        return jax.make_mesh((pods, dp, model_parallel),
                             ("pod", "data", "model"))
    return jax.make_mesh((dp, model_parallel), ("data", "model"))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of interconnect over 4 links).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,     # per chip
        "peak_ops_int8": 393e12,       # per chip
        "hbm_bw": 819e9,               # bytes/s per chip
        "ici_bw": 50e9,                # bytes/s per link
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a kind not in ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

"""Placement layer: shard the engine's instance axis over a device mesh.

``engine.run_batch`` advances B colonies with one vmapped ``while_loop`` on
one device.  This module is the multi-device route (DESIGN.md §11): the
same loop body is wrapped in ``shard_map`` over a 1-D ``data`` mesh axis,
so one jitted call steps B instances spread across D devices.  There is
**no cross-device traffic inside the loop** — every instance's trajectory
is device-local (the per-instance freeze mask already makes trajectories
independent of batch composition), each shard's ``while_loop`` exits when
its *local* instances are done, and the only collective cost is the final
gather when the caller reads the sharded outputs.

Uneven batches: when B is not a multiple of the mesh's device count the
instance axis is padded with **phantom slots** — row 0 of the problem and
state replicated, with budget 0 — which the engine's done mask freezes
before the first step, exactly the mechanism ``batch.py`` uses for phantom
cities and the streaming pool uses for empty slots.  Padding happens
outside the jitted program and the outputs are sliced back to B rows, so
callers never observe it.

Exactness contract (tests/test_sharded.py): sharded ``run_batch`` is
*bitwise* identical per instance to the single-device call for any device
count, including B % D != 0 and donated buffers — each shard runs the same
per-slice numerics as the single-device vmapped program, and the phantom
slots never step.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import aco

from . import engine

Array = jax.Array


def data_mesh(devices: Optional[int] = None, axis: str = "data") -> Mesh:
    """1-D mesh over the host's first ``devices`` accelerators.

    Built by a function, never at import time (the dry-run isolation rule:
    importing this module must not touch jax device state).
    """
    n = devices if devices is not None else len(jax.devices())
    avail = len(jax.devices())
    if not 1 <= n <= avail:
        raise ValueError(f"requested {n} devices, have {avail}")
    return Mesh(jax.devices()[:n], (axis,))


def device_label(device, index: int) -> str:
    """Stable human-readable label for one mesh position — the Chrome
    trace *process* name of that device's streaming pools and the
    ``device`` field of request-scoped lifecycle events, so one
    request's journey through a sharded mesh can name the physical
    device it ran on (DESIGN.md §14).  ``device=None`` (the default,
    single-device route) stays the bare ``dev<i>``."""
    if device is None:
        return f"dev{index}"
    return f"dev{index}:{device.platform}{device.id}"


def pad_to_devices(problem: aco.Problem, states: aco.ColonyState,
                   budgets: Array, since: Array, multiple: int,
                   mets=None):
    """Pad the instance axis to a multiple of ``multiple`` with phantom
    slots: row 0's problem/state replicated with budget 0, which the
    engine's done mask freezes before the first step (their lanes are
    computed then discarded by the where-merge, so they only need finite
    numerics — a real instance's row is finite).  ``mets`` (metrics rows,
    DESIGN.md §13) pads the same way and is sliced back with the rest.
    Returns the padded pytrees and the original B."""
    b = budgets.shape[0]
    pad = (-b) % multiple
    if pad == 0:
        return problem, states, budgets, since, mets, b

    def rep(x):
        return jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])

    problem = jax.tree.map(rep, problem)
    states = jax.tree.map(rep, states)
    budgets = jnp.concatenate([budgets, jnp.zeros((pad,), budgets.dtype)])
    since = jnp.concatenate([since, jnp.zeros((pad,), since.dtype)])
    if mets is not None:
        mets = jax.tree.map(rep, mets)
    return problem, states, budgets, since, mets, b


# One compiled program per (mesh, axis, cfg, max_iters, patience, donate):
# the same cache granularity as engine's jit, plus the topology.
_CACHE: dict = {}


def _sharded_fn(mesh: Mesh, axis: str, cfg: aco.ACOConfig, max_iters: int,
                patience: int, donate: bool):
    key = (mesh, axis, cfg, max_iters, patience, donate)
    fn = _CACHE.get(key)
    if fn is None:
        spec = P(axis)
        n_out = 3 if cfg.metrics else 2

        def local(problem, states, budgets, since, mets):
            # Per-shard body == the single-device program on the local
            # slice; its while_loop conds on *local* done masks only, so
            # shards finish independently (no collectives => divergent
            # trip counts across devices are fine).  The metrics rows
            # (leafless None with metrics off) shard with the instances.
            return engine._run_batch_impl(problem, states, budgets, cfg,
                                          max_iters, patience, since, mets)

        # check_vma=False: with the check on, every pallas_call in the
        # body (use_pallas=True) must declare its outputs' varying mesh
        # axes (ShapeDtypeStruct.vma), which the mesh-agnostic kernels do
        # not.  Nothing is lost: the body has no collectives and every
        # output is sharded, so no value is claimed replicated.
        sharded = shard_map(local, mesh=mesh,
                            in_specs=(spec, spec, spec, spec, spec),
                            out_specs=(spec,) * n_out, check_vma=False)
        fn = jax.jit(sharded, donate_argnums=(1, 3, 4) if donate else ())
        _CACHE[key] = fn
    return fn


def run_batch_sharded(problem: aco.Problem, states: aco.ColonyState,
                      budgets: Array, cfg: aco.ACOConfig, max_iters: int,
                      patience: int, since: Array, mesh: Mesh,
                      instance_spec: str = "data", donate: bool = False,
                      mets=None):
    """Mesh route of ``engine.run_batch``: pad B to a device multiple,
    shard the instance axis over ``mesh[instance_spec]``, run, slice back.

    Donation covers the (possibly padded) stacked state, stagnation
    counters and metrics rows, same contract as the single-device donated
    route.  Returns ``(states, since)``, plus the updated metrics rows
    when ``cfg.metrics`` is set."""
    if instance_spec not in mesh.shape:
        raise ValueError(f"mesh has no axis {instance_spec!r}; "
                         f"axes: {tuple(mesh.shape)}")
    d = mesh.shape[instance_spec]
    problem, states, budgets, since, mets, b = pad_to_devices(
        problem, states, budgets, since, d, mets)
    if donate:
        engine._quiet_cpu_donation_warning()
    fn = _sharded_fn(mesh, instance_spec, cfg, max_iters, patience, donate)
    out = fn(problem, states, budgets, since, mets)
    if out[0].best_len.shape[0] != b:        # slice phantom slots back off
        out = jax.tree.map(lambda x: x[:b], out)
    return out

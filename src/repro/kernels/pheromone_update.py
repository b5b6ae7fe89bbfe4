"""Pallas kernel: fused pheromone evaporation + deposit (paper §IV.B).

TPU-native adaptation of the paper's scatter-to-gather (DESIGN.md §2): the
deposit matrix for an output tile (I, J) is

    D[I, J] = sum_e  [frm_e in I] * w_e * [to_e in J]
            = F_chunk^T @ (w * T_chunk)        -- an MXU matmul

with F/T one-hot slabs built *inside* the kernel from the int32 edge
endpoint vectors via iota-compares (never materialised in HBM). The edge
stream is the innermost grid axis; the output block doubles as the
accumulator, initialised with the evaporated pheromone (1-rho)*tau so
evaporation is fused for free.

Grid: (n/bi, n/bj, E/be). Edge padding uses endpoint -1 (matches no city).
Symmetric deposit is handled by the wrapper duplicating reversed edges.

Masking contract (padded instances, DESIGN.md §10): the kernel itself is
mask-complete through its edge stream — a phantom-tail edge arrives with
weight exactly 0 (contributing an exact 0 to the accumulator) and padded
edge slots arrive as -1 endpoints (matching no row/column).  The
``ops.pheromone_update`` wrapper builds that stream with
``core.pheromone.tour_edges``/``edge_weights`` (closing edge wraps at
position n_actual-1), so the kernel and pure-JAX deposits share one edge
semantics and cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_BLOCK_I = 128
DEFAULT_BLOCK_J = 128
DEFAULT_BLOCK_E = 1024   # XLA tiles 1-D s32/f32 arrays T(1024) on the TPU


def _update_kernel(tau_ref, frm_ref, to_ref, w_ref, out_ref, *,
                   rho: float, bi: int, bj: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    e = pl.program_id(2)

    @pl.when(e == 0)
    def _init():
        out_ref[...] = (1.0 - rho) * tau_ref[...]

    frm = frm_ref[...]                       # (1, be)
    to = to_ref[...]
    w = w_ref[...]
    rows = i * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, 1), 0)
    cols = j * bj + jax.lax.broadcasted_iota(jnp.int32, (bj, 1), 0)
    Ft = (rows == frm).astype(jnp.float32)                     # (bi, be)
    Tt = (cols == to).astype(jnp.float32) * w                  # (bj, be)
    # full f32 precision: the weights ride in Tt, and the TPU's default
    # precision would round them to bf16
    out_ref[...] += jax.lax.dot_general(
        Ft, Tt, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                     # (bi, bj)


@functools.partial(
    jax.jit,
    static_argnames=("rho", "block_i", "block_j", "block_e", "interpret"),
)
def pheromone_update(tau: jax.Array, frm: jax.Array, to: jax.Array,
                     w: jax.Array, rho: float,
                     block_i: int = DEFAULT_BLOCK_I,
                     block_j: int = DEFAULT_BLOCK_J,
                     block_e: int = DEFAULT_BLOCK_E,
                     interpret: bool | None = None) -> jax.Array:
    """tau (n0, n1) f32; frm/to (E,) int32 directed edges; w (E,) f32 deposit.

    Returns (1-rho)*tau + D. Pass each undirected edge twice (both
    directions) for the symmetric-TSP update. tau may be rectangular —
    the column-sharded island colony passes a (n, n/shards) shard with
    `to` indices already shifted into the local column frame.
    """
    n0, n1 = tau.shape
    bi = min(block_i, n0)
    bj = min(block_j, n1)
    be = min(block_e, max(int(frm.shape[0]), 1))
    pad_n_i = (-n0) % bi
    pad_n_j = (-n1) % bj
    pad_e = (-int(frm.shape[0])) % be
    tau_p = jnp.pad(tau, ((0, pad_n_i), (0, pad_n_j)))
    if pad_e:
        frm = jnp.pad(frm, (0, pad_e), constant_values=-1)
        to = jnp.pad(to, (0, pad_e), constant_values=-1)
        w = jnp.pad(w, (0, pad_e))
    gi = tau_p.shape[0] // bi
    gj = tau_p.shape[1] // bj
    ge = frm.shape[0] // be
    # Edges travel as (1, E) rows: a 1-D edge block gains a squeezed
    # leading dim under vmap, which the TPU's block rules refuse.
    edge = pl.BlockSpec((1, be), lambda i, j, e: (0, e))
    out = pl.pallas_call(
        functools.partial(_update_kernel, rho=rho, bi=bi, bj=bj),
        grid=(gi, gj, ge),
        in_specs=[
            pl.BlockSpec((bi, bj), lambda i, j, e: (i, j)),
            edge, edge, edge,
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, e: (i, j)),
        out_shape=jax.ShapeDtypeStruct(tau_p.shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(tau_p, frm.astype(jnp.int32)[None], to.astype(jnp.int32)[None],
      w.astype(jnp.float32)[None])
    return out[:n0, :n1]

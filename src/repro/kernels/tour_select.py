"""Pallas kernel: data-parallel next-city selection (paper Fig. 1 + tiling).

One ant = one row (sublane), cities = lanes. Each grid step processes an
(ant-block x city-tile) VMEM block: fuse tabu masking, the stochastic
transform (iroulette multiply / gumbel add / greedy identity), a per-tile
partial argmax, and a running cross-tile (value, index) reduction — exactly
the paper's "partial best per tile, then final reduction" scheme with the
running reduction carried in the output block across the innermost grid axis.

Random numbers are supplied as an input block (generated by jax.random in the
wrapper): on a real TPU target this would be pltpu.prng_random_bits seeded
per (ant, iteration); the operand form keeps the kernel bit-exact against the
ref.py oracle on CPU interpret mode, which is what we can validate here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_BLOCK_M = 8
DEFAULT_BLOCK_N = 512

_NEG_INF = -1e30


def first_arg(v, largest: bool = True):
    """Row-wise (max, index of its first occurrence) of a 2-D tile, both
    (rows, 1); ``largest=False`` gives the min.  Spelled out instead of
    jnp.argmax: the kernels must break ties on the first index, as XLA's
    argmax does, whatever the TPU's own index reduction does."""
    best = (jnp.max if largest else jnp.min)(v, axis=1, keepdims=True)
    lanes = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    first = jnp.min(jnp.where(v == best, lanes, v.shape[1]), axis=1,
                    keepdims=True)
    return best, first


def _transform(rows, mask, rand, mode: str):
    """Per-city score whose argmax is the selected city."""
    if mode == "iroulette":
        # paper's scheme: value * U(0,1] * tabu-bit
        return rows * rand * mask
    if mode == "gumbel":
        g = -jnp.log(-jnp.log(jnp.clip(rand, 1e-12, 1.0 - 1e-7)))
        valid = (rows > 0) & (mask > 0)
        return jnp.where(valid, jnp.log(jnp.maximum(rows, 1e-38)) + g, _NEG_INF)
    if mode == "greedy":
        return jnp.where(mask > 0, rows, _NEG_INF)
    raise ValueError(mode)


def _select_kernel(rows_ref, vis_ref, rand_ref, nact_ref, val_ref, idx_ref,
                   *, mode: str, block_n: int, n_tiles: int):
    j = pl.program_id(1)
    rows = rows_ref[...]
    cols = j * block_n + jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    vis = vis_ref[...].astype(jnp.int32)    # v5e has no int8 vector compare
    mask = ((vis == 0) & (cols < nact_ref[0, 0])).astype(rows.dtype)
    rand = rand_ref[...]
    v = _transform(rows, mask, rand, mode)          # (bm, bn)

    tile_val, local = first_arg(v)                  # (bm, 1) each
    tile_idx = local + j * block_n                  # first-max within tile

    @pl.when(j == 0)
    def _init():
        val_ref[...] = tile_val
        idx_ref[...] = tile_idx

    @pl.when(j > 0)
    def _update():
        cur_val = val_ref[...]
        cur_idx = idx_ref[...]
        better = tile_val > cur_val                  # strict: first tile wins ties
        val_ref[...] = jnp.where(better, tile_val, cur_val)
        idx_ref[...] = jnp.where(better, tile_idx, cur_idx)


@functools.partial(
    jax.jit, static_argnames=("mode", "block_m", "block_n", "interpret")
)
def tour_select(rows: jax.Array, visited: jax.Array, rand: jax.Array,
                mode: str = "iroulette",
                n_actual: jax.Array | None = None,
                block_m: int = DEFAULT_BLOCK_M,
                block_n: int = DEFAULT_BLOCK_N,
                interpret: bool | None = None) -> jax.Array:
    """rows/visited/rand: (m, n). Returns (m,) int32 selected city per ant.

    ``n_actual``: optional traced () scalar; cities >= n_actual (the
    phantom tail of a padded instance, DESIGN.md §8) are masked inside the
    kernel via an iota-compare, so padded tiles contribute exactly-zero
    weight (iroulette) / -inf score (gumbel, greedy).  City padding added
    here for non-divisible tiles carries visited=1 (never selectable); ant
    padding is sliced off.
    """
    m, n = rows.shape
    bm = min(block_m, max(m, 1))
    bn = min(block_n, n)
    pad_m = (-m) % bm
    pad_n = (-n) % bn
    if pad_m or pad_n:
        rows = jnp.pad(rows, ((0, pad_m), (0, pad_n)))
        rand = jnp.pad(rand, ((0, pad_m), (0, pad_n)), constant_values=1.0)
        visited = jnp.pad(visited.astype(jnp.int8), ((0, pad_m), (0, pad_n)),
                          constant_values=1)
    visited = visited.astype(jnp.int8)
    mp, np_ = rows.shape
    gm, gn = mp // bm, np_ // bn
    n_act = jnp.asarray(np_ if n_actual is None else n_actual,
                        jnp.int32).reshape(1, 1)
    val, idx = pl.pallas_call(
        functools.partial(_select_kernel, mode=mode, block_n=bn, n_tiles=gn),
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, 1), rows.dtype),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(rows, visited, rand, n_act)
    del val
    return idx[:m, 0]

"""Pallas kernel: 2-opt move-delta evaluation + move selection (DESIGN.md §7).

One ant = one row (sublane), moves = lanes (the flattened n*k NN-restricted
move set).  Each grid step loads an (ant-block x move-tile) VMEM block of the
four gathered distance operands, forms the move delta

    delta = d(a, c) + d(a', c') - d(a, a') - d(c, c')

in registers, masks invalid (degenerate) moves, and reduces it to a per-tile
(value, index) pair; a running cross-tile reduction is carried in the output
block across the innermost grid axis — the same partial-best-then-reduce
scheme as tour_select.py, applied to the move tensor instead of the city row.

Two selection modes, matching core/localsearch.py:

- ``best``   running masked min of delta (first-argmin tie semantics).
- ``first``  running min of the flat move index among improving moves
             (delta < -thr), i.e. first-improvement; the winning delta rides
             along so the caller can gate on it.

The gathers that build the operand tensors stay in the wrapper (XLA): on TPU
arbitrary dynamic gathers don't vectorise inside a kernel, while the delta
arithmetic + reduction — the O(m * n * k) hot loop — runs tile-by-tile in
VMEM.  Bit-comparable to kernels/ref.py::two_opt_best in f32.

Masking contract (padded instances, DESIGN.md §10): phantom-touching moves
reach this kernel with valid=0 — core.localsearch._two_opt_operands zeroes
them before the reduction — so their inf/NaN deltas are replaced by +inf
(mode="best") or excluded from the improving set (mode="first") inside the
tile; tile padding added here carries valid=0 the same way.  A padded tour
therefore selects exactly the move its trimmed real tour would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret
from .tour_select import first_arg

DEFAULT_BLOCK_M = 8
DEFAULT_BLOCK_N = 512

_INF = 1e30
_IMAX = 2**31 - 1


def _delta_kernel(a1_ref, a2_ref, r1_ref, r2_ref, valid_ref,
                  val_ref, idx_ref, *, mode: str, thr: float, block_n: int):
    j = pl.program_id(1)
    delta = a1_ref[...] + a2_ref[...] - r1_ref[...] - r2_ref[...]
    ok = valid_ref[...].astype(jnp.int32) != 0   # no int8 compare on v5e

    if mode == "best":
        v = jnp.where(ok, delta, _INF)
        tile_val, local = first_arg(v, largest=False)             # (bm, 1)
        tile_idx = local + j * block_n
    elif mode == "first":
        imp = ok & (delta < -thr)
        has = jnp.any(imp, axis=1, keepdims=True)
        local = first_arg(imp.astype(jnp.float32))[1]   # first True
        # delta at the local winner, via one-hot select (TPU-safe gather)
        lanes = jax.lax.broadcasted_iota(jnp.int32, delta.shape, 1)
        dsel = jnp.sum(jnp.where(lanes == local, delta, 0.0), axis=1,
                       keepdims=True)
        tile_val = jnp.where(has, dsel, _INF)
        tile_idx = jnp.where(has, local + j * block_n, _IMAX)
    else:
        raise ValueError(mode)

    @pl.when(j == 0)
    def _init():
        val_ref[...] = tile_val
        idx_ref[...] = tile_idx

    @pl.when(j > 0)
    def _update():
        cur_val = val_ref[...]
        cur_idx = idx_ref[...]
        if mode == "best":
            better = tile_val < cur_val       # strict: first tile wins ties
        else:
            better = tile_idx < cur_idx       # earliest improving move wins
        val_ref[...] = jnp.where(better, tile_val, cur_val)
        idx_ref[...] = jnp.where(better, tile_idx, cur_idx)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "thr", "block_m", "block_n", "interpret"),
)
def two_opt_best(add1: jax.Array, add2: jax.Array, rem1: jax.Array,
                 rem2: jax.Array, valid: jax.Array, thr: float = 0.0,
                 mode: str = "best", block_m: int = DEFAULT_BLOCK_M,
                 block_n: int = DEFAULT_BLOCK_N,
                 interpret: bool | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Operands (m, M) f32 (+ valid mask); returns ((m,) delta, (m,) idx).

    ``best``: (min masked delta, its first flat index); delta is +inf when
    every move is masked.  ``first``: (delta, index) of the first move with
    delta < -thr, (+inf, INT32_MAX) when none.  Move padding carries
    valid=0; ant padding is sliced off.
    """
    m, M = add1.shape
    bm = min(block_m, max(m, 1))
    bn = min(block_n, M)
    pad_m = (-m) % bm
    pad_n = (-M) % bn
    valid = valid.astype(jnp.int8)
    if pad_m or pad_n:
        pad2 = ((0, pad_m), (0, pad_n))
        add1, add2 = jnp.pad(add1, pad2), jnp.pad(add2, pad2)
        rem1, rem2 = jnp.pad(rem1, pad2), jnp.pad(rem2, pad2)
        valid = jnp.pad(valid, pad2)          # padding is invalid (0)
    mp, Mp = add1.shape
    gm, gn = mp // bm, Mp // bn
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out_spec = pl.BlockSpec((bm, 1), lambda i, j: (i, 0))
    val, idx = pl.pallas_call(
        functools.partial(_delta_kernel, mode=mode, thr=thr, block_n=bn),
        grid=(gm, gn),
        in_specs=[spec, spec, spec, spec, spec],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(add1.astype(jnp.float32), add2.astype(jnp.float32),
      rem1.astype(jnp.float32), rem2.astype(jnp.float32), valid)
    return val[:m, 0], idx[:m, 0]

"""Pallas kernel: sparse candidate-page next-city selection (DESIGN.md §12).

The sparse construction step needs, per ant, the tabu bit and the random
draw *at its K candidate cities* — a (m, K) gather from (m, n) tensors —
followed by the tau^alpha * eta^beta weighting, masking, and selection
over the K-wide page.  This kernel fuses all of it over
(ant-block x city-tile) VMEM blocks:

- **candidate gather** of visited/rand as a batched one-hot contraction:
  per tile, ``memb[b, q, t] = (cand[b, q] == col_t)`` and a dot over the
  tile axis accumulates the gathered values across the innermost grid
  axis.  Exactly one tile matches each candidate; the other tiles add an
  exact 0.0, so the accumulated gather is bitwise a jnp gather;
- **weighting/selection** on the final tile only: the same static-
  integer-exponent folding (``choice_info._ipow``) and per-mode transform
  (``tour_select._transform``) as the dense kernels, argmax over the K
  page positions, plus the ``have`` bit (any unvisited candidate with
  positive weight) that triggers the caller's nearest-unvisited fallback.

Candidate ids < 0 (padding added here for non-divisible pages) match no
column: they gather visited=0 / rand=0 and carry zero weight, so they are
never selected while any real candidate survives, and ``have`` ignores
them.  ``kernels/ref.py`` holds the bit-comparable oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret
from .choice_info import _ipow
from .tour_select import _transform, first_arg

DEFAULT_BLOCK_M = 8
DEFAULT_BLOCK_N = 512


def _sparse_kernel(*refs, mode: str, alpha: float, beta: float,
                   block_n: int, n_tiles: int, quant: str):
    # Quantised pages (core/quant.py): tau_ref holds the resident int8/bf16
    # payload; int8 adds a (bm, K) per-row scale operand (the caller
    # broadcasts the page-row scales to page width).  Dequant runs once, in
    # the final-tile epilogue, in-register.  "none" is today's fp32 body.
    if quant == "int8":
        (tau_ref, scale_ref, eta_ref, cand_ref, vis_ref, rand_ref,
         pos_ref, have_ref, av_ref, ar_ref) = refs
    else:
        (tau_ref, eta_ref, cand_ref, vis_ref, rand_ref,
         pos_ref, have_ref, av_ref, ar_ref) = refs
    j = pl.program_id(1)
    cand = cand_ref[...]                                      # (bm, K)
    cols = j * block_n + jax.lax.broadcasted_iota(
        jnp.int32, cand.shape + (block_n,), 2)                # (bm, K, bn)
    memb = (cand[:, :, None] == cols).astype(jnp.float32)
    # batched one-hot contraction: exact gather of the tile's contribution
    # (full f32 precision — the TPU's default rounds f32 operands to bf16)
    gv = jax.lax.dot_general(
        memb, vis_ref[...].astype(jnp.float32),
        (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                   # (bm, K)
    gr = jax.lax.dot_general(
        memb, rand_ref[...],
        (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _init():
        av_ref[...] = gv
        ar_ref[...] = gr

    @pl.when(j > 0)
    def _acc():
        av_ref[...] = av_ref[...] + gv
        ar_ref[...] = ar_ref[...] + gr

    @pl.when(j == n_tiles - 1)
    def _select():
        tau_p = tau_ref[...]
        if quant == "int8":
            # exact dequant: int8 values are exactly representable in f32,
            # and the scale operand is the same f32 the oracle multiplies.
            tau_p = tau_p.astype(jnp.float32) * scale_ref[...]
        elif quant == "bf16":
            tau_p = tau_p.astype(jnp.float32)
        w = _ipow(tau_p, alpha) * _ipow(eta_ref[...], beta)
        mask = (av_ref[...] == 0).astype(w.dtype)
        v = _transform(w, mask, ar_ref[...], mode)
        pos_ref[...] = first_arg(v)[1]
        have_ref[...] = ((w * mask).sum(axis=1, keepdims=True) > 0
                         ).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "alpha", "beta", "block_m", "block_n",
                     "interpret"),
)
def sparse_select(tau_rows: jax.Array, eta_rows: jax.Array,
                  cand: jax.Array, visited: jax.Array, rand: jax.Array,
                  alpha: float = 1.0, beta: float = 2.0,
                  mode: str = "iroulette",
                  tau_scale: jax.Array | None = None,
                  block_m: int = DEFAULT_BLOCK_M,
                  block_n: int = DEFAULT_BLOCK_N,
                  interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """tau_rows/eta_rows (m, K) f32; cand (m, K) i32 candidate city ids;
    visited (m, n) bool/int8; rand (m, n) f32.

    Returns (pos (m,) i32 — page position of the selected candidate,
    have (m,) i32 — 1 iff any unvisited positive-weight candidate exists;
    pos is only meaningful where have is 1).

    Quantised pages (core/quant.py): int8/bf16 ``tau_rows`` are
    dequantised in the kernel's final-tile epilogue; ``tau_scale`` is the
    (m, K) f32 scale (page-row scales broadcast to page width — candidate
    and overflow columns carry their own store's scale), required for int8
    and ignored otherwise.
    """
    if tau_rows.dtype == jnp.int8:
        q_mode = "int8"
        assert tau_scale is not None, "int8 tau pages need their scales"
    elif tau_rows.dtype == jnp.bfloat16:
        q_mode = "bf16"
    else:
        q_mode = "none"
        tau_rows = tau_rows.astype(jnp.float32)
    m, kk = cand.shape
    n = visited.shape[1]
    bm = min(block_m, max(m, 1))
    bn = min(block_n, n)
    pad_m = (-m) % bm
    pad_n = (-n) % bn
    visited = visited.astype(jnp.int8)
    if pad_m:
        tau_rows = jnp.pad(tau_rows, ((0, pad_m), (0, 0)))
        eta_rows = jnp.pad(eta_rows, ((0, pad_m), (0, 0)))
        cand = jnp.pad(cand, ((0, pad_m), (0, 0)), constant_values=-1)
        visited = jnp.pad(visited, ((0, pad_m), (0, 0)), constant_values=1)
        rand = jnp.pad(rand, ((0, pad_m), (0, 0)))
        if q_mode == "int8":
            tau_scale = jnp.pad(tau_scale, ((0, pad_m), (0, 0)))
    if pad_n:
        visited = jnp.pad(visited, ((0, 0), (0, pad_n)), constant_values=1)
        rand = jnp.pad(rand, ((0, 0), (0, pad_n)))
    mp, np_ = visited.shape
    gm, gn = mp // bm, np_ // bn
    in_specs = [
        pl.BlockSpec((bm, kk), lambda i, j: (i, 0)),   # tau page
        pl.BlockSpec((bm, kk), lambda i, j: (i, 0)),   # eta page
        pl.BlockSpec((bm, kk), lambda i, j: (i, 0)),   # candidate ids
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),   # visited
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),   # rand
    ]
    operands = [tau_rows, eta_rows.astype(jnp.float32),
                cand.astype(jnp.int32), visited, rand.astype(jnp.float32)]
    if q_mode == "int8":
        in_specs.insert(1, pl.BlockSpec((bm, kk), lambda i, j: (i, 0)))
        operands.insert(1, tau_scale.astype(jnp.float32))
    pos, have, _, _ = pl.pallas_call(
        functools.partial(_sparse_kernel, mode=mode, alpha=float(alpha),
                          beta=float(beta), block_n=bn, n_tiles=gn,
                          quant=q_mode),
        grid=(gm, gn),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),    # pos
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),    # have
            pl.BlockSpec((bm, kk), lambda i, j: (i, 0)),   # vis accumulator
            pl.BlockSpec((bm, kk), lambda i, j: (i, 0)),   # rand accumulator
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
            jax.ShapeDtypeStruct((mp, kk), jnp.float32),
            jax.ShapeDtypeStruct((mp, kk), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(*operands)
    return pos[:m, 0], have[:m, 0]

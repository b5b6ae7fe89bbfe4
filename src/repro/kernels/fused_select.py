"""Pallas kernel: fused choice->select construction step (DESIGN.md §10).

One construction step of the data-parallel strategy ladder is, on the
pure-JAX route, three materialised (m, n) tensors per scan step: the row
gather ``choice_info[cur]``, the tabu mask multiply, and the stochastic
transform fed to argmax.  This kernel fuses the whole step into one pass
over (ant-block x city-tile) VMEM blocks:

- **row gather** of tau/eta tiles by the per-ant current city, computed as
  a one-hot MXU matmul (``onehot(cur) @ tile``) so the gather vectorises on
  TPU (arbitrary dynamic gathers don't; the one-hot sum is exact in f32 —
  one 1.0 per row, zeros elsewhere — so it is bitwise a gather);
- **weighting** ``tau^alpha * eta^beta`` with the same static-integer-
  exponent folding as ``core/strategies.choice_matrix`` (bitwise-identical
  values to gathering a precomputed choice matrix);
- **visited/phantom masking**: the tabu bit and a ``col < n_actual``
  iota-compare against a scalar operand, so padded tiles (city padding and
  the phantom tail of bucketed instances) contribute exactly-zero weight
  (iroulette) / -inf score (gumbel, greedy);
- **selection**: the same per-tile partial argmax + running cross-tile
  (value, index) reduction as ``tour_select.py``.

The (m, n) weight matrix is never materialised in HBM: per grid step only
an (bm, bn) tile of it exists, in registers.  ``kernels/ref.py`` holds the
bit-comparable oracle; ``core/strategies._make_fused_step`` wires this into
the construction registry and ``core/aco.colony_step`` routes
``use_pallas=True`` + ``construction="data_parallel"`` here — which also
drops the per-iteration (n, n) choice-matrix precompute from that route
entirely.
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
# The full-height (n, bn) tau/eta column tiles are double-buffered in VMEM;
# keep them inside the TPU's 16 MiB default scoped VMEM by narrowing the
# tile as n grows (any bn gives the same selection).
_TILE_VMEM_BUDGET = 12 * 2**20


def _fit_block_n(n: int, block_n: int, tau_bytes: int) -> int:
    # tau (tau_bytes) + eta (f32) per column, two buffers each
    while block_n > 128 and \
            2 * n * block_n * (tau_bytes + 4) > _TILE_VMEM_BUDGET:
        block_n //= 2
    return block_n


def _gather_rows(onehot, tile):
    """``onehot @ tile`` at full f32 precision: a default-precision f32
    matmul on the TPU rounds its operands to bf16, which would make the
    one-hot row gather inexact."""
    return jax.lax.dot_general(
        onehot, tile, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _fused_kernel(*refs, mode: str, alpha: float, beta: float,
                  block_n: int, n_rows: int, quant: str):
    # Quantised tau (core/quant.py): the tile arrives as the resident
    # int8/bf16 payload and is dequantised here, in-register, per tile —
    # the fp32 (n, n) matrix never exists.  ``quant`` is a static kernel
    # parameter; "none" is byte-for-byte today's fp32 body.
    if quant == "int8":
        (tau_ref, scale_ref, eta_ref, cur_ref, vis_ref, rand_ref, nact_ref,
         val_ref, idx_ref) = refs
    else:
        (tau_ref, eta_ref, cur_ref, vis_ref, rand_ref, nact_ref,
         val_ref, idx_ref) = refs
    j = pl.program_id(1)
    cur = cur_ref[...]                                        # (bm, 1)
    rows_iota = jax.lax.broadcasted_iota(jnp.int32, (1, n_rows), 1)
    onehot = (cur == rows_iota).astype(jnp.float32)           # (bm, n)
    # Exact gather of the (bm, bn) tau/eta row tiles as an MXU matmul.
    tau_tile = tau_ref[...]
    if quant != "none":
        # int8 in [-127, 127] and bf16 are exactly representable in f32,
        # so the one-hot contraction below stays bitwise a gather.
        tau_tile = tau_tile.astype(jnp.float32)
    tau_rows = _gather_rows(onehot, tau_tile)
    if quant == "int8":
        # Gather the per-row scale with the same one-hot contraction and
        # multiply after the payload gather: scale is constant along the
        # row, so (gathered q) * (gathered scale) multiplies exactly the
        # operands full dequantise-then-gather would — bitwise equal to
        # the ref.py oracle on the dequantised matrix.
        srow = _gather_rows(onehot, scale_ref[...])           # (bm, 1)
        tau_rows = tau_rows * srow
    eta_rows = _gather_rows(onehot, eta_ref[...])
    w = _ipow(tau_rows, alpha) * _ipow(eta_rows, beta)        # (bm, bn)

    cols = j * block_n + jax.lax.broadcasted_iota(
        jnp.int32, w.shape, 1)                                # (bm, bn)
    n_act = nact_ref[0, 0]
    # widen before comparing: v5e has no int8 vector compare
    vis = vis_ref[...].astype(jnp.int32)
    mask = ((vis == 0) & (cols < n_act)).astype(w.dtype)
    v = _transform(w, mask, rand_ref[...], mode)

    tile_val, local = first_arg(v)                            # (bm, 1)
    tile_idx = local + j * block_n                            # first max

    @pl.when(j == 0)
    def _init():
        val_ref[...] = tile_val
        idx_ref[...] = tile_idx

    @pl.when(j > 0)
    def _update():
        cur_val = val_ref[...]
        cur_idx = idx_ref[...]
        better = tile_val > cur_val           # strict: first tile wins ties
        val_ref[...] = jnp.where(better, tile_val, cur_val)
        idx_ref[...] = jnp.where(better, tile_idx, cur_idx)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "alpha", "beta", "block_m", "block_n",
                     "interpret"),
)
def fused_select(tau: jax.Array, eta: jax.Array, cur: jax.Array,
                 visited: jax.Array, rand: jax.Array,
                 alpha: float = 1.0, beta: float = 2.0,
                 n_actual: jax.Array | None = None,
                 mode: str = "iroulette",
                 tau_scale: jax.Array | None = None,
                 block_m: int = DEFAULT_BLOCK_M,
                 block_n: int = DEFAULT_BLOCK_N,
                 interpret: bool | None = None) -> jax.Array:
    """tau/eta (n, n); cur (m,) i32; visited/rand (m, n).  -> (m,) i32.

    ``n_actual``: optional traced () scalar; cities >= n_actual (phantom
    tail of a padded instance) are never selected.  City padding added here
    for non-divisible tiles is masked the same way, so any block size gives
    the same selection; ant padding is sliced off.

    Quantised tau (core/quant.py): an int8 or bf16 ``tau`` routes the
    payload into the kernel untouched and dequantises per tile in the
    epilogue; ``tau_scale`` is the (n, 1) f32 per-row scale, required for
    int8 and ignored otherwise.
    """
    if tau.dtype == jnp.int8:
        q_mode = "int8"
        assert tau_scale is not None, "int8 tau needs its per-row scale"
    elif tau.dtype == jnp.bfloat16:
        q_mode = "bf16"
    else:
        q_mode = "none"
        tau = tau.astype(jnp.float32)
    m, n = visited.shape
    bm = min(block_m, max(m, 1))
    bn = min(_fit_block_n(n, block_n, tau.dtype.itemsize), n)
    pad_m = (-m) % bm
    pad_n = (-n) % bn
    visited = visited.astype(jnp.int8)
    cur = cur.astype(jnp.int32).reshape(m, 1)
    if pad_m:
        cur = jnp.pad(cur, ((0, pad_m), (0, 0)))
        visited = jnp.pad(visited, ((0, pad_m), (0, 0)), constant_values=1)
        rand = jnp.pad(rand, ((0, pad_m), (0, 0)), constant_values=1.0)
    if pad_n:
        tau = jnp.pad(tau, ((0, 0), (0, pad_n)))
        eta = jnp.pad(eta, ((0, 0), (0, pad_n)))
        visited = jnp.pad(visited, ((0, 0), (0, pad_n)), constant_values=1)
        rand = jnp.pad(rand, ((0, 0), (0, pad_n)), constant_values=1.0)
    n_act = jnp.asarray(n if n_actual is None else n_actual,
                        jnp.int32).reshape(1, 1)
    mp, np_ = visited.shape
    gm, gn = mp // bm, np_ // bn
    in_specs = [
        pl.BlockSpec((n, bn), lambda i, j: (0, j)),    # tau column tile
        pl.BlockSpec((n, bn), lambda i, j: (0, j)),    # eta column tile
        pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),    # cur
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),   # visited
        pl.BlockSpec((bm, bn), lambda i, j: (i, j)),   # rand
        pl.BlockSpec((1, 1), lambda i, j: (0, 0)),     # n_actual
    ]
    operands = [tau, eta.astype(jnp.float32), cur,
                visited, rand.astype(jnp.float32), n_act]
    if q_mode == "int8":
        in_specs.insert(1, pl.BlockSpec((n, 1), lambda i, j: (0, 0)))
        operands.insert(1, tau_scale.astype(jnp.float32))
    val, idx = pl.pallas_call(
        functools.partial(_fused_kernel, mode=mode, alpha=float(alpha),
                          beta=float(beta), block_n=bn, n_rows=n,
                          quant=q_mode),
        grid=(gm, gn),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(*operands)
    del val
    return idx[:m, 0]

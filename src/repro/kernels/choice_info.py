"""Pallas kernel: the paper's "Choice kernel" — choice = tau^alpha * eta^beta.

Memory-bound elementwise op over the (n, n) matrices; tiled (block_m,
block_n) through VMEM. Integer alpha/beta in {1,2,3,4} are specialised to
repeated multiplies (no transcendental), matching core/strategies.choice_matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 512


def _ipow(x, p: float):
    if p == 1.0:
        return x
    if float(p).is_integer() and 0 < int(p) <= 4:
        y = x
        for _ in range(int(p) - 1):
            y = y * x
        return y
    return x ** p


def _choice_kernel(tau_ref, eta_ref, nact_ref, out_ref, *, alpha: float,
                   beta: float, bm: int, bn: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    out = _ipow(tau_ref[...], alpha) * _ipow(eta_ref[...], beta)
    # Phantom rows/cols (>= n_actual) of a padded instance carry eta == 0
    # already; the iota mask pins them (and tile padding) to exactly 0.
    rows = i * bm + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    cols = j * bn + jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
    n_act = nact_ref[0, 0]
    out_ref[...] = jnp.where((rows < n_act) & (cols < n_act), out, 0.0)


@functools.partial(
    jax.jit, static_argnames=("alpha", "beta", "block_m", "block_n", "interpret")
)
def choice_info(tau: jax.Array, eta: jax.Array, alpha: float = 1.0,
                beta: float = 2.0, n_actual: jax.Array | None = None,
                block_m: int = DEFAULT_BLOCK_M,
                block_n: int = DEFAULT_BLOCK_N,
                interpret: bool | None = None) -> jax.Array:
    """``n_actual``: optional traced () scalar; choice values touching a
    phantom row/column (>= n_actual) are exactly 0 — same as the pure-JAX
    route, where phantom eta == 0 zeroes the product (DESIGN.md §10)."""
    n0, n1 = tau.shape
    bm = min(block_m, n0)
    bn = min(block_n, n1)
    pad_m = (-n0) % bm
    pad_n = (-n1) % bn
    if pad_m or pad_n:
        tau = jnp.pad(tau, ((0, pad_m), (0, pad_n)))
        eta = jnp.pad(eta, ((0, pad_m), (0, pad_n)))
    n_act = jnp.asarray(max(n0, n1) if n_actual is None else n_actual,
                        jnp.int32).reshape(1, 1)
    gm, gn = tau.shape[0] // bm, tau.shape[1] // bn
    out = pl.pallas_call(
        functools.partial(_choice_kernel, alpha=alpha, beta=beta,
                          bm=bm, bn=bn),
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(tau.shape, tau.dtype),
        interpret=resolve_interpret(interpret),
    )(tau, eta, n_act)
    return out[:n0, :n1]

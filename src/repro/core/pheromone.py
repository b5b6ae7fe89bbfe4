"""Pheromone-update strategies (paper §IV.B, Tables III/IV).

Strategy ladder, mirroring the paper's kernel versions:

- ``scatter``     the TPU analogue of the paper's winning *atomic* version:
                  XLA scatter-add of 1/C^k along each ant's tour edges.
                  (TPU has no atomics; XLA serialises colliding updates in a
                  sorted scatter — semantically identical to atomicAdd.)
- ``reduction``   the paper's Instruction & Thread *Reduction* version:
                  symmetric TSP => canonicalise each edge to (lo, hi) and
                  scatter only the upper triangle, half the update work, then
                  mirror.
- ``s2g``         honest *scatter-to-gather* (paper Fig. 3): every pheromone
                  cell scans every tour edge — O(n^4) work for m = n. Kept
                  deliberately faithful so the paper's Table III slow-down
                  scaling (claim C4) is reproducible.
- ``s2g_tiled``   scatter-to-gather with tile-blocked membership tests
                  (paper's 'Tiling' version, tile = theta).
- ``onehot``      TPU-native adaptation (DESIGN.md §2): deposit as a one-hot
                  matmul D = F^T (w * T) over edge chunks. Same pure-gather
                  memory pattern as s2g, but the membership test becomes MXU
                  work. The Pallas kernel (kernels/pheromone_update.py)
                  builds the one-hots in VMEM on the fly.

All strategies produce identical tau (up to float associativity); asserted in
tests/test_pheromone.py.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# The one-hot deposits carry the f32 deposit weights in a matmul operand;
# at default precision the TPU rounds matmul operands to bf16.
_EXACT = jax.lax.Precision.HIGHEST


def evaporate(tau: Array, rho: float) -> Array:
    """Eq. 2: tau <- (1 - rho) tau."""
    return (1.0 - rho) * tau


def tour_edges(tours: Array,
               n_actual: Optional[Array] = None) -> tuple[Array, Array]:
    """Directed edge endpoints (m, n) for closed tours.

    With ``n_actual`` (traced scalar; padded instances, DESIGN.md §8) the
    closing edge wraps at position n_actual-1 back to position 0; the
    phantom-tail positions still produce (phantom, phantom) index pairs but
    the masked deposit functions below give them zero weight.
    """
    t = jnp.roll(tours, -1, axis=-1)
    if n_actual is not None:
        idx = jnp.arange(tours.shape[-1], dtype=jnp.int32)
        t = jnp.where(idx == n_actual - 1, tours[..., :1], t)
    return tours, t


def edge_weights(tours: Array, w: Array,
                 n_actual: Optional[Array] = None) -> Array:
    """(m*n,) per-edge deposit weights; phantom-tail edges masked to 0.

    Public alongside ``tour_edges``: the kernel deposit wrapper
    (kernels/ops.pheromone_update) builds its edge stream with the same
    pair, so the kernel and pure-JAX routes share one edge semantics.
    """
    ns = tours.shape[-1]
    wrep = jnp.broadcast_to(w[:, None], (w.shape[0], ns))
    if n_actual is not None:
        idx = jnp.arange(ns, dtype=jnp.int32)
        wrep = jnp.where(idx[None, :] < n_actual, wrep, 0.0)
    return wrep.ravel()


def deposit_scatter(n: int, tours: Array, w: Array, symmetric: bool = True,
                    n_actual: Optional[Array] = None) -> Array:
    """Atomic-analogue scatter-add (paper versions 1/2)."""
    f, t = tour_edges(tours, n_actual)
    wrep = edge_weights(tours, w, n_actual)
    d = jnp.zeros((n, n), jnp.float32).at[f.ravel(), t.ravel()].add(wrep)
    if symmetric:
        d = d + d.T
    return d


def deposit_reduction(n: int, tours: Array, w: Array,
                      n_actual: Optional[Array] = None) -> Array:
    """Paper's Reduction version: half the scatters via edge canonicalisation."""
    f, t = tour_edges(tours, n_actual)
    lo = jnp.minimum(f, t)
    hi = jnp.maximum(f, t)
    wrep = edge_weights(tours, w, n_actual)
    upper = jnp.zeros((n, n), jnp.float32).at[lo.ravel(), hi.ravel()].add(wrep)
    return upper + upper.T


@partial(jax.jit, static_argnames=("n", "row_tile", "col_tile"))
def deposit_s2g(n: int, tours: Array, w: Array, row_tile: int = 0,
                col_tile: int = 0, n_actual: Optional[Array] = None) -> Array:
    """Scatter-to-gather: cell (i,j) gathers over ALL m*n edges (paper Fig. 3).

    row_tile/col_tile = 0 means untiled semantics (single tile). The tiled
    variant is the paper's 'Scatter to Gather + Tiling'; tiles bound the
    VMEM-resident membership masks exactly like the paper's shared-memory
    tiles. Work is O(n^2 * m * n) regardless of tiling — that is the point.

    Mask-aware for padded tours: phantom-tail edges carry weight 0 so their
    (phantom, phantom) membership hits contribute nothing, and the closing
    edge wraps at position n_actual-1 (DESIGN.md §8).
    """
    f, t = tour_edges(tours, n_actual)
    m, ns = f.shape
    bi = row_tile or min(n, 64)
    bj = col_tile or min(n, 64)
    # pad n up to multiples
    ni = -(-n // bi) * bi
    nj = -(-n // bj) * bj
    fw = (f.ravel(), edge_weights(tours, w, n_actual))
    tr = t.ravel()

    def row_block(i0):
        rows = i0 + jnp.arange(bi)
        mi = (fw[0][None, :] == rows[:, None]).astype(jnp.float32)  # (bi, E)
        mi = mi * fw[1][None, :]

        def col_block(j0):
            cols = j0 + jnp.arange(bj)
            mj = (tr[None, :] == cols[:, None]).astype(jnp.float32)  # (bj, E)
            return jnp.matmul(mi, mj.T, precision=_EXACT)             # (bi, bj)

        blocks = jax.lax.map(col_block, jnp.arange(0, nj, bj))       # (k, bi, bj)
        return blocks.transpose(1, 0, 2).reshape(bi, nj)

    rows = jax.lax.map(row_block, jnp.arange(0, ni, bi))   # (ni/bi, bi, nj)
    d = rows.reshape(ni, nj)[:n, :n]
    return d + d.T


@partial(jax.jit, static_argnames=("n", "chunk"))
def deposit_onehot(n: int, tours: Array, w: Array, chunk: int = 8,
                   n_actual: Optional[Array] = None) -> Array:
    """TPU-native deposit: D = F^T (w*T) accumulated over ant chunks.

    F/T are (chunk*ns, n) one-hot matrices, never larger than one chunk.
    Mask-aware for padded tours: the per-edge weight matrix zeroes the
    phantom tail and the closing edge wraps at position n_actual-1.
    """
    f, t = tour_edges(tours, n_actual)
    m, ns = f.shape
    we = edge_weights(tours, w, n_actual).reshape(m, ns)
    c = min(chunk, m)
    pad = (-m) % c
    if pad:
        f = jnp.concatenate([f, jnp.zeros((pad, ns), f.dtype)], 0)
        t = jnp.concatenate([t, jnp.zeros((pad, ns), t.dtype)], 0)
        we = jnp.concatenate([we, jnp.zeros((pad, ns), we.dtype)], 0)
    nchunks = f.shape[0] // c

    def body(acc, i):
        fs = jax.lax.dynamic_slice_in_dim(f, i * c, c).ravel()
        ts = jax.lax.dynamic_slice_in_dim(t, i * c, c).ravel()
        ws = jax.lax.dynamic_slice_in_dim(we, i * c, c).ravel()
        F = jax.nn.one_hot(fs, n, dtype=jnp.float32)
        T = jax.nn.one_hot(ts, n, dtype=jnp.float32) * ws[:, None]
        return acc + jnp.matmul(F.T, T, precision=_EXACT), None

    d0 = jnp.zeros((n, n), jnp.float32)
    d, _ = jax.lax.scan(body, d0, jnp.arange(nchunks))
    return d + d.T


STRATEGIES = ("scatter", "reduction", "s2g", "s2g_tiled", "onehot")


def deposit(n: int, tours: Array, w: Array, strategy: str = "scatter",
            tile: int = 64, n_actual: Optional[Array] = None) -> Array:
    if strategy == "scatter":
        return deposit_scatter(n, tours, w, n_actual=n_actual)
    if strategy == "reduction":
        return deposit_reduction(n, tours, w, n_actual=n_actual)
    if strategy == "s2g":
        return deposit_s2g(n, tours, w, 0, 0, n_actual)
    if strategy == "s2g_tiled":
        return deposit_s2g(n, tours, w, tile, tile, n_actual)
    if strategy == "onehot":
        return deposit_onehot(n, tours, w, n_actual=n_actual)
    raise ValueError(f"unknown deposit strategy {strategy}")


def update(tau: Array, tours: Array, w: Array, rho: float,
           strategy: str = "scatter", tile: int = 64,
           n_actual: Optional[Array] = None) -> Array:
    """Full pheromone update: evaporation (eq. 2) + deposit (eq. 3/4)."""
    n = tau.shape[0]
    return evaporate(tau, rho) + deposit(n, tours, w, strategy, tile, n_actual)


def local_update_acs(tau: Array, frm: Array, to: Array, xi: float,
                     tau0: float, w: Optional[Array] = None) -> Array:
    """ACS local pheromone rule on the just-crossed edges (both directions).

    The sequential rule tau <- (1-xi) tau + xi tau0 is applied once per
    crossing.  It is a contraction toward tau0, so c applications compose to
    the closed form tau <- (1-xi)^c tau + (1 - (1-xi)^c) tau0 *independent
    of order* — which is what we compute: per-edge crossing counts via a
    deterministic scatter-add, then the closed form.  (A scatter-``set``
    with duplicate edge indices — multiple ants crossing the same edge —
    has unspecified winner order and made the result nondeterministic.)

    ``w``: optional per-edge crossing multiplicity (phantom-tail edges of
    padded tours pass 0 so they contribute no decay); defaults to 1.
    """
    n = tau.shape[0]
    ones = jnp.ones(frm.shape, tau.dtype) if w is None else w.astype(tau.dtype)
    counts = jnp.zeros((n, n), tau.dtype).at[frm, to].add(ones)
    counts = counts + counts.T               # symmetric: both directions
    factor = jnp.power(jnp.asarray(1.0 - xi, tau.dtype), counts)
    return factor * tau + (1.0 - factor) * tau0

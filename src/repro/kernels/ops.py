"""Jitted public wrappers around the Pallas kernels.

``INTERPRET`` is True on CPU (kernel bodies execute in Python for
validation) and flips to False on a real TPU backend automatically.

Every wrapper is mask-aware: ``n_actual`` (a traced () int32 scalar, the
real-city count of a padded instance — DESIGN.md §8) threads through to the
kernels, where padded tiles and phantom cities contribute exactly-zero
weight / deposit / -inf score.  The one kernel route that remains
genuinely unsupported — per-instance ``aco.Hyper`` operands, whose traced
alpha/beta exponents cannot be static kernel parameters — raises
``UnsupportedKernelRoute`` from ``check_kernel_route`` (the single typed
rejection point; DESIGN.md §10 has the support matrix).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import resolve_interpret
from . import choice_info as _ci
from . import fused_select as _fs
from . import pheromone_update as _pu
from . import sparse_select as _ss
from . import tour_select as _ts
from . import two_opt as _to


INTERPRET = resolve_interpret(None)


class UnsupportedKernelRoute(NotImplementedError):
    """A config/problem combination the kernels genuinely cannot serve."""


def check_kernel_route(masked: bool = False, hyper: bool = False,
                       sparse: bool = False,
                       selection: Optional[str] = None,
                       local_search: Optional[str] = None,
                       construction: Optional[str] = None,
                       streaming: bool = False,
                       mesh: bool = False,
                       tau_dtype: str = "fp32") -> None:
    """Validate that the kernel/sparse route supports this problem shape.

    The single typed rejection point (DESIGN.md §10/§12 support matrix):
    every route combination the kernels or the sparse representation
    genuinely cannot serve raises ``UnsupportedKernelRoute`` with one
    actionable line here, up front, instead of failing deep in a trace.

    - masked (padded) instances: fully supported everywhere (dense kernels
      and the sparse route, except sparse Partial-ACO — window positions
      index the real tour, so padded instances must run unpadded);
    - per-instance ``Hyper`` operands: unsupported on the Pallas route
      (kernel exponents are static) *and* on the sparse route (sparse
      programs specialise on static alpha/beta for the same reason);
    - sparse x roulette: inverse-CDF sampling needs a full choice row's
      cumsum — candidate pages cannot express it;
    - sparse x local search: 2-opt/Or-opt evaluate arbitrary (i, j) edges
      against the dense distance matrix;
    - sparse x streaming / mesh sharding: not wired yet (the batched
      sparse engine route is; see DESIGN.md §12 route matrix);
    - quantised tau (``tau_dtype`` bf16/int8, DESIGN.md §15): supported on
      the dense pure-JAX, Pallas, sparse, streaming, sharded and
      checkpoint routes — but *not* with per-instance ``Hyper`` operands
      (quality-gap guarantees are audited per static config; mixing
      per-slot tuning profiles over a lossy store is unvalidated).
    """
    if tau_dtype not in ("fp32", "bf16", "int8"):
        raise UnsupportedKernelRoute(
            f"unknown tau_dtype {tau_dtype!r}: the quantised pheromone "
            "store supports 'fp32' | 'bf16' | 'int8' (core/quant.py).")
    if hyper and tau_dtype != "fp32":
        raise UnsupportedKernelRoute(
            f"per-instance Hyper operands cannot run over a quantised "
            f"pheromone store (tau_dtype={tau_dtype!r}): the quantised "
            "quality gates are validated per static config only. Drop "
            "Problem.hyper or run tau_dtype='fp32'.")
    if hyper:
        if sparse:
            raise UnsupportedKernelRoute(
                "the sparse route cannot serve per-instance Hyper "
                "operands: sparse programs specialise on static "
                "alpha/beta. Drop the Hyper profiles or run the dense "
                "pure-JAX route (sparse=False, use_pallas=False).")
        raise UnsupportedKernelRoute(
            "use_pallas=True cannot serve per-instance Hyper operands: "
            "kernel alpha/beta are static compile-time parameters, but "
            "Hyper carries traced per-instance exponents. Run the "
            "pure-JAX route (use_pallas=False) for per-instance "
            "hyperparameters, or drop Problem.hyper.")
    if not sparse:
        return
    if selection == "roulette":
        raise UnsupportedKernelRoute(
            "sparse construction cannot serve selection='roulette': "
            "inverse-CDF sampling needs the full choice row's cumsum, "
            "which candidate pages do not hold. Use selection="
            "'iroulette', 'gumbel' or 'greedy', or run sparse=False.")
    if local_search is not None and local_search != "none":
        raise UnsupportedKernelRoute(
            f"sparse route cannot serve local_search={local_search!r}: "
            "2-opt/Or-opt moves evaluate arbitrary city pairs against "
            "the dense (n, n) distance matrix. Set local_search='none' "
            "or run sparse=False.")
    if construction is not None and construction not in ("data_parallel",
                                                         "partial"):
        raise UnsupportedKernelRoute(
            f"sparse route has no construction={construction!r}: the "
            "candidate-page step replaces the dense strategy ladder. Use "
            "construction='data_parallel' (standard) or 'partial' "
            "(Partial-ACO mutation), or run sparse=False.")
    if construction == "partial" and masked:
        raise UnsupportedKernelRoute(
            "sparse Partial-ACO cannot run on padded (masked) instances: "
            "mutation windows index positions of the real best tour. Run "
            "the instance unpadded (solo run_sparse) or use "
            "construction='data_parallel'.")
    if streaming:
        raise UnsupportedKernelRoute(
            "sparse instances are not wired into the streaming pool yet: "
            "slot surgery assumes dense (n, n) ColonyState buffers. Use "
            "the batched sparse engine route (solver.engine."
            "solve_instances with sparse=True) or stream dense.")
    if mesh:
        raise UnsupportedKernelRoute(
            "sparse batches are not wired through mesh sharding yet: the "
            "placement layer shards dense Problem pytrees. Run sparse "
            "batches single-device (mesh=None) or shard dense.")


def choice_info(tau: jax.Array, eta: jax.Array, alpha: float = 1.0,
                beta: float = 2.0,
                n_actual: Optional[jax.Array] = None) -> jax.Array:
    return _ci.choice_info(tau, eta, alpha, beta, n_actual,
                           interpret=INTERPRET)


def tour_select(rows: jax.Array, visited: jax.Array, rand: jax.Array,
                mode: str = "iroulette",
                n_actual: Optional[jax.Array] = None) -> jax.Array:
    return _ts.tour_select(rows, visited, rand, mode, n_actual,
                           interpret=INTERPRET)


def fused_select(tau: jax.Array, eta: jax.Array, cur: jax.Array,
                 visited: jax.Array, rand: jax.Array,
                 alpha: float = 1.0, beta: float = 2.0,
                 n_actual: Optional[jax.Array] = None,
                 mode: str = "iroulette",
                 tau_scale: Optional[jax.Array] = None) -> jax.Array:
    """Fused construction step: row gather + tau^a*eta^b + mask + select,
    without materialising the (m, n) weight matrix (kernels/fused_select).
    int8/bf16 ``tau`` payloads dequantise per tile in the kernel epilogue;
    ``tau_scale`` is the int8 per-row scale (core/quant.py)."""
    return _fs.fused_select(tau, eta, cur, visited, rand, alpha, beta,
                            n_actual, mode, tau_scale=tau_scale,
                            interpret=INTERPRET)


def sparse_select(tau_rows: jax.Array, eta_rows: jax.Array,
                  cand: jax.Array, visited: jax.Array, rand: jax.Array,
                  alpha: float = 1.0, beta: float = 2.0,
                  mode: str = "iroulette",
                  tau_scale: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Sparse candidate-page selection: gather visited/rand at the K
    candidate cities, weight tau^a * eta^b, mask, select — one kernel,
    no (m, n) weight tensor (kernels/sparse_select).  Returns (pos, have):
    the winning page position and whether a selectable candidate exists
    (the sparse construction step's nearest-unvisited fallback trigger).
    int8/bf16 page payloads dequantise in the kernel epilogue; ``tau_scale``
    is the int8 (m, K) broadcast scale (core/quant.py)."""
    return _ss.sparse_select(tau_rows, eta_rows, cand, visited, rand,
                             alpha, beta, mode, tau_scale=tau_scale,
                             interpret=INTERPRET)


def tour_select_step(selection: str = "iroulette"):
    """StepImpl closure for core.strategies.construct_tours injection."""

    def step(key, choice_info_, st, t):
        del t
        rows = choice_info_[st.cur]
        u = jax.random.uniform(key, rows.shape, rows.dtype,
                               minval=1e-6, maxval=1.0)
        return tour_select(rows, st.visited, u, selection)

    return step


def pheromone_update(tau: jax.Array, tours: jax.Array, w: jax.Array,
                     rho: float,
                     n_actual: Optional[jax.Array] = None) -> jax.Array:
    """Symmetric fused update from (m, n) tours + (m,) weights.

    Mask-aware: with ``n_actual`` the closing edge wraps at position
    n_actual-1 and phantom-tail edges carry weight exactly 0, so padded
    tours deposit identically to their trimmed real tours (the same edge
    semantics as core.pheromone.tour_edges/edge_weights — reused here so
    the kernel and pure-JAX routes can never drift).
    """
    from repro.core import pheromone as _ph   # lazy: kernels stay core-free
    f, t = _ph.tour_edges(tours, n_actual)
    frm = f.ravel()
    to = t.ravel()
    wrep = _ph.edge_weights(tours, w, n_actual)
    # both directions for the symmetric TSP
    f2 = jnp.concatenate([frm, to])
    t2 = jnp.concatenate([to, frm])
    w2 = jnp.concatenate([wrep, wrep])
    return _pu.pheromone_update(tau, f2, t2, w2, rho, interpret=INTERPRET)


def pheromone_update_edges(tau: jax.Array, frm: jax.Array, to: jax.Array,
                           w: jax.Array, rho: float) -> jax.Array:
    return _pu.pheromone_update(tau, frm, to, w, rho, interpret=INTERPRET)


def two_opt_best(add1: jax.Array, add2: jax.Array, rem1: jax.Array,
                 rem2: jax.Array, valid: jax.Array, thr: float = 0.0,
                 mode: str = "best") -> tuple[jax.Array, jax.Array]:
    """Per-ant best/first 2-opt move over (m, M) gathered move operands.

    Mask-awareness lives in ``valid``: core.localsearch builds it with
    phantom-touching moves already zeroed (their inf/NaN deltas never
    reach the reduction), so padded tiles contribute +inf delta only.
    """
    return _to.two_opt_best(add1, add2, rem1, rem2, valid, thr=float(thr),
                            mode=mode, interpret=INTERPRET)

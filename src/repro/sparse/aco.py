"""Sparse colony step: the dense ``core.aco.colony_step`` control flow on
the O(n·k) paged representation.

One iteration = construct (or Partial-ACO-mutate) m tours over candidate
pages, track the best, deposit per variant, clamp (MMAS) / locally decay
(ACS) — the exact step order and key discipline of the dense step, so at
k = n-1 (every edge on a candidate page, overflow empty) the trajectories
coincide bit-for-bit for AS/MMAS/ACS (tests/test_sparse.py).

Route validation happens once, up front, through the single typed
rejection point ``kernels.ops.check_kernel_route`` — roulette selection
(needs full-row CDFs), dense-matrix local search, and per-instance Hyper
operands raise ``UnsupportedKernelRoute`` with one actionable line
instead of failing deep in a trace.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aco as dense_aco
from repro.core import quant, tsp

from . import construct, pheromone, store
from .store import SparseColonyState, SparseProblem

Array = jax.Array


def check_sparse_route(cfg: dense_aco.ACOConfig, hyper: bool = False,
                       masked: bool = False) -> None:
    """Reject sparse x feature combinations the route cannot serve."""
    from repro.kernels import ops as kops
    kops.check_kernel_route(masked=masked, hyper=hyper, sparse=True,
                            selection=cfg.selection,
                            local_search=cfg.local_search,
                            construction=cfg.construction,
                            tau_dtype=cfg.tau_dtype)


def make_sparse_problem_cfg(instance: tsp.TSPInstance,
                            cfg: dense_aco.ACOConfig,
                            n_pad: Optional[int] = None) -> SparseProblem:
    return store.make_sparse_problem(instance, cfg.sparse_k, n_pad)


def init_sparse_colony(instance: tsp.TSPInstance, cfg: dense_aco.ACOConfig,
                       seed: Optional[int] = None,
                       n_pad: Optional[int] = None) -> SparseColonyState:
    """Fresh sparse state: tau0 on every page, empty overflow slots.

    tau0 comes from the same NN-tour formulas as the dense
    ``aco.initial_tau`` (computed row-wise, no (n, n) matrix).  Partial-ACO
    construction needs a valid running best to mutate, so it seeds
    best_tour/best_len with the NN tour itself; the standard route starts
    from the identity tour at +inf, exactly like the dense init.
    """
    n = instance.n
    n_pad = n if n_pad is None else n_pad
    # page width, NOT clamped to n-1: the problem pages keep the full
    # ``sparse_k`` width with surplus self-sentinel columns (store.
    # build_candidates), and tau must line up column-for-column.
    k = max(1, cfg.sparse_k)
    tau0 = store.sparse_initial_tau(instance, cfg)
    if cfg.construction == "partial":
        nn_tour, nn_len = store.sparse_nearest_neighbour_tour(instance)
        best_tour = jnp.asarray(
            np.concatenate([nn_tour,
                            np.arange(n, n_pad, dtype=np.int32)]))
        best_len = jnp.asarray(np.float32(nn_len))
    else:
        best_tour = jnp.arange(n_pad, dtype=jnp.int32)
        best_len = jnp.asarray(np.float32(np.inf))
    o = cfg.sparse_overflow
    return SparseColonyState(
        tau=dense_aco.make_tau(jnp.full((n_pad, k), tau0, jnp.float32),
                               cfg),
        tau_def=jnp.asarray(np.float32(tau0)),
        ovf_city=jnp.full((n_pad, o), store.OVF_EMPTY, jnp.int32),
        ovf_tau=_make_ovf_tau(jnp.zeros((n_pad, o), jnp.float32), cfg),
        best_tour=best_tour,
        best_len=best_len,
        iteration=jnp.asarray(0, jnp.int32),
        key=jax.random.PRNGKey(cfg.seed if seed is None else seed),
    )


def _make_ovf_tau(ovf_f32, cfg: dense_aco.ACOConfig):
    """Overflow pages follow the store dtype but never carry an
    error-feedback residual: slots churn (adopt/evict) so a carried
    per-slot residual would attribute one edge's error to another."""
    if not quant.is_quantised(cfg.tau_dtype):
        return ovf_f32
    return quant.quantise(ovf_f32, cfg.tau_dtype)


@partial(jax.jit, static_argnames=("cfg", "ewt"))
def sparse_colony_step(problem: SparseProblem, state: SparseColonyState,
                       cfg: dense_aco.ACOConfig,
                       ewt: str) -> tuple:
    """One full sparse ACO iteration; mirrors ``aco.colony_step``.

    ``ewt`` (static): TSPLIB rounding rule for the lazy off-list
    distances; candidate-page distances are precomputed.

    Returns (new_state, it_best_len); with ``cfg.metrics``, additionally
    an ``obs.StepMetrics`` (tau stats over the (n, k) pages, overflow
    adoption/eviction counts from the ovf_city delta) — read-only
    reductions, bitwise-neutral to the state trajectory (DESIGN.md §13).
    """
    n = problem.n
    m = cfg.num_ants(n)
    n_act = problem.n_actual
    check_sparse_route(cfg, masked=n_act is not None)
    quantised = quant.is_quantised(cfg.tau_dtype)
    if quantised:
        # extra split feeds the two quantise-on-store steps (tau pages and
        # overflow pages); the fp32 branch keeps today's two-way split.
        key, k_tour, k_q = jax.random.split(state.key, 3)
    else:
        key, k_tour = jax.random.split(state.key)
        k_q = None

    # Named scopes as in aco.colony_step (construct, deposit): the
    # candidate-page weights are computed inside the construction loop, so
    # this route has no separate choice phase, and it runs no local search.
    with jax.named_scope("construct"):
        if cfg.construction == "partial":
            res = construct.partial_tours(
                k_tour, problem, state.tau, state.ovf_city, state.ovf_tau,
                state.best_tour, state.best_len, m, cfg.partial_window,
                cfg.selection, cfg.alpha, cfg.beta, ewt,
                use_pallas=cfg.use_pallas, draw_mode=cfg.draw_mode)
        else:
            res = construct.construct_sparse_tours(
                k_tour, problem, state.tau, state.ovf_city, state.ovf_tau, m,
                cfg.selection, cfg.alpha, cfg.beta, ewt,
                use_pallas=cfg.use_pallas, draw_mode=cfg.draw_mode)

    it_best_idx = jnp.argmin(res.lengths)
    it_best_len = res.lengths[it_best_idx]
    it_best_tour = res.tours[it_best_idx]
    if cfg.construction == "partial":
        # delta lengths are float32-approximate; re-measure the candidate
        # exactly before accepting, so the best sequence is monotone.
        it_best_len = store.sparse_tour_length(
            problem, it_best_tour[None, :], ewt, n_act)[0]

    improved = it_best_len < state.best_len
    best_len = jnp.where(improved, it_best_len, state.best_len)
    best_tour = jnp.where(improved, it_best_tour, state.best_tour)

    with jax.named_scope("deposit"):
        rho, q = cfg.rho, cfg.q
        if cfg.variant == "as":
            dep_tours, dep_w = res.tours, q / res.lengths
        elif cfg.variant == "mmas":
            if cfg.mmas_best == "global":
                dep_tours, dep_w = best_tour[None, :], (q / best_len)[None]
            else:
                dep_tours = it_best_tour[None, :]
                dep_w = (q / it_best_len)[None]
        elif cfg.variant == "acs":
            dep_tours = best_tour[None, :]
            dep_w = (rho * q / best_len)[None]
        else:
            raise ValueError(f"unknown variant {cfg.variant}")

        adopt = cfg.variant in ("mmas", "acs") and cfg.sparse_overflow > 0
        # Transient fp32 views for the update/clamp path (identity for fp32);
        # construction above consumed the resident payload directly.
        tau_full = quant.dequantise(state.tau) if quantised else state.tau
        ovf_full = (quant.dequantise(state.ovf_tau) if quantised
                    else state.ovf_tau)
        tau, tau_def, ovf_city, ovf_tau = pheromone.update_sparse(
            tau_full, state.tau_def, state.ovf_city, ovf_full,
            problem.cand, dep_tours, dep_w, rho, adopt, n_act)

        n_eff = n if n_act is None else n_act
        clamp = None
        if cfg.variant == "mmas":
            tau_max = q / (rho * best_len)
            tau_min = tau_max / (2.0 * n_eff)
            tau = jnp.clip(tau, tau_min, tau_max)
            tau_def = jnp.clip(tau_def, tau_min, tau_max)
            ovf_tau = jnp.clip(ovf_tau, tau_min, tau_max)
            clamp = (tau_min, tau_max)
        elif cfg.variant == "acs":
            tau0 = q / (n_eff * jnp.maximum(best_len, 1e-9))
            tau, tau_def, ovf_tau = pheromone.local_update_acs_sparse(
                tau, tau_def, ovf_tau, problem.cand, res.tours, cfg.xi, tau0,
                n_act)

        # Quantise-on-store: pages and overflow each requantise with their
        # own key; metrics below read the exact fp32 tau of this step.
        tau_store, ovf_store = tau, ovf_tau
        if quantised:
            k_q1, k_q2 = jax.random.split(k_q)
            tau_store = quant.requantise(
                tau, state.tau, cfg.tau_dtype,
                quant.round_key(cfg.tau_round, k_q1))
            ovf_store = quant.requantise(
                ovf_tau, state.ovf_tau, cfg.tau_dtype,
                quant.round_key(cfg.tau_round, k_q2))

    new_state = SparseColonyState(tau_store, tau_def, ovf_city, ovf_store,
                                  best_tour, best_len,
                                  state.iteration + 1, key)
    if not cfg.metrics:
        return new_state, it_best_len
    from repro.obs import metrics as obs_metrics
    # overflow churn from the ovf_city delta: a slot whose city changed to
    # a non-empty value was adopted; if it previously held another city,
    # that city was evicted to make room (pheromone.update_sparse's
    # evict-weakest-iff-stronger rule).
    changed = (ovf_city != state.ovf_city)
    adopted = jnp.sum((changed & (ovf_city != store.OVF_EMPTY))
                      .astype(jnp.int32))
    evicted = jnp.sum((changed & (state.ovf_city != store.OVF_EMPTY)
                       & (ovf_city != store.OVF_EMPTY)).astype(jnp.int32))
    mets = obs_metrics.step_metrics(
        res.lengths, it_best_len, best_len, improved, tau, clamp,
        ovf_adopted=adopted, ovf_evicted=evicted)
    return new_state, it_best_len, mets


def run_sparse(instance: tsp.TSPInstance, cfg: dense_aco.ACOConfig,
               state: Optional[SparseColonyState] = None,
               problem: Optional[SparseProblem] = None) -> SparseColonyState:
    """Python-loop driver for one sparse colony (jitted inner step)."""
    check_sparse_route(cfg)
    if problem is None:
        problem = make_sparse_problem_cfg(instance, cfg)
    if state is None:
        state = init_sparse_colony(instance, cfg)
    ewt = instance.edge_weight_type
    for _ in range(int(state.iteration), cfg.iterations):
        state = sparse_colony_step(problem, state, cfg, ewt)[0]
    return state

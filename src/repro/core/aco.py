"""The ACO engine: Ant System (paper's subject) plus MMAS / ACS variants.

State is a pytree (``ColonyState``) so that one colony step jits cleanly,
scans across iterations, shards across mesh axes (islands.py) and round-trips
through checkpoints (checkpoint/).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import localsearch, pheromone, quant, strategies, tsp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class ACOConfig:
    # Paper/Dorigo-Stützle recommended defaults.
    alpha: float = 1.0
    beta: float = 2.0
    rho: float = 0.5
    q: float = 1.0                 # deposit numerator (1/C^k scaled by q)
    m: Optional[int] = None        # ants; None => m = n (paper §V)
    variant: str = "as"            # as | mmas | acs
    construction: str = "data_parallel"
    selection: str = "iroulette"   # iroulette (paper) | gumbel (exact) | roulette
    # Per-(ant, city) randomness derivation (core/sampling.py): "packed"
    # keeps the historical flat-counter threefry draws; "counter" derives
    # each element's bits from an explicit (ant, city) counter, making the
    # draws invariant to the padded bucket width — the exactness basis of
    # the AOT program cache's neighbour-bucket route (DESIGN.md §16).
    draw_mode: str = "packed"      # packed | counter
    nn_k: int = 30                 # NN-list length (paper uses 30)
    deposit: str = "scatter"       # pheromone strategy (see pheromone.py)
    deposit_tile: int = 64
    iterations: int = 100
    seed: int = 0
    use_pallas: bool = False       # route choice/tour/deposit through kernels/
    # Local search (DESIGN.md §7): polish constructed tours before deposit.
    local_search: str = "none"     # localsearch.STRATEGIES key
    ls_every: int = 1              # apply every k-th iteration
    ls_tours: str = "all"          # all | iteration_best
    ls_rounds: int = 24            # bounded improvement rounds per application
    ls_improvement: str = "best"   # best | first
    ls_seg_max: int = 3            # Or-opt max segment length
    # MMAS
    mmas_best: str = "iteration"   # iteration | global
    # ACS
    q0: float = 0.9
    xi: float = 0.1
    # Sparse/paged representation (repro.sparse, DESIGN.md §12): O(n·k)
    # candidate-edge storage instead of dense (n, n) tensors.
    sparse: bool = False
    sparse_k: int = 32             # candidate-list width of the sparse pages
    sparse_overflow: int = 4       # off-list adoption slots per city
    partial_window: int = 64       # Partial-ACO rebuild window (construction="partial")
    # Quantised resident pheromone (core/quant.py, DESIGN.md §15): tau is
    # held as a low-precision QuantTau payload (+ per-row scales for int8)
    # and dequantised to a transient fp32 tensor for each step's compute;
    # the Pallas selection kernels dequantise tile-by-tile instead and
    # never materialise the fp32 matrix.  "fp32" keeps today's raw Array
    # leaf — bitwise-identical routes, unchanged pytree structure.
    tau_dtype: str = "fp32"        # fp32 | bf16 | int8
    tau_round: str = "stochastic"  # quantise-on-store rounding | "nearest"
    tau_compensation: bool = False  # carry fp32 error-feedback residual
    # In-jit telemetry (repro.obs, DESIGN.md §13): when True, colony_step /
    # sparse_colony_step additionally return an obs.StepMetrics pytree of
    # per-iteration convergence scalars, and engine.run_batch carries one
    # row per instance next to the ColonyState.  Statically gated and
    # bitwise-neutral: tours/lengths/tau/keys are identical either way.
    metrics: bool = False

    def num_ants(self, n: int) -> int:
        return self.m if self.m is not None else n


class ColonyState(NamedTuple):
    tau: "Array | quant.QuantTau"  # (n, n) pheromone (QuantTau if quantised)
    best_tour: Array      # (n,) int32
    best_len: Array       # () float32
    iteration: Array      # () int32
    key: Array            # PRNG key


class Hyper(NamedTuple):
    """Per-instance ACO hyperparameters as traced scalar operands.

    When attached to ``Problem.hyper`` these *override* the static
    ``ACOConfig`` fields of the same name inside ``colony_step``, and —
    because they are operands, per-instance under vmap — one compiled
    batched program (solver/engine.run_batch, solver/streaming) can mix
    tuning profiles across slots.  Exponentiation then takes the generic
    ``x ** p`` route instead of the static integer-folding fast path, so
    numerics are comparable only *within* the operand mode: batched ==
    solo holds bitwise when both carry a Hyper (tests/test_solver.py).
    """
    alpha: Array          # () float32  choice exponent on tau
    beta: Array           # () float32  choice exponent on eta
    rho: Array            # () float32  evaporation rate
    q: Array              # () float32  deposit numerator

    @classmethod
    def make(cls, cfg: "ACOConfig", alpha: Optional[float] = None,
             beta: Optional[float] = None, rho: Optional[float] = None,
             q: Optional[float] = None) -> "Hyper":
        """Profile from a config plus any per-field overrides."""
        def pick(v, d):
            return jnp.float32(d if v is None else v)
        return cls(pick(alpha, cfg.alpha), pick(beta, cfg.beta),
                   pick(rho, cfg.rho), pick(q, cfg.q))


class Problem(NamedTuple):
    """Device-resident constants for one TSP instance.

    ``n_actual`` is None for ordinary instances.  For padded instances
    (solver/batch.py: phantom cities at inf distance, eta exactly 0) it is
    the scalar count of real cities — a traced operand, per-instance under
    vmap — and flips colony_step into mask-aware mode (DESIGN.md §8).

    ``hyper`` is None for ordinary instances (hyperparameters come from the
    static ACOConfig); when set, its per-instance alpha/beta/rho/q operands
    take precedence (DESIGN.md §9).
    """
    dist: Array           # (n, n) float32
    eta: Array            # (n, n) float32  (1/d)
    nn: Array             # (n, k) int32
    n_actual: Optional[Array] = None   # () int32, or None (unpadded)
    hyper: Optional[Hyper] = None      # per-instance overrides, or None


def make_problem(instance: tsp.TSPInstance, nn_k: int = 30) -> Problem:
    dist = jnp.asarray(instance.distances())
    eta = tsp.heuristic_matrix(dist)
    nn = tsp.nn_lists(dist, min(nn_k, instance.n - 1))
    return Problem(dist, eta, nn)


def initial_tau(instance: tsp.TSPInstance, cfg: ACOConfig,
                rho: Optional[float] = None) -> float:
    """tau0 = m / C_nn (AS), 1/(rho C_nn) (MMAS), 1/(n C_nn) (ACS).

    ``rho`` overrides cfg.rho (per-instance Hyper profiles: MMAS tau0
    depends on the evaporation rate, so a slot's initial trail must match
    the profile it will run under).
    """
    d = instance.distances()
    _, c_nn = tsp.nearest_neighbour_tour(d)
    n = instance.n
    m = cfg.num_ants(n)
    if cfg.variant == "mmas":
        return 1.0 / ((cfg.rho if rho is None else rho) * c_nn)
    if cfg.variant == "acs":
        return 1.0 / (n * c_nn)
    return m / c_nn


def make_tau(tau_f32: Array, cfg: ACOConfig) -> "Array | quant.QuantTau":
    """Initial tau in the config's resident representation: raw fp32 (the
    bitwise-stable default) or a deterministically-rounded QuantTau.  Used
    by every init path (solo, engine slot stacks, streaming refill
    surgery) so a refilled slot starts from exactly what a solo quantised
    run starts from."""
    if not quant.is_quantised(cfg.tau_dtype):
        return tau_f32
    quant.validate_tau_dtype(cfg.tau_dtype, cfg.tau_round)
    return quant.quantise(tau_f32, cfg.tau_dtype,
                          compensation=cfg.tau_compensation)


def init_colony(instance: tsp.TSPInstance, cfg: ACOConfig,
                seed: Optional[int] = None) -> ColonyState:
    n = instance.n
    tau0 = initial_tau(instance, cfg)
    key = jax.random.PRNGKey(cfg.seed if seed is None else seed)
    return ColonyState(
        tau=make_tau(jnp.full((n, n), tau0, jnp.float32), cfg),
        best_tour=jnp.arange(n, dtype=jnp.int32),
        best_len=jnp.asarray(np.float32(np.inf)),
        iteration=jnp.asarray(0, jnp.int32),
        key=key,
    )


def _choice(tau: Array, eta: Array, cfg: ACOConfig, alpha, beta,
            n_actual: Optional[Array] = None) -> Array:
    if cfg.use_pallas:
        # alpha/beta are the hyper-resolved values; on the kernel route
        # check_kernel_route has already guaranteed they are the static
        # config floats (traced Hyper exponents are rejected upstream).
        from repro.kernels import ops as kops
        return kops.choice_info(tau, eta, alpha, beta, n_actual)
    return strategies.choice_matrix(tau, eta, alpha, beta)


def ls_config(cfg: ACOConfig) -> localsearch.LocalSearchConfig:
    """Derive the LocalSearchConfig embedded in an ACOConfig."""
    return localsearch.LocalSearchConfig(
        kind=cfg.local_search, rounds=cfg.ls_rounds,
        improvement=cfg.ls_improvement, seg_max=cfg.ls_seg_max,
        use_pallas=cfg.use_pallas)


def polish_tours(problem: Problem, tours: Array,
                 cfg: ACOConfig) -> tuple[Array, Array]:
    """Local-search-improve (m, n) tours; returns (tours, lengths).

    Shared by colony_step (below) and the island exchange (islands.py),
    which polishes migrated elite tours before they deposit.  Mask-aware
    when problem.n_actual is set (padded instances).
    """
    return localsearch.improve_with_lengths(
        problem.dist, problem.nn, tours, ls_config(cfg), problem.n_actual)


def _apply_local_search(problem: Problem, res: strategies.TourResult,
                        iteration: Array, cfg: ACOConfig
                        ) -> strategies.TourResult:
    """Polish constructed tours per cfg.ls_tours, every cfg.ls_every iters.

    The ls_every gate is a lax.cond on the traced iteration counter: it
    skips the work on a single colony, but under vmap (the island model
    batches colony_step over islands) cond lowers to select and both
    branches run — there ls_every>1 only changes *which* iterations'
    results are kept, not the compute.  The while_loop early-exit in
    localsearch.improve keeps the dead branch cheap (converged tours exit
    after one evaluation round).
    """
    if cfg.ls_tours not in ("all", "iteration_best"):
        raise ValueError(f"unknown ls_tours {cfg.ls_tours!r}")

    def run(args):
        tours, lengths = args
        if cfg.ls_tours == "iteration_best":
            ib = jnp.argmin(lengths)
            pol, pol_len = polish_tours(problem, tours[ib][None, :], cfg)
            return tours.at[ib].set(pol[0]), lengths.at[ib].set(pol_len[0])
        return polish_tours(problem, tours, cfg)

    if cfg.ls_every <= 1:
        tours, lengths = run((res.tours, res.lengths))
    else:
        tours, lengths = jax.lax.cond(
            iteration % cfg.ls_every == 0, run, lambda args: args,
            (res.tours, res.lengths))
    return strategies.TourResult(tours, lengths)


@partial(jax.jit, static_argnames=("cfg",))
def colony_step(problem: Problem, state: ColonyState,
                cfg: ACOConfig) -> tuple:
    """One full ACO iteration: construct m tours, update pheromone, track best.

    Returns (new_state, iteration_best_length); with ``cfg.metrics`` set,
    (new_state, iteration_best_length, obs.StepMetrics).  The metrics are
    read-only reductions over intermediates this step computes anyway — no
    extra PRNG draws, no reordering — so the state trajectory is bitwise
    identical either way (tests/test_obs.py).
    """
    n = problem.dist.shape[0]
    m = cfg.num_ants(n)
    n_act = problem.n_actual           # None, or traced () int32 (padded)
    h = problem.hyper                  # None, or traced per-instance Hyper
    quantised = quant.is_quantised(cfg.tau_dtype)
    if cfg.use_pallas:
        # Masked (padded) instances are kernel-supported; per-instance
        # Hyper operands are not (static kernel exponents) — one typed
        # rejection point for the whole kernel route (DESIGN.md §10).
        from repro.kernels import ops as kops
        kops.check_kernel_route(masked=n_act is not None,
                                hyper=h is not None,
                                tau_dtype=cfg.tau_dtype)
    elif quantised:
        # Pure-JAX quantised route still goes through the single rejection
        # point: quantised x per-instance Hyper is unsupported everywhere.
        from repro.kernels import ops as kops
        kops.check_kernel_route(hyper=h is not None, tau_dtype=cfg.tau_dtype)
    alpha = cfg.alpha if h is None else h.alpha
    beta = cfg.beta if h is None else h.beta
    rho = cfg.rho if h is None else h.rho
    q = cfg.q if h is None else h.q
    if quantised:
        # One extra split feeds quantise-on-store; the fp32 branch keeps
        # today's two-way split, so its key trajectory is untouched.
        key, k_tour, k_q = jax.random.split(state.key, 3)
    else:
        key, k_tour = jax.random.split(state.key)
        k_q = None
    # Transient fp32 view for this step's compute (identity for fp32).
    tau_full = quant.dequantise(state.tau) if quantised else state.tau

    method = cfg.construction
    if cfg.use_pallas and method == "data_parallel":
        # kernels/fused_select: the whole construction step (gather,
        # weighting, masking, selection) is one kernel — no (n, n) choice
        # precompute on this route at all.
        method = "fused"

    # Named scopes (choice, construct, local_search, deposit) put each
    # phase's name in the metadata of every device operation it lowers
    # to, Pallas kernels included, so a profiler capture attributes
    # device time to the phases of the step.  Metadata only: the program
    # and its results are the same without them.
    tau_c, tau_scale = tau_full, None
    if method == "fused":
        choice_info = jnp.zeros((1, 1), jnp.float32)   # unused by the step
        if quantised:
            # The fused kernel dequantises tile-by-tile in its epilogue:
            # hand it the resident payload (+ per-row scales for int8)
            # instead of a materialised fp32 matrix.
            tau_c = state.tau.q
            tau_scale = state.tau.scale if cfg.tau_dtype == "int8" else None
    else:
        with jax.named_scope("choice"):
            choice_info = _choice(tau_full, problem.eta, cfg, alpha, beta,
                                  n_act)

    with jax.named_scope("construct"):
        res = strategies.construct_tours(
            k_tour, problem.dist, choice_info, m,
            method=method, selection=cfg.selection,
            nn=problem.nn, tau=tau_c, eta=problem.eta,
            alpha=alpha, beta=beta, n_actual=n_act,
            tau_scale=tau_scale, draw_mode=cfg.draw_mode,
        )

    pre_ls_lengths = None
    if cfg.local_search != "none":
        # improved tours drive the deposit: LS runs before best-tracking
        # and before the pheromone update (DESIGN.md §7).
        if cfg.metrics:
            pre_ls_lengths = res.lengths    # acceptance-rate baseline
        with jax.named_scope("local_search"):
            res = _apply_local_search(problem, res, state.iteration, cfg)

    it_best_idx = jnp.argmin(res.lengths)
    it_best_len = res.lengths[it_best_idx]
    it_best_tour = res.tours[it_best_idx]

    improved = it_best_len < state.best_len
    best_len = jnp.where(improved, it_best_len, state.best_len)
    best_tour = jnp.where(improved, it_best_tour, state.best_tour)

    with jax.named_scope("deposit"):
        if cfg.variant == "as":
            dep_tours, dep_w = res.tours, q / res.lengths
        elif cfg.variant == "mmas":
            if cfg.mmas_best == "global":
                dep_tours = best_tour[None, :]
                dep_w = (q / best_len)[None]
            else:
                dep_tours = it_best_tour[None, :]
                dep_w = (q / it_best_len)[None]
        elif cfg.variant == "acs":
            dep_tours = best_tour[None, :]
            dep_w = (rho * q / best_len)[None]
        else:
            raise ValueError(f"unknown variant {cfg.variant}")

        if cfg.use_pallas:
            from repro.kernels import ops as kops
            tau = kops.pheromone_update(tau_full, dep_tours, dep_w, rho,
                                        n_actual=n_act)
        else:
            tau = pheromone.update(tau_full, dep_tours, dep_w, rho,
                                   strategy=cfg.deposit, tile=cfg.deposit_tile,
                                   n_actual=n_act)

        # MMAS/ACS normalisations use the real city count of padded instances.
        n_eff = n if n_act is None else n_act
        clamp = None
        if cfg.variant == "mmas":
            tau_max = q / (rho * best_len)
            tau_min = tau_max / (2.0 * n_eff)
            tau = jnp.clip(tau, tau_min, tau_max)
            clamp = (tau_min, tau_max)
        elif cfg.variant == "acs":
            # Parallel-ACS local rule: decay edges crossed this iteration.
            f, t = pheromone.tour_edges(res.tours, n_act)
            tau0 = q / (n_eff * jnp.maximum(best_len, 1e-9))
            ew = None
            if n_act is not None:
                # phantom-tail crossings must not decay (multiplicity 0)
                idx = jnp.arange(n, dtype=jnp.int32)
                ew = jnp.broadcast_to((idx < n_act).astype(tau.dtype),
                                      res.tours.shape).ravel()
            tau = pheromone.local_update_acs(tau, f.ravel(), t.ravel(), cfg.xi,
                                             tau0, w=ew)

        # Quantise-on-store (quant.py): the fp32 result of this step's update
        # becomes the next resident payload; metrics below read the exact fp32
        # tau this step computed, before the store rounds it.
        tau_store = tau
        if quantised:
            tau_store = quant.requantise(
                tau, state.tau, cfg.tau_dtype,
                quant.round_key(cfg.tau_round, k_q))

    new_state = ColonyState(tau_store, best_tour, best_len,
                            state.iteration + 1, key)
    if not cfg.metrics:
        return new_state, it_best_len
    from repro.obs import metrics as obs_metrics
    mets = obs_metrics.step_metrics(
        res.lengths, it_best_len, best_len, improved, tau, clamp,
        pre_ls_lengths)
    return new_state, it_best_len, mets


def run(instance: tsp.TSPInstance, cfg: ACOConfig,
        state: Optional[ColonyState] = None,
        checkpoint_cb=None, checkpoint_every: int = 0):
    """Python-loop driver (checkpointable); inner step is jitted.

    ``cfg.sparse=True`` routes to the O(n·k) paged representation
    (repro.sparse.run_sparse; returns a SparseColonyState — same
    best_tour/best_len/iteration/key fields, paged tau instead of (n, n)).
    """
    if cfg.sparse:
        from repro import sparse as sparse_mod
        return sparse_mod.run_sparse(instance, cfg, state)
    problem = make_problem(instance, cfg.nn_k)
    if state is None:
        state = init_colony(instance, cfg)
    start = int(state.iteration)
    for i in range(start, cfg.iterations):
        state = colony_step(problem, state, cfg)[0]
        if checkpoint_cb and checkpoint_every and (i + 1) % checkpoint_every == 0:
            checkpoint_cb(state)
    return state


@partial(jax.jit, static_argnames=("cfg", "iterations"))
def run_scan(problem: Problem, state: ColonyState, cfg: ACOConfig,
             iterations: int) -> tuple[ColonyState, Array]:
    """Fused multi-iteration driver (benchmarks / island inner loop).

    Returns (state, it_best per iteration); with ``cfg.metrics`` the aux
    is ``(it_best, StepMetrics)`` with every leaf stacked over iterations
    — a full convergence curve from one jitted call.  The scan carry
    threads the stagnation counter the per-step metrics cannot know.
    """
    if cfg.metrics:
        def body_m(carry, _):
            st, since = carry
            st2, it_best, m = colony_step(problem, st, cfg)
            since = jnp.where(m.improved > 0, 0, since + 1)
            return (st2, since), (it_best, m._replace(stagnation=since))

        (state, _), aux = jax.lax.scan(
            body_m, (state, jnp.asarray(0, jnp.int32)), None,
            length=iterations)
        return state, aux

    def body(st, _):
        st, it_best = colony_step(problem, st, cfg)
        return st, it_best

    return jax.lax.scan(body, state, None, length=iterations)

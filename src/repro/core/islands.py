"""Distributed ACO on the production mesh (DESIGN.md §4).

Two orthogonal, composable levels of parallelism — the paper's two stages,
lifted from the chip to the network:

1. **Island model** over the ``pod`` x ``data`` axes (Stützle '98 /
   Michel-Middendorf, the paper's §III related work): each island runs an
   independent colony; every ``exchange_every`` local iterations the islands
   (a) migrate their best tour around a ``ppermute`` ring — an immigrant
   better than the local best replaces it and deposits like an elite ant —
   and (b) optionally mix pheromone trails toward the population mean
   (``tau <- (1-lam) tau + lam mean``, lam=0 disables). Exchanges are the
   only synchronisation points: stragglers cost nothing in between
   (bounded-staleness BSP), and the exchange collective itself is a
   fixed-size (n,)-int message, independent of colony size.

2. **City-sharded colony** over the ``model`` axis, for instances whose
   pheromone matrix does not fit one device: the choice matrix, tabu mask
   and pheromone matrix are column-sharded; each shard computes a *partial
   best* next city and an ``all_gather`` of the (value, index) pairs picks
   the winner — the paper's Fig.1 tile-then-reduce scheme where a "tile" is
   a whole accelerator and the reduction runs over ICI. The deposit shard is
   a column slab computed with the one-hot-matmul kernel (no all-reduce of
   the n^2 matrix is ever needed: tours are replicated, the deposit is
   computed owner-local — communication is O(m) per step, not O(n^2)).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import aco, pheromone, quant, strategies, tsp

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class IslandConfig:
    aco: aco.ACOConfig = dataclasses.field(default_factory=aco.ACOConfig)
    exchange_every: int = 8       # local iterations between exchanges
    rounds: int = 4               # number of exchange rounds
    mix_lambda: float = 0.1       # pheromone mixing toward population mean
    migrate: bool = True          # best-tour ring migration
    elite_weight: float = 1.0     # immigrant deposit scale


# --------------------------------------------------------------------------
# Island model (pod/data axes)
# --------------------------------------------------------------------------

def init_island_states(instance: tsp.TSPInstance, cfg: IslandConfig,
                       n_islands: int, seed0: int = 0) -> aco.ColonyState:
    """Stacked ColonyState with leading island axis; distinct RNG streams."""
    states = [aco.init_colony(instance, cfg.aco, seed=seed0 + i)
              for i in range(n_islands)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def _exchange(st: aco.ColonyState, problem: aco.Problem, cfg: IslandConfig,
              axis: str | tuple[str, ...],
              axis_sizes: dict[str, int]) -> aco.ColonyState:
    """Ring migration + pheromone mixing. st leaves have leading local axis 1.

    axis_sizes carries the static mesh extents (mesh.shape) — axis sizes
    must be known at trace time for the ppermute ring and the early-out.
    """
    ax = (axis,) if isinstance(axis, str) else tuple(axis)
    size = 1
    for a in ax:
        size *= axis_sizes[a]
    if size == 1:
        return st

    new_tau = st.tau
    best_tour, best_len = st.best_tour, st.best_len
    if cfg.migrate:
        # flatten multi-axis ring: successor along the last axis with carry.
        perm_axis = ax[-1]
        sz = axis_sizes[perm_axis]
        perm = [(i, (i + 1) % sz) for i in range(sz)]
        imm_tour = jax.lax.ppermute(st.best_tour, perm_axis, perm)
        imm_len = jax.lax.ppermute(st.best_len, perm_axis, perm)
        if cfg.aco.local_search != "none":
            # polish the immigrant before it competes and deposits
            # (DESIGN.md §7): the local leading axis doubles as the batch.
            imm_tour, imm_len = aco.polish_tours(problem, imm_tour, cfg.aco)
        better = imm_len < st.best_len
        best_tour = jnp.where(better, imm_tour, st.best_tour)
        best_len = jnp.where(better, imm_len, st.best_len)
        # immigrant deposits like an elite ant
        # local leading axis (1 island/device) doubles as the ant axis m=1.
        w = (cfg.elite_weight * cfg.aco.q / jnp.maximum(imm_len, 1e-9))
        dep = pheromone.deposit(st.tau.shape[-1], imm_tour, w, "scatter")
        new_tau = st.tau + jnp.where(better[..., None, None], dep, 0.0)
    if cfg.mix_lambda > 0.0:
        mean_tau = jax.lax.pmean(new_tau, ax)
        new_tau = (1 - cfg.mix_lambda) * new_tau + cfg.mix_lambda * mean_tau
    return aco.ColonyState(new_tau, best_tour, best_len, st.iteration, st.key)


def run_islands(instance: tsp.TSPInstance, cfg: IslandConfig, mesh: Mesh,
                island_axes: tuple[str, ...] = ("data",),
                state: Optional[aco.ColonyState] = None,
                checkpoint_cb=None) -> aco.ColonyState:
    """Run the island model with one island per device along island_axes.

    Any mesh axis not in island_axes must have size 1 (or be consumed by the
    sharded-colony path below). Returns the stacked island states; global
    best = argmin over the island axis.
    """
    if quant.is_quantised(cfg.aco.tau_dtype):
        from repro.kernels import ops as kops
        raise kops.UnsupportedKernelRoute(
            "the island model cannot run over a quantised pheromone store "
            f"(tau_dtype={cfg.aco.tau_dtype!r}): immigrant deposits and "
            "pmean trail mixing operate on raw fp32 tau leaves. Run "
            "tau_dtype='fp32' for islands, or use the engine/streaming "
            "routes for quantised colonies.")
    n_islands = int(np.prod([mesh.shape[a] for a in island_axes]))
    if state is None:
        state = init_island_states(instance, cfg, n_islands)
    problem = aco.make_problem(instance, cfg.aco.nn_k)

    spec = P(island_axes)
    st_specs = aco.ColonyState(
        tau=P(island_axes, None, None), best_tour=P(island_axes, None),
        best_len=spec, iteration=spec, key=P(island_axes, None))

    @partial(shard_map, mesh=mesh, in_specs=(st_specs,),
             out_specs=st_specs, check_vma=False)
    def round_fn(st: aco.ColonyState) -> aco.ColonyState:
        # local leading axis is 1 island per device: vmap over it.
        def one(st1):
            st1, _ = aco.run_scan(problem, st1, cfg.aco, cfg.exchange_every)
            return st1
        st = jax.vmap(one)(st)
        return _exchange(st, problem, cfg, island_axes,
                         {a: mesh.shape[a] for a in island_axes})

    step = jax.jit(round_fn)
    for r in range(cfg.rounds):
        state = step(state)
        if checkpoint_cb is not None:
            checkpoint_cb(state, r)
    return state


def global_best(state: aco.ColonyState) -> tuple[np.ndarray, float]:
    lens = np.asarray(state.best_len)
    i = int(np.argmin(lens))
    return np.asarray(state.best_tour[i]), float(lens[i])


# --------------------------------------------------------------------------
# City-sharded colony (model axis) — the paper's tiling at mesh level
# --------------------------------------------------------------------------

class ShardedColonyState(NamedTuple):
    tau: Array        # (n, n/S) column shard per device
    best_tour: Array  # (n,) replicated
    best_len: Array   # ()
    iteration: Array  # ()
    key: Array


def init_sharded_colony(instance: tsp.TSPInstance, cfg: aco.ACOConfig,
                        mesh: Mesh, axis: str = "model") -> ShardedColonyState:
    n = instance.n
    tau0 = aco.initial_tau(instance, cfg)
    s = mesh.shape[axis]
    assert n % s == 0, f"n={n} must divide model axis {s}"
    tau = jnp.full((n, n), tau0, jnp.float32)
    rep = NamedSharding(mesh, P())
    return ShardedColonyState(
        tau=jax.device_put(tau, NamedSharding(mesh, P(None, axis))),
        best_tour=jax.device_put(jnp.arange(n, dtype=jnp.int32), rep),
        best_len=jax.device_put(jnp.asarray(np.inf, jnp.float32), rep),
        iteration=jax.device_put(jnp.asarray(0, jnp.int32), rep),
        key=jax.device_put(jax.random.PRNGKey(cfg.seed), rep),
    )


def _sharded_construct(dist_l: Array, choice_l: Array, key: Array, m: int,
                       n: int, nl: int, axis: str, selection: str
                       ) -> tuple[Array, Array]:
    """Construct m tours with column-sharded choice matrix.

    dist_l/choice_l: (n, nl) local column slabs. Returns (tours (m,n)
    replicated, lengths (m,)).
    """
    sidx = jax.lax.axis_index(axis)
    col0 = sidx * nl
    kp, kc = jax.random.split(key)
    start = jax.random.randint(kp, (m,), 0, n, dtype=jnp.int32)  # replicated
    ants = jnp.arange(m)

    vis0 = jnp.zeros((m, nl), jnp.bool_)
    own0 = (start >= col0) & (start < col0 + nl)
    vis0 = vis0.at[ants, jnp.clip(start - col0, 0, nl - 1)].max(own0)

    def body(carry, t):
        cur, vis, lens = carry
        k = jax.random.fold_in(kc, t)
        k = jax.random.fold_in(k, sidx)          # decorrelated per shard
        w = choice_l[cur] * (~vis)               # (m, nl)
        u = jax.random.uniform(k, w.shape, w.dtype, minval=1e-6, maxval=1.0)
        v = w * u                                # iroulette partial
        pv = jnp.max(v, axis=1)                  # (m,) partial best value
        pi = jnp.argmax(v, axis=1).astype(jnp.int32) + col0
        # mesh-level reduction over shards: the paper's final argmax, as two
        # (m,)-sized all-reduces (pmax value + pmin index among the max-
        # holders) instead of an (S, m) all-gather — 16x fewer bytes and
        # bit-identical first-argmax semantics (smallest winning index).
        gmax = jax.lax.pmax(pv.astype(jnp.float32), axis)
        cand = jnp.where(pv.astype(jnp.float32) == gmax, pi,
                         jnp.int32(2**31 - 1))
        nxt = jax.lax.pmin(cand, axis)
        own = (nxt >= col0) & (nxt < col0 + nl)
        vis = vis.at[ants, jnp.clip(nxt - col0, 0, nl - 1)].max(own)
        # length contribution d[cur, nxt]: owner of nxt column adds it.
        dloc = dist_l[cur, jnp.clip(nxt - col0, 0, nl - 1)]
        lens = lens + jnp.where(own, dloc, 0.0)
        return (nxt, vis, lens), nxt

    lens0 = jnp.zeros((m,), jnp.float32)
    (last, _, lens), steps = jax.lax.scan(
        body, (start, vis0, lens0), jnp.arange(1, n))
    # closing edge last->start
    ownc = (start >= col0) & (start < col0 + nl)
    lens = lens + jnp.where(
        ownc, dist_l[last, jnp.clip(start - col0, 0, nl - 1)], 0.0)
    lens = jax.lax.psum(lens, axis)
    tours = jnp.concatenate([start[None], steps], 0).T.astype(jnp.int32)
    return tours, lens


def sharded_colony_step_fn(mesh: Mesh, n: int, cfg: aco.ACOConfig,
                           axis: str = "model", use_pallas: bool = False,
                           ants_axis: Optional[str] = None,
                           choice_dtype=jnp.float32):
    """Build the jitted city-sharded colony step for a given mesh/instance.

    ants_axis: additionally shard the ant population over this axis (the
    paper's task-level parallelism lifted to the mesh: one colony, ants split
    m/|data| per row, deposit psum'd over the rows). choice_dtype=bf16 halves
    the per-step choice-row gather traffic (the memory-bound term of the
    construction loop).
    """
    s = mesh.shape[axis]
    nl = n // s
    m = cfg.num_ants(n)
    d_ants = mesh.shape[ants_axis] if ants_axis else 1
    assert m % d_ants == 0
    m_l = m // d_ants

    dspec = P(None, axis)
    st_spec = ShardedColonyState(
        tau=dspec, best_tour=P(None), best_len=P(), iteration=P(), key=P(None))

    def step(dist_l: Array, eta_l: Array, st: ShardedColonyState):
        choice_l = strategies.choice_matrix(
            st.tau, eta_l, cfg.alpha, cfg.beta).astype(choice_dtype)
        key, k_t = jax.random.split(st.key)
        if ants_axis:
            k_t = jax.random.fold_in(k_t, jax.lax.axis_index(ants_axis))
        tours, lengths = _sharded_construct(
            dist_l, choice_l, k_t, m_l, n, nl, axis, cfg.selection)
        ib = jnp.argmin(lengths)
        it_len = lengths[ib]
        it_tour = tours[ib]
        if ants_axis:
            # global iteration-best across ant shards: tiny all-gather
            lens_all = jax.lax.all_gather(it_len, ants_axis)     # (D,)
            tours_all = jax.lax.all_gather(it_tour, ants_axis)   # (D, n)
            gb = jnp.argmin(lens_all)
            it_len = lens_all[gb]
            it_tour = tours_all[gb]
        better = it_len < st.best_len
        best_len = jnp.where(better, it_len, st.best_len)
        best_tour = jnp.where(better, it_tour, st.best_tour)
        # owner-local column-slab deposit (communication-free on the city
        # axis; psum over ant shards when the population is split).
        col0 = jax.lax.axis_index(axis) * nl
        frm = tours.ravel()
        to = jnp.roll(tours, -1, axis=-1).ravel()
        wrep = jnp.repeat(cfg.q / lengths, n)
        f2 = jnp.concatenate([frm, to])
        t2 = jnp.concatenate([to, frm]) - col0   # local column frame
        w2 = jnp.concatenate([wrep, wrep])
        t2 = jnp.where((t2 >= 0) & (t2 < nl), t2, -1)
        if use_pallas:
            from repro.kernels import ops as kops
            from repro.kernels import pheromone_update as pu_k
            tau = pu_k.pheromone_update(st.tau, f2, t2, w2, cfg.rho,
                                        interpret=kops.INTERPRET)
            dep = tau - (1 - cfg.rho) * st.tau
        else:
            valid = t2 >= 0
            dep = jnp.zeros((n, nl), jnp.float32).at[
                jnp.where(valid, f2, 0), jnp.where(valid, t2, 0)
            ].add(jnp.where(valid, w2, 0.0))
        if ants_axis:
            dep = jax.lax.psum(dep, ants_axis)
        tau = (1 - cfg.rho) * st.tau + dep
        return ShardedColonyState(tau, best_tour, best_len,
                                  st.iteration + 1, key), it_len

    smapped = shard_map(step, mesh=mesh, in_specs=(dspec, dspec, st_spec),
                        out_specs=(st_spec, P()), check_vma=False)
    return jax.jit(smapped)


def run_sharded_colony(instance: tsp.TSPInstance, cfg: aco.ACOConfig,
                       mesh: Mesh, axis: str = "model",
                       iterations: Optional[int] = None,
                       state: Optional[ShardedColonyState] = None
                       ) -> ShardedColonyState:
    if quant.is_quantised(cfg.tau_dtype):
        from repro.kernels import ops as kops
        raise kops.UnsupportedKernelRoute(
            "the city-sharded colony cannot run over a quantised pheromone "
            f"store (tau_dtype={cfg.tau_dtype!r}): tau column slabs are raw "
            "fp32 per-device shards. Run tau_dtype='fp32' on this route.")
    n = instance.n
    d = jnp.asarray(instance.distances())
    eta = tsp.heuristic_matrix(d)
    sh = NamedSharding(mesh, P(None, axis))
    d = jax.device_put(d, sh)
    eta = jax.device_put(eta, sh)
    if state is None:
        state = init_sharded_colony(instance, cfg, mesh, axis)
    step = sharded_colony_step_fn(mesh, n, cfg, axis)
    for _ in range(iterations or cfg.iterations):
        state, _ = step(d, eta, state)
    return state

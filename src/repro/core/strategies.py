"""Tour-construction strategies (paper §IV.A).

The strategy ladder mirrors Table II of the paper:

1. ``task_baseline``  task parallelism, one logical thread per ant,
                      heuristic values recomputed at every construction step
                      (the paper's version 1 — "redundantly calculates
                      heuristic information").
2. ``task_choice``    task parallelism + precomputed choice_info
                      (the paper's version 2, "Choice kernel").
3. ``nn_list``        nearest-neighbour candidate lists with best-unvisited
                      fallback (the paper's version 4; versions 5/6 are
                      GPU-memory-placement variants with no TPU analogue —
                      see DESIGN.md §2).
4. ``data_parallel``  the paper's contribution (version 7/8): the whole
                      colony's step is one (m, n) tensor op — gather choice
                      rows, mask tabu, multiply by per-city randoms, reduce.
                      On TPU the city axis lives in VPU lanes; the Pallas
                      ``tour_select`` kernel (kernels/tour_select.py) is the
                      tiled in-VMEM version and can be injected via
                      ``step_impl``.

All variants share one lax.scan skeleton so that solution-quality parity
(claim C6) is attributable to the selection semantics only.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import sampling, tsp

Array = jax.Array


class TourState(NamedTuple):
    cur: Array      # (m,) int32 current city
    visited: Array  # (m, n) bool tabu list


class TourResult(NamedTuple):
    tours: Array    # (m, n) int32 city permutations
    lengths: Array  # (m,) float32 closed-tour lengths


def place_ants(key: Array, m: int, n: int,
               n_actual: Optional[Array] = None) -> Array:
    """Random initial city per ant (paper: 'ants are randomly placed').

    ``n_actual`` (traced scalar) bounds placement to the real cities of a
    padded instance; the draw is bitwise identical to the unpadded draw for
    the same key (threefry bits are counter-mode in the ant index).
    """
    hi = n if n_actual is None else n_actual
    return jax.random.randint(key, (m,), 0, hi, dtype=jnp.int32)


def _mark_visited(visited: Array, cities: Array) -> Array:
    """Set ``visited[i, cities[i]]`` for every ant i, by a one-hot OR.

    Bitwise the scatter ``visited.at[ants, cities].set(True)``: each row
    gains one True, and an index past the last column matches none, as the
    scatter drops it (cities are never negative).  One elementwise (m, n)
    pass, where the scatter's per-ant writes took over half of a
    1002-city iteration's device time on a TPU v5e.  The sparse path
    (sparse/construct.py) keeps its scatter: at its n an (m, n) pass per
    step outweighs its O(m k).
    """
    n = visited.shape[-1]
    return visited | (jnp.arange(n, dtype=jnp.int32)[None, :]
                      == cities[:, None])


def _init_state(start: Array, n: int) -> TourState:
    m = start.shape[0]
    return TourState(start, _mark_visited(jnp.zeros((m, n), jnp.bool_), start))


def _finish(start: Array, steps: Array, dist: Array,
            n_actual: Optional[Array] = None) -> TourResult:
    """steps (n-1, m) emitted cities -> tours (m, n) + lengths."""
    tours = jnp.concatenate([start[None, :], steps], axis=0).T  # (m, n)
    tours = tours.astype(jnp.int32)
    if n_actual is not None:
        return TourResult(tours, tsp.tour_length(dist, tours, n_actual))
    nxt = jnp.roll(tours, -1, axis=-1)
    lengths = tsp.edge_sum(dist[tours, nxt])
    return TourResult(tours, lengths)


StepImpl = Callable[[Array, Array, TourState, int, dict], Array]
# (key, choice_info, state, t, extras) -> next city (m,)
# Steps are MODULE-LEVEL functions keyed by (method, selection) so that
# repeated construct_tours calls hit the jit cache (a fresh closure per call
# would retrace every time — observed as ~1.4 s/call of pure compile).


def _make_dense_step(selector: str, draw_mode: str = "packed") -> StepImpl:
    sel = sampling.get_selector(selector, draw_mode)

    def step(key, choice_info, st, t, extras):
        del t, extras
        w = choice_info[st.cur] * (~st.visited)          # (m, n)
        return sel(key, w)

    return step


def _make_recompute_step(selector: str, draw_mode: str = "packed"
                         ) -> StepImpl:
    """Paper's baseline: recompute tau^a * eta^b for the current row each
    step (tau/eta/alpha/beta arrive as operands via ``extras``)."""
    sel = sampling.get_selector(selector, draw_mode)

    def step(key, choice_info, st, t, extras):
        del choice_info, t
        w = (extras["tau"][st.cur] ** extras["alpha"]
             * extras["eta"][st.cur] ** extras["beta"]) * (~st.visited)
        return sel(key, w)

    return step


def _make_nn_step(selector: str, lazy: bool = True,
                  draw_mode: str = "packed") -> StepImpl:
    """NN-list construction: sample among unvisited candidates; if the whole
    candidate set is visited, fall back to the best unvisited city by choice
    value (paper §II: 'selects the best neighbour according to eq. 1').

    ``lazy`` (the default) gates the dense O(m*n) fallback behind a
    count-gated ``lax.cond``: the (m, n) row gather + argmax only runs on
    steps where at least one ant has exhausted its candidate set, so an
    iteration costs O(m*n*k) + (fallback steps) * O(m*n) instead of an
    unconditional O(m*n^2) — the asymptotic win candidate lists exist for.
    Under vmap (solver/engine.run_batch batches colony_step) cond lowers to
    select and both branches run every step; the lazy win applies to solo /
    island colonies, which is where the paper's Table II measurement lives.
    ``lazy=False`` keeps the pre-overhaul unconditional fallback, registered
    as ``nn_list_eager`` purely as the regression baseline for
    benchmarks/construction_profile.py.  Both variants are bitwise
    identical in output — the fallback value is only consumed where
    ``have`` is False.
    """
    sel = sampling.get_selector(selector, draw_mode)

    def step(key, choice_info, st, t, extras):
        del t
        nn = extras["nn"]
        m = st.cur.shape[0]
        ants = jnp.arange(m)
        cand = nn[st.cur]                                   # (m, k)
        cw = choice_info[st.cur[:, None], cand]             # (m, k)
        cmask = ~st.visited[ants[:, None], cand]
        wc = cw * cmask
        have = wc.sum(-1) > 0
        local = sel(key, wc)                                # (m,) in [0, k)
        nxt_nn = cand[ants, local]

        def dense_fallback(_):
            w_full = choice_info[st.cur] * (~st.visited)    # (m, n)
            return jnp.argmax(w_full, axis=-1).astype(jnp.int32)

        if lazy:
            nxt_fb = jax.lax.cond(jnp.all(have), lambda _: nxt_nn,
                                  dense_fallback, None)
        else:
            nxt_fb = dense_fallback(None)
        return jnp.where(have, nxt_nn, nxt_fb)

    return step


def _draw_step_uniform(key: Array, shape: tuple, dtype,
                       draw_mode: str) -> Array:
    """The per-(ant, city) uniform tensor the kernel steps consume: packed
    (flat threefry counters, the historical bitwise behaviour) or counter
    mode (width-invariant bits, solver/programs.py neighbour routing)."""
    if draw_mode == "counter":
        return sampling.counter_uniform(key, shape, minval=1e-6,
                                        maxval=1.0).astype(dtype)
    return jax.random.uniform(key, shape, dtype, minval=1e-6, maxval=1.0)


def _make_pallas_step(selector: str, draw_mode: str = "packed") -> StepImpl:
    def step(key, choice_info, st, t, extras):
        del t
        from repro.kernels import ops as kops
        rows = choice_info[st.cur]
        u = _draw_step_uniform(key, rows.shape, rows.dtype, draw_mode)
        return kops.tour_select(rows, st.visited, u, selector,
                                extras["n_actual"])

    return step


def _make_fused_step(selector: str, alpha: float, beta: float,
                     draw_mode: str = "packed") -> StepImpl:
    """Fused choice->select kernel step (kernels/fused_select.py): the row
    gather, tau^alpha*eta^beta weighting, tabu/phantom masking and selection
    run in one pass over tiles — no (m, n) weight matrix, and no (n, n)
    choice-matrix precompute on this route (aco.colony_step skips it).

    alpha/beta are static kernel parameters, so this step is built inside
    ``_construct``'s trace (cached per static (alpha, beta) jit key) rather
    than registered in ``_STEPS``; per-instance traced exponents are
    rejected upstream (kernels.ops.check_kernel_route).
    """
    def step(key, choice_info, st, t, extras):
        del choice_info, t
        from repro.kernels import ops as kops
        u = _draw_step_uniform(key, st.visited.shape, jnp.float32,
                               draw_mode)
        # Quantised tau (core/quant.py): extras["tau"] carries the resident
        # int8/bf16 payload and the kernel dequantises per tile.  The
        # payload dtype is static at trace time, so passing the per-row
        # scale only for int8 adds no new jit keys.
        scale = (extras["tau_scale"]
                 if extras["tau"].dtype == jnp.int8 else None)
        return kops.fused_select(extras["tau"], extras["eta"], st.cur,
                                 st.visited, u, alpha, beta,
                                 extras["n_actual"], selector,
                                 tau_scale=scale)

    return step


_STEPS: dict[tuple[str, str, str], StepImpl] = {}
for _dm in sampling.DRAW_MODES:
    for _sel in sampling.SELECTORS:
        _STEPS[("data_parallel", _sel, _dm)] = _make_dense_step(_sel, _dm)
        _STEPS[("task_choice", _sel, _dm)] = _make_dense_step(
            "roulette" if _sel == "iroulette" else _sel, _dm)
        _STEPS[("task_baseline", _sel, _dm)] = \
            _make_recompute_step("roulette", _dm)
        _STEPS[("nn_list", _sel, _dm)] = _make_nn_step(_sel, draw_mode=_dm)
        _STEPS[("nn_list_eager", _sel, _dm)] = \
            _make_nn_step(_sel, lazy=False, draw_mode=_dm)
        _STEPS[("pallas", _sel, _dm)] = _make_pallas_step(_sel, _dm)


@partial(jax.jit, static_argnames=("n", "method", "selection", "masked",
                                   "alpha_s", "beta_s", "draw_mode"))
def _construct(key: Array, choice_info: Array, dist: Array, start: Array,
               extras: dict, n: int, method: str,
               selection: str, masked: bool = False,
               alpha_s: Optional[float] = None,
               beta_s: Optional[float] = None,
               draw_mode: str = "packed") -> TourResult:
    # alpha_s/beta_s: static exponents for the fused kernel step only (its
    # closure is built per trace; the jit cache is keyed on their values).
    if method == "fused":
        step_impl = _make_fused_step(selection, alpha_s, beta_s, draw_mode)
    else:
        step_impl = _STEPS[(method, selection, draw_mode)]
    st0 = _init_state(start, n)

    def body(st: TourState, t: Array):
        k = jax.random.fold_in(key, t)
        nxt = step_impl(k, choice_info, st, t, extras)
        if masked:
            # Padded instance: once the real cities are exhausted (phantom
            # weights are all 0 — eta is 0 there), emit the phantom tail in
            # fixed index order, so every padded tour is the real-city
            # permutation at positions [0, n_actual) followed by cities
            # n_actual..n-1.  This invariant is what makes masked
            # tour-length, deposit and local search exact (DESIGN.md §8).
            nxt = jnp.where(t < extras["n_actual"], nxt, t).astype(jnp.int32)
        return TourState(nxt, _mark_visited(st.visited, nxt)), nxt

    _, steps = jax.lax.scan(body, st0, jnp.arange(1, n))
    return _finish(start, steps, dist, extras["n_actual"] if masked else None)


def construct_tours(
    key: Array,
    dist: Array,
    choice_info: Array,
    m: int,
    method: str = "data_parallel",
    selection: str = "iroulette",
    nn: Optional[Array] = None,
    tau: Optional[Array] = None,
    eta: Optional[Array] = None,
    alpha: float = 1.0,
    beta: float = 2.0,
    step_impl: Optional[StepImpl] = None,
    n_actual: Optional[Array] = None,
    tau_scale: Optional[Array] = None,
    draw_mode: str = "packed",
) -> TourResult:
    """Build m complete tours under the given strategy.

    choice_info: (n, n) precomputed tau^alpha * eta^beta (ignored by
    ``task_baseline``, which recomputes it row-wise each step).
    Beyond the paper ladder, two more methods: ``fused`` (the fused
    choice->select Pallas kernel, kernels/fused_select.py — requires
    tau/eta and *static* alpha/beta; choice_info is ignored) and
    ``nn_list_eager`` (the pre-overhaul unconditional dense fallback, kept
    as the regression baseline for benchmarks/construction_profile.py).
    ``step_impl``: pass the string "pallas" via method, or a custom StepImpl
    (custom callables bypass the jit cache — fine inside an outer jit like
    aco.colony_step, slow if called repeatedly in eager mode).
    ``n_actual``: traced scalar count of real cities for padded instances
    (solver/); ant placement and selection are restricted to real cities and
    the phantom tail is emitted in fixed order. Returned lengths are masked
    real-tour lengths. Not supported for step_impl injection.
    ``draw_mode``: "packed" (default, historical bitwise behaviour) or
    "counter" — width-invariant per-(ant, city) randomness (sampling.py),
    the exactness basis of neighbour-bucket routing (DESIGN.md §16).
    """
    n = dist.shape[0]
    masked = n_actual is not None
    kp, kc = jax.random.split(key)
    start = place_ants(kp, m, n, n_actual)
    zero = jnp.zeros((1, 1), jnp.float32)
    extras = {
        "tau": tau if tau is not None else zero,
        "tau_scale": tau_scale if tau_scale is not None else zero,
        "eta": eta if eta is not None else zero,
        "alpha": jnp.float32(alpha),
        "beta": jnp.float32(beta),
        "nn": nn if nn is not None else jnp.zeros((1, 1), jnp.int32),
        "n_actual": (jnp.asarray(n_actual, jnp.int32) if masked
                     else jnp.asarray(n, jnp.int32)),
    }
    if step_impl is not None:
        assert not masked, "n_actual is not supported with step_impl injection"
        # custom injection path (un-cached trace)
        def _custom(key_, ci_, dist_, start_, extras_):
            st0 = _init_state(start_, n)

            def body(st, t):
                k = jax.random.fold_in(key_, t)
                nxt = step_impl(k, ci_, st, t)
                return TourState(nxt, _mark_visited(st.visited, nxt)), nxt

            _, steps = jax.lax.scan(body, st0, jnp.arange(1, n))
            return _finish(start_, steps, dist_)

        return _custom(kc, choice_info, dist, start, extras)
    if method not in ("data_parallel", "task_choice", "task_baseline",
                      "nn_list", "nn_list_eager", "pallas", "fused"):
        raise ValueError(f"unknown construction method {method}")
    if method == "task_baseline":
        assert tau is not None and eta is not None
    if method in ("nn_list", "nn_list_eager"):
        assert nn is not None
    alpha_s = beta_s = None
    if method == "fused":
        assert tau is not None and eta is not None
        if isinstance(alpha, jax.core.Tracer) or \
                isinstance(beta, jax.core.Tracer):
            from repro.kernels import ops as kops
            raise kops.UnsupportedKernelRoute(
                "fused construction kernel needs static alpha/beta; traced "
                "per-instance exponents run the pure-JAX route")
        alpha_s, beta_s = float(alpha), float(beta)
    if draw_mode not in sampling.DRAW_MODES:
        raise ValueError(f"unknown draw_mode {draw_mode!r}; "
                         f"supported: {', '.join(sampling.DRAW_MODES)}")
    return _construct(kc, choice_info, dist, start, extras, n, method,
                      selection, masked, alpha_s, beta_s, draw_mode)


def choice_matrix(tau: Array, eta: Array, alpha, beta) -> Array:
    """The paper's Choice kernel: precompute tau^a * eta^b once per iteration.

    Static integer exponents take the cheap path (XLA folds x**1, x**2 to
    mults); traced exponents (per-instance Hyper operands, DESIGN.md §9)
    take the generic pow.  The Pallas version lives in kernels/choice_info.py.
    """
    def ipow(x: Array, p) -> Array:
        if not isinstance(p, (int, float)):
            return x ** p               # traced per-instance exponent
        if p == 1.0:
            return x
        if p == 2.0:
            return x * x
        if p == int(p) and 0 < int(p) <= 4:
            y = x
            for _ in range(int(p) - 1):
                y = y * x
            return y
        return x ** p

    return ipow(tau, alpha) * ipow(eta, beta)

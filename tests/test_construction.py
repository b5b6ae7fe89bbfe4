"""Construction hot-path overhaul tests (DESIGN.md §10).

Four claims:

1. The fused choice->select kernel (kernels/fused_select.py) matches its
   pure-jnp oracle (kernels/ref.py) bitwise across odd shapes,
   non-divisible block sizes, and masked (n_actual < n) instances.
2. Kernel route == pure-JAX route through ``colony_step``: constructed
   tours/lengths are bitwise equal for AS/MMAS/ACS, masked and unmasked;
   full ColonyState (tau included) is bitwise for single-deposit updates
   (MMAS, ACS, AS with one ant) — AS with many ants differs in deposit
   summation order by design, asserted to ulp tolerance.
3. The lazy NN fallback (count-gated lax.cond) is bitwise identical to the
   pre-overhaul eager fallback registered as ``nn_list_eager``.
4. ``run_batch(donate=True)`` returns the same results as the non-donating
   route, and ``check_kernel_route`` enforces the support matrix.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aco, strategies, tsp
from repro.kernels import fused_select as fs_k
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.solver import batch as batch_mod
from repro.solver import engine, streaming

KEY = jax.random.PRNGKey(7)


# ------------------------------------------------------------ fused kernel
def _fused_case(m, n, mode, alpha=1.0, beta=2.0, n_actual=None,
                block_m=8, block_n=512, seed=0):
    k = jax.random.fold_in(KEY, seed * 7919 + m * 31 + n)
    tau = jax.random.uniform(k, (n, n)) + 0.1
    eta = jax.random.uniform(jax.random.fold_in(k, 1), (n, n)) + 0.1
    hi = n if n_actual is None else int(n_actual)
    if n_actual is not None:
        # padded-instance invariant: phantom eta is exactly 0
        eta = eta.at[:, hi:].set(0.0).at[hi:, :].set(0.0)
    cur = jax.random.randint(jax.random.fold_in(k, 2), (m,), 0, hi)
    vis = jax.random.uniform(jax.random.fold_in(k, 3), (m, n)) < 0.5
    vis = vis.at[:, 0].set(False)
    rand = jax.random.uniform(jax.random.fold_in(k, 4), (m, n),
                              minval=1e-6, maxval=1.0)
    na = None if n_actual is None else jnp.int32(n_actual)
    got = fs_k.fused_select(tau, eta, cur, vis, rand, alpha, beta, na, mode,
                            block_m=block_m, block_n=block_n, interpret=True)
    exp = ref.fused_select(tau, eta, cur, vis.astype(jnp.int8), rand,
                           alpha, beta, na, mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
    if n_actual is not None:
        assert (np.asarray(got) < hi).all(), "phantom city selected"


@pytest.mark.parametrize("mode", ["iroulette", "gumbel", "greedy"])
@pytest.mark.parametrize("m,n", [(1, 7), (5, 48), (16, 513), (3, 130)])
def test_fused_select_matches_ref(mode, m, n):
    _fused_case(m, n, mode)


@pytest.mark.parametrize("alpha,beta", [(1.0, 2.0), (2.0, 3.0), (0.5, 2.5)])
def test_fused_select_exponents(alpha, beta):
    _fused_case(9, 100, "iroulette", alpha=alpha, beta=beta)


@pytest.mark.parametrize("block_m,block_n", [(3, 60), (8, 128), (16, 37),
                                             (5, 512)])
def test_fused_select_block_invariance(block_m, block_n):
    """Tiling (incl. non-divisible blocks) must not change the selection."""
    _fused_case(13, 259, "iroulette", block_m=block_m, block_n=block_n)
    _fused_case(13, 259, "greedy", block_m=block_m, block_n=block_n,
                n_actual=197)


@pytest.mark.parametrize("mode", ["iroulette", "gumbel", "greedy"])
@pytest.mark.parametrize("n,n_actual", [(64, 64), (64, 41), (513, 400),
                                        (130, 97)])
def test_fused_select_masked(mode, n, n_actual):
    _fused_case(11, n, mode, n_actual=n_actual)


def test_tour_select_masked_matches_ref():
    m, n, na = 9, 130, 97
    k = jax.random.fold_in(KEY, 55)
    rows = jax.random.uniform(k, (m, n)) + 0.01
    vis = jax.random.uniform(jax.random.fold_in(k, 1), (m, n)) < 0.5
    vis = vis.at[:, 0].set(False)
    rand = jax.random.uniform(jax.random.fold_in(k, 2), (m, n),
                              minval=1e-6, maxval=1.0)
    for mode in ("iroulette", "gumbel", "greedy"):
        got = kops.tour_select(rows, vis, rand, mode, jnp.int32(na))
        exp = ref.tour_select(rows, vis.astype(jnp.int8), rand, mode,
                              jnp.int32(na))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))
        assert (np.asarray(got) < na).all()


def test_choice_info_masked_zeroes_phantoms():
    n, na = 100, 67
    k = jax.random.fold_in(KEY, 66)
    tau = jax.random.uniform(k, (n, n)) + 0.1
    eta = jax.random.uniform(jax.random.fold_in(k, 1), (n, n)) + 0.1
    got = np.asarray(kops.choice_info(tau, eta, 1.0, 2.0, jnp.int32(na)))
    exp = np.array(ref.choice_info(tau, eta, 1.0, 2.0))
    exp[na:, :] = 0.0
    exp[:, na:] = 0.0
    np.testing.assert_array_equal(got, exp)


def test_pheromone_update_masked_matches_scatter():
    """Masked kernel deposit == masked pure-JAX scatter: phantom-tail edges
    are weight-0 and the closing edge wraps at n_actual-1."""
    from repro.core import pheromone
    n, na, m = 48, 37, 5
    k = jax.random.fold_in(KEY, 77)
    tours = jnp.stack([
        jnp.concatenate([jax.random.permutation(jax.random.fold_in(k, i), na),
                         jnp.arange(na, n)])
        for i in range(m)
    ]).astype(jnp.int32)
    w = jax.random.uniform(jax.random.fold_in(k, 9), (m,)) + 0.1
    tau = jax.random.uniform(jax.random.fold_in(k, 10), (n, n)) + 0.5
    got = kops.pheromone_update(tau, tours, w, 0.5, n_actual=jnp.int32(na))
    exp = pheromone.update(tau, tours, w, 0.5, strategy="scatter",
                           n_actual=jnp.int32(na))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-5, atol=1e-5)
    # phantom block must be pure evaporation: no deposit leaked
    np.testing.assert_array_equal(np.asarray(got)[na:, na:],
                                  np.asarray(0.5 * tau)[na:, na:])


# ------------------------------------------------- kernel route == JAX route
def _state_diff(a: aco.ColonyState, b: aco.ColonyState):
    tours_eq = np.array_equal(np.asarray(a.best_tour), np.asarray(b.best_tour))
    len_eq = np.array_equal(np.asarray(a.best_len), np.asarray(b.best_len))
    tau_eq = np.array_equal(np.asarray(a.tau), np.asarray(b.tau))
    return tours_eq, len_eq, tau_eq


@pytest.mark.parametrize("variant,full_bitwise", [
    ("as", False),     # m ants deposit: summation order differs by design
    ("mmas", True),    # single-tour deposit: every cell gets <= 1 deposit
    ("acs", False),    # shared post-deposit math fuses differently (ulp)
])
def test_kernel_route_equals_jax_route(variant, full_bitwise):
    """use_pallas=True (fused construction + kernel deposit) against the
    pure-JAX route through real colony_step iterations: constructed tours
    and best lengths are bitwise equal always; tau is bitwise where the
    deposit is single-hit per cell (DESIGN.md §10), ulp-close otherwise."""
    inst = tsp.circle_instance(49, seed=3)
    prob = aco.make_problem(inst, nn_k=10)
    kw = dict(iterations=4, variant=variant, selection="iroulette", seed=1)
    cfg_j = aco.ACOConfig(use_pallas=False, **kw)
    cfg_k = aco.ACOConfig(use_pallas=True, **kw)
    sj = aco.init_colony(inst, cfg_j)
    sk = aco.init_colony(inst, cfg_k)
    for _ in range(3):
        sj, _ = aco.colony_step(prob, sj, cfg_j)
        sk, _ = aco.colony_step(prob, sk, cfg_k)
        tours_eq, len_eq, tau_eq = _state_diff(sj, sk)
        assert tours_eq and len_eq
        if full_bitwise:
            assert tau_eq
        else:
            np.testing.assert_allclose(np.asarray(sj.tau), np.asarray(sk.tau),
                                       rtol=1e-5, atol=1e-7)


def test_kernel_route_as_single_ant_full_bitwise():
    """One ant -> one tour -> no duplicate deposit edges -> the AS kernel
    route is fully bitwise too."""
    inst = tsp.circle_instance(40, seed=4)
    prob = aco.make_problem(inst, nn_k=8)
    cfg_j = aco.ACOConfig(iterations=4, m=1, seed=2, use_pallas=False)
    cfg_k = aco.ACOConfig(iterations=4, m=1, seed=2, use_pallas=True)
    sj = aco.init_colony(inst, cfg_j)
    sk = aco.init_colony(inst, cfg_k)
    for _ in range(3):
        sj, _ = aco.colony_step(prob, sj, cfg_j)
        sk, _ = aco.colony_step(prob, sk, cfg_k)
    assert all(_state_diff(sj, sk))


def test_fused_construction_bitwise_vs_dense():
    """construct_tours: fused kernel method == data_parallel method,
    bitwise, same PRNG stream (tie semantics included)."""
    inst = tsp.random_instance(73, seed=9)          # odd n: non-divisible
    prob = aco.make_problem(inst, nn_k=10)
    tau = jnp.full((73, 73), 0.7)
    key = jax.random.fold_in(KEY, 3)
    ci = strategies.choice_matrix(tau, prob.eta, 1.0, 2.0)
    for sel in ("iroulette", "greedy"):
        rj = strategies.construct_tours(key, prob.dist, ci, 20,
                                        method="data_parallel", selection=sel,
                                        tau=tau, eta=prob.eta)
        rk = strategies.construct_tours(key, prob.dist, jnp.zeros((1, 1)), 20,
                                        method="fused", selection=sel,
                                        tau=tau, eta=prob.eta)
        np.testing.assert_array_equal(np.asarray(rj.tours),
                                      np.asarray(rk.tours))
        np.testing.assert_array_equal(np.asarray(rj.lengths),
                                      np.asarray(rk.lengths))


@pytest.mark.parametrize("variant", ["as", "mmas", "acs"])
def test_masked_kernel_route_matches_pure_and_solo(variant):
    """Padded instances through the batched engine with use_pallas=True:
    tours/lengths match the pure-JAX masked route bitwise, and batched ==
    solo composition holds on the kernel route."""
    insts = [tsp.circle_instance(n, seed=i)
             for i, n in enumerate((13, 20, 29))]
    kw = dict(iterations=5, variant=variant, selection="iroulette")
    cfg_k = aco.ACOConfig(use_pallas=True, **kw)
    cfg_j = aco.ACOConfig(use_pallas=False, **kw)
    st_k, bk = engine.solve_instances(insts, cfg_k, n_pad=32)
    st_j, _ = engine.solve_instances(insts, cfg_j, n_pad=32)
    np.testing.assert_array_equal(np.asarray(st_k.best_tour),
                                  np.asarray(st_j.best_tour))
    np.testing.assert_array_equal(np.asarray(st_k.best_len),
                                  np.asarray(st_j.best_len))
    for r in engine.collect(st_k, bk):
        assert tsp.is_valid_tour(np.asarray(r["best_tour"]))
    # batched == solo on the kernel route (default per-index seeds: cfg.seed+i)
    solo, _ = engine.solve_instances([insts[1]], cfg_k, n_pad=32,
                                     seeds=[cfg_k.seed + 1])
    assert float(solo.best_len[0]) == float(st_k.best_len[1])
    np.testing.assert_array_equal(np.asarray(solo.best_tour[0]),
                                  np.asarray(st_k.best_tour[1]))


def test_streaming_pallas_matches_solo():
    """StreamingSolverService now composes with use_pallas=True."""
    cfg = aco.ACOConfig(iterations=6, use_pallas=True)
    svc = streaming.StreamingSolverService(cfg, max_batch=2, chunk=3)
    sizes = (14, 21, 18)
    for i, n in enumerate(sizes):
        svc.submit(tsp.circle_instance(n, seed=i), seed=i)
    res = {r.request_id: r for r in svc.run_until_drained()}
    assert len(res) == 3
    for i, n in enumerate(sizes):
        st, _ = engine.solve_instances([tsp.circle_instance(n, seed=i)],
                                       cfg, n_pad=res[i].bucket, seeds=[i])
        assert float(st.best_len[0]) == res[i].best_len


# ------------------------------------------------------- lazy NN fallback
@pytest.mark.parametrize("kind", ["circle", "random"])
def test_lazy_nn_fallback_bitwise_equals_eager(kind):
    """The count-gated lax.cond fallback must be unobservable in output:
    nn_list == nn_list_eager bitwise (the fallback branch value is only
    consumed where a candidate set is exhausted)."""
    make = tsp.circle_instance if kind == "circle" else tsp.random_instance
    inst = make(61, seed=11)
    prob = aco.make_problem(inst, nn_k=6)     # tiny k: fallback fires often
    tau = jnp.full((61, 61), 0.4)
    ci = strategies.choice_matrix(tau, prob.eta, 1.0, 2.0)
    key = jax.random.fold_in(KEY, 13)
    a = strategies.construct_tours(key, prob.dist, ci, 61, method="nn_list",
                                   selection="iroulette", nn=prob.nn)
    b = strategies.construct_tours(key, prob.dist, ci, 61,
                                   method="nn_list_eager",
                                   selection="iroulette", nn=prob.nn)
    np.testing.assert_array_equal(np.asarray(a.tours), np.asarray(b.tours))
    np.testing.assert_array_equal(np.asarray(a.lengths),
                                  np.asarray(b.lengths))


def test_lazy_nn_fallback_under_vmap():
    """Under vmap the cond lowers to select (both branches run) — results
    must still match the solo lazy route bitwise."""
    insts = [tsp.circle_instance(n, seed=i) for i, n in enumerate((17, 23))]
    cfg = aco.ACOConfig(iterations=4, construction="nn_list", nn_k=5)
    st, b = engine.solve_instances(insts, cfg, n_pad=32)
    solo, _ = engine.solve_instances([insts[0]], cfg, n_pad=32,
                                     seeds=[cfg.seed])
    assert float(solo.best_len[0]) == float(st.best_len[0])


# ------------------------------------------------- donation + support matrix
def test_run_batch_donate_matches_non_donating():
    insts = [tsp.circle_instance(n, seed=i) for i, n in enumerate((12, 18))]
    cfg = aco.ACOConfig(iterations=5)
    b = batch_mod.make_batch(insts, 32, 10)
    budgets = jnp.asarray([5, 3], jnp.int32)
    r0, s0 = engine.run_batch(b.problem,
                              engine.init_states(insts, cfg, [0, 1], 32),
                              budgets, cfg, 5, patience=2)
    r1, s1 = engine.run_batch(b.problem,
                              engine.init_states(insts, cfg, [0, 1], 32),
                              budgets, cfg, 5, patience=2, donate=True)
    for x, y in zip(jax.tree.leaves(r0), jax.tree.leaves(r1)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_kernel_route_support_matrix():
    """check_kernel_route: masked is supported, Hyper operands are not —
    and the rejection is one typed error everywhere it surfaces."""
    kops.check_kernel_route()                      # plain: fine
    kops.check_kernel_route(masked=True)           # padded instances: fine
    with pytest.raises(kops.UnsupportedKernelRoute, match="Hyper"):
        kops.check_kernel_route(hyper=True)
    assert issubclass(kops.UnsupportedKernelRoute, NotImplementedError)
    # colony_step surfaces it for hyper-carrying problems on the kernel route
    inst = tsp.circle_instance(16, seed=0)
    cfg = aco.ACOConfig(iterations=2, use_pallas=True)
    prob = aco.make_problem(inst, 5)._replace(hyper=aco.Hyper.make(cfg))
    with pytest.raises(kops.UnsupportedKernelRoute, match="Hyper"):
        aco.colony_step(prob, aco.init_colony(inst, cfg), cfg)
    # the fused construction method rejects genuinely *traced* exponents
    # the same way...
    def build(a):
        return strategies.construct_tours(
            KEY, prob.dist, jnp.zeros((1, 1)), 4, method="fused",
            tau=jnp.ones((16, 16)), eta=prob.eta,
            alpha=a, beta=2.0).lengths
    with pytest.raises(kops.UnsupportedKernelRoute, match="static"):
        jax.jit(build)(jnp.float32(1.5))
    # ...but any concrete scalar (python, numpy, or jax) is static-able
    for a in (1.5, np.float32(1.5), jnp.float32(1.5)):
        assert build(a).shape == (4,)


# ------------------------------------------ visited mask: one-hot OR update
def _scatter_mark(visited, cities):
    """The tabu-mask update as it was: one scattered write per ant."""
    return visited.at[jnp.arange(cities.shape[0]), cities].set(True)


@jax.jit
def _lengths_of(dist, tours, n_actual):
    return strategies._finish(tours[:, 0], tours[:, 1:].T, dist,
                              n_actual).lengths


def _scatter_construct(key, dist, ci, m, method, n_actual, extras):
    """The construction loop as it was before the one-hot visited update:
    the same step from ``_STEPS``, the same scan, the tabu mask marked by
    ``.at[ants, nxt].set(True)``."""
    n = dist.shape[0]
    kp, kc = jax.random.split(key)
    start = strategies.place_ants(kp, m, n, n_actual)
    if method == "fused":
        step = strategies._make_fused_step("iroulette", 1.0, 2.0)
    else:
        step = strategies._STEPS[(method, "iroulette", "packed")]

    def body(st, t):
        nxt = step(jax.random.fold_in(kc, t), ci, st, t, extras)
        if n_actual is not None:
            nxt = jnp.where(t < extras["n_actual"], nxt, t).astype(jnp.int32)
        return strategies.TourState(nxt, _scatter_mark(st.visited, nxt)), nxt

    st0 = strategies.TourState(
        start, _scatter_mark(jnp.zeros((m, n), jnp.bool_), start))
    _, steps = jax.lax.scan(body, st0, jnp.arange(1, n))
    return jnp.concatenate([start[None, :], steps], axis=0).T


def _run_batch_leaves(insts, cfg):
    b = batch_mod.make_batch(insts, 32, 6)
    seeds = list(range(len(insts)))
    res, _ = engine.run_batch(b.problem,
                              engine.init_states(insts, cfg, seeds, 32),
                              jnp.full((len(insts),), 3, jnp.int32), cfg, 3)
    return [np.asarray(x) for x in jax.tree.leaves(res)]


@pytest.mark.parametrize("method,masked", [
    (meth, masked)
    for meth in ("data_parallel", "task_choice", "nn_list", "nn_list_eager",
                 "pallas", "fused")
    for masked in (False, True)] + [("run_batch", True)])
def test_one_hot_visited_bitwise_equals_scatter(method, masked, monkeypatch):
    """The one-hot OR visited update builds the tours the scatter built:
    construct_tours against a copy of the scatter loop, unmasked and with a
    phantom tail, and engine.run_batch over 3 slots of uneven n_actual with
    the scatter patched back in."""
    if method == "run_batch":
        insts = [tsp.random_instance(n, seed=i)
                 for i, n in enumerate((13, 29, 21))]
        cfg = aco.ACOConfig(iterations=3, variant="as")
        new = _run_batch_leaves(insts, cfg)
        jax.clear_caches()
        monkeypatch.setattr(strategies, "_mark_visited", _scatter_mark)
        try:
            old = _run_batch_leaves(insts, cfg)
        finally:
            jax.clear_caches()
        for a, b in zip(new, old):
            np.testing.assert_array_equal(a, b)
        return
    n, n_act = (40, 27) if masked else (37, None)
    m = 12
    inst = tsp.random_instance(n_act or n, seed=5)
    if masked:
        prob = batch_mod.padded_problem(inst, n, nn_k=6)
    else:
        prob = aco.make_problem(inst, nn_k=6)
    tau = jax.random.uniform(jax.random.fold_in(KEY, 21), (n, n)) + 0.2
    ci = strategies.choice_matrix(tau, prob.eta, 1.0, 2.0)
    key = jax.random.fold_in(KEY, 22)
    kw = dict(method=method, selection="iroulette", nn=prob.nn, tau=tau,
              eta=prob.eta, n_actual=prob.n_actual)
    got = strategies.construct_tours(key, prob.dist, ci, m, **kw)
    na = (jnp.asarray(n, jnp.int32) if n_act is None else prob.n_actual)
    extras = {"tau": tau, "tau_scale": jnp.zeros((1, 1), jnp.float32),
              "eta": prob.eta, "alpha": jnp.float32(1.0),
              "beta": jnp.float32(2.0), "nn": prob.nn, "n_actual": na}
    tours = jax.jit(_scatter_construct, static_argnums=(3, 4))(
        key, prob.dist, ci, m, method, prob.n_actual, extras)
    np.testing.assert_array_equal(np.asarray(got.tours), np.asarray(tours))
    np.testing.assert_array_equal(
        np.asarray(got.lengths),
        np.asarray(_lengths_of(prob.dist, tours, prob.n_actual)))
    for t in np.asarray(got.tours):
        assert tsp.is_valid_tour(t)


@pytest.mark.parametrize("masked", [False, True])
def test_dense_construct_lowers_without_scatter(masked):
    """The dense loop marks visited with no scatter (the scatter sat in the
    init and in the loop body); the sparse loop keeps its own, on purpose."""
    from repro.sparse import construct as sp_construct
    from repro.sparse import store
    inst = tsp.random_instance(24, seed=3)
    prob = (batch_mod.padded_problem(inst, 32, nn_k=6) if masked
            else aco.make_problem(inst, nn_k=6))

    def build(key, ci):
        return strategies.construct_tours(key, prob.dist, ci, 8,
                                          n_actual=prob.n_actual)

    text = jax.jit(build).lower(KEY, prob.eta).as_text()
    assert "stablehlo.scatter" not in text
    sp = store.make_sparse_problem(inst, 6)
    k = sp.cand.shape[1]

    def build_sparse(key, tau):
        return sp_construct.construct_sparse_tours(
            key, sp, tau, jnp.full((24, 0), store.OVF_EMPTY, jnp.int32),
            jnp.zeros((24, 0), jnp.float32), 8, "iroulette", 1.0, 2.0,
            inst.edge_weight_type)

    sparse_text = jax.jit(build_sparse).lower(
        KEY, jnp.ones((24, k), jnp.float32)).as_text()
    assert "stablehlo.scatter" in sparse_text

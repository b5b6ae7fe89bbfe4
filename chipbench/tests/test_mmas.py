"""The closed-loop check follows the configuration's variant: the MAX-MIN
Ant System's pheromone numbers and the 2-opt gain left, on the plain
reference and on whole runs of a MMAS + 2-opt cell with faults planted
in the program; Ant System's numbers unchanged."""
import copy
import dataclasses
import math
import types

import numpy as np
import pytest

from chipbench import rehearse, run
from chipbench.harness import (cells, check, control, generator, reference,
                               session, single)


def instance(n=60, seed=0):
    xy = generator.coordinates({"kind": "uniform", "box": 1000,
                                "min_distance": 1.0}, n,
                               generator.rng_for(seed, 0))
    return xy, reference.distances(xy, "EUC_2D")


def mmas_chunk(dist, m=20, c=4, rho=0.5, seed=0, update=None):
    """tau before and after a chunk of ``c`` MMAS iterations that follows
    3 others, on random tours, and the best length after it."""
    n = dist.shape[0]
    rng = np.random.default_rng(seed)
    tau = np.full((n, n), 1.0 / (rho * reference.nn_tour_length(dist)))
    best = math.inf
    update = update or (lambda tau, ts, ls, best: reference.mmas_update(
        tau, ts[np.argmin(ls)], ls.min(), best, rho))
    for it in range(3 + c):
        if it == 3:
            before = tau.copy()
        ts = np.stack([rng.permutation(n) for _ in range(m)])
        ls = np.asarray([reference.tour_length(dist, t) for t in ts])
        best = min(best, ls.min())
        tau = update(tau, ts, ls, best)
    return before, tau, best


def numbers(before, after, best, n=60, c=4, rho=0.5):
    return reference.mmas_numbers(before, after, n, c, rho, 1.0, best)


# --------------------------------------------------- the plain reference
@pytest.mark.parametrize("c", [1, 4, 20])
def test_sound_mmas_chunk_reads_clean(c):
    _, d = instance()
    nums = numbers(*mmas_chunk(d, c=c), c=c)
    assert nums["dep_asym"] == 0.0
    assert nums["mmas_bound_err"] < 1e-12
    assert 0.0 < nums["mmas_rows_over"] <= 1.0
    assert nums["mmas_rows_under"] == 0.0


def test_every_ant_depositing_fills_the_rows():
    def every_ant(tau, ts, ls, best):
        tau = reference.update_pheromone(tau, ts, ls, 0.5)
        tau_max = 1.0 / (0.5 * best)
        return np.clip(tau, tau_max / 120, tau_max)
    _, d = instance()
    assert numbers(*mmas_chunk(d, update=every_ant))["mmas_rows_over"] > 3


def test_no_clamp_leaves_the_bounds():
    def no_clamp(tau, ts, ls, best):
        i = np.argmin(ls)
        return reference.update_pheromone(tau, ts[i:i + 1], ls[i:i + 1],
                                          0.5)
    _, d = instance()
    nums = numbers(*mmas_chunk(d, c=20, update=no_clamp), c=20)
    assert nums["mmas_bound_err"] > 1e-3


def test_one_sided_mmas_deposit_is_asymmetric():
    def one_sided(tau, ts, ls, best):
        i = np.argmin(ls)
        tau = tau * 0.5
        tau[ts[i], np.roll(ts[i], -1)] += 2 / ls[i]
        tau_max = 1.0 / (0.5 * best)
        return np.clip(tau, tau_max / 120, tau_max)
    _, d = instance()
    assert numbers(*mmas_chunk(d, update=one_sided))["dep_asym"] > 0.1


def test_slower_evaporation_rises_everywhere():
    def half_rho(tau, ts, ls, best):
        i = np.argmin(ls)
        return reference.mmas_update(tau, ts[i], ls[i], best, 0.25)
    _, d = instance()
    nums = numbers(*mmas_chunk(d, update=half_rho))
    assert nums["mmas_rows_over"] > 3 and nums["mmas_bound_err"] > 0.1


def test_no_deposit_leaves_rows_untouched():
    def none(tau, ts, ls, best):
        tau_max = 1.0 / (0.5 * best)
        return np.clip(tau * 0.5, tau_max / 120, tau_max)
    _, d = instance()
    assert numbers(*mmas_chunk(d, update=none))["mmas_rows_under"] == 1.0


def test_two_opt_gain_left():
    """0 on a tour the reference 2-opt has polished, above 0 on the
    nearest-neighbour tour; each move's gain is exact."""
    _, d = instance(120, 3)
    k = 10
    n = d.shape[0]
    visited, t = np.zeros(n, bool), [0]
    visited[0] = True
    for _ in range(n - 1):
        nxt = int(np.argmin(np.where(visited, np.inf, d[t[-1]])))
        visited[nxt] = True
        t.append(nxt)
    nn_tour = np.asarray(t)
    assert reference.tour_length(d, nn_tour) == reference.nn_tour_length(d)
    gain = reference.two_opt_gain(d, nn_tour, k)
    assert gain > 0
    polished = reference.two_opt_polish(d, nn_tour, k)
    assert reference.is_permutation(polished, n)
    assert reference.two_opt_gain(d, polished, k) == 0.0
    assert reference.tour_length(d, polished) < \
        reference.tour_length(d, nn_tour) * (1 - gain / 2)


def test_served_numbers_read_the_gain_only_with_two_opt():
    xy, d = instance()
    t = np.arange(60)
    ans = [{"coords": xy, "edge_weight_type": "EUC_2D", "tour": t,
            "best_len": reference.tour_length(d, t), "iterations": 1,
            "budget": 1, "partial": True}]
    assert "ls_gain_left" not in check.served_numbers(ans)
    assert "ls_gain_left" not in check.served_numbers(
        ans, conf={"local_search": "none"})
    got = check.served_numbers(ans, conf={"local_search": "2opt",
                                          "nn_k": 8})
    assert got["ls_gain_left"] == reference.two_opt_gain(d, t, 8) > 0


# --------------------------------------------- the check per variant
def as_cell():
    return rehearse.tiny(cells.resolve(cells.load_bench(), "tsp1002.as"))


def parent_numbers(answers, deposit, conf):
    """``single.numbers`` as it read every closed-loop cell before the
    check followed the variant."""
    out = check.served_numbers(answers)
    m = int(conf["m"]) if conf.get("m") else int(conf["n"])
    out.update(reference.deposit_numbers(
        deposit["tau_before"], deposit["tau_after"], int(conf["n"]), m,
        int(deposit["chunk"]), float(conf["rho"]),
        float(conf.get("q", 1.0)), deposit["best_len"]))
    return out


# single.numbers of the parent tree on the float32 reference colony at
# the rehearsal size (XLA:CPU)
AS_BEFORE = {
    1: {"best_over_nn": 0.8565011500473549,
        "dep_rowsum_spread": 2.2291099968800312e-07,
        "dep_weight_over": 0.8786060510121502,
        "dep_weight_under": 1.1381665296386299},
    2: {"best_over_nn": 0.854261624078402,
        "dep_rowsum_spread": 1.208014568981073e-07,
        "dep_weight_over": 0.8929657378712301,
        "dep_weight_under": 1.1198637949804573},
}


@pytest.mark.parametrize("seed", [1, 2])
def test_ant_system_numbers_are_unchanged(seed):
    c = as_cell()
    conf = c.config
    req = generator.single_instance(conf, seed)
    answer, deposit = control.reference_colony(
        req.coords, conf["instance"]["edge_weight_type"], int(conf["m"]),
        conf["alpha"], conf["beta"], conf["rho"], conf["q"],
        int(c.traffic["iterations"]), int(conf["service"]["chunk"]), seed,
        "float32")
    got = single.numbers([answer], deposit, conf)
    assert got == parent_numbers([answer], deposit, conf)
    assert got == pytest.approx(dict(
        AS_BEFORE[seed], tour_invalid=0.0, len_err=0.0, iters_short=0.0,
        dep_asym=0.0), rel=1e-9, abs=0)


def test_a_variant_without_a_check_is_refused():
    conf = dict(as_cell().config, variant="acs")
    with pytest.raises(ValueError, match="no pheromone check"):
        single.numbers([], {"tau_before": None}, conf)
    with pytest.raises(ValueError, match="no pheromone check"):
        single.run(cells.Cell("x", 1, conf, as_cell().traffic, [], []),
                   1, 1.0, False, None, 0.0)


# ------------------------------------------------ MMAS + 2-opt, whole runs
MMAS_CONF = {
    "name": "mmas-2opt-rehearsal",
    "n": 40,
    "m": 10,
    "variant": "mmas",
    "alpha": 1.0,
    "beta": 2.0,
    "rho": 0.5,
    "q": 1.0,
    "selection": "iroulette",
    "construction": "data_parallel",
    "deposit": "scatter",
    "nn_k": 30,
    "local_search": "2opt",
    "ls_tours": "iteration_best",
    "ls_rounds": 1000,
    "service": {"max_batch": 1, "min_bucket": 16, "chunk": 4},
    "instance": {"kind": "uniform", "box": 11500,
                 "edge_weight_type": "EUC_2D", "min_distance": 1.0},
    "limits": {"tour_invalid": 0, "len_err": 0, "iters_short": 0,
               "best_over_nn": 1.25, "dep_asym": 0, "mmas_bound_err": 1e-5,
               "mmas_rows_over": 1.0, "mmas_rows_under": 0,
               "ls_gain_left": 0},
}
MMAS_TRAFFIC = {"kind": "closed_loop", "iterations": 12, "warm_solves": 1,
                "trace_s": 1.0}


@pytest.fixture
def fresh_programs():
    """Planted faults must reach the compiled programs."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def mmas_run():
    session.add_program_to_path()
    cell = cells.Cell("mmas-2opt.rehearsal", 1, copy.deepcopy(MMAS_CONF),
                      dict(MMAS_TRAFFIC), [], [])
    devs = session.devices(1, allow_cpu=True)
    _, ok, rows = run.run_cell(cell, 20241, 2.0, False, devs,
                               session.now())
    return ok, {r[0]: r[1] for r in rows}


def every_ant(monkeypatch):
    """Every ant deposits its constructed tour, AS-style, before the
    clamp."""
    from repro.core import pheromone, strategies
    built, real_construct, real_update = {}, strategies.construct_tours, \
        pheromone.update

    def construct(*a, **k):
        built["res"] = real_construct(*a, **k)
        return built["res"]

    def update(tau, tours, w, rho, **k):
        res = built["res"]
        return real_update(tau, res.tours, 1.0 / res.lengths, rho, **k)
    monkeypatch.setattr(strategies, "construct_tours", construct)
    monkeypatch.setattr(pheromone, "update", update)


def no_clamp(monkeypatch):
    from repro.core import aco
    jnp = aco.jnp
    names = {k: getattr(jnp, k) for k in dir(jnp) if not k.startswith("__")}
    names["clip"] = lambda x, lo, hi: x
    monkeypatch.setattr(aco, "jnp", types.SimpleNamespace(**names))


def one_sided(monkeypatch):
    from repro.core import pheromone
    real = pheromone.deposit_scatter

    def deposit(n, tours, w, strategy="scatter", tile=64, n_actual=None):
        return 2.0 * real(n, tours, w, symmetric=False, n_actual=n_actual)
    monkeypatch.setattr(pheromone, "deposit", deposit)


def half_rho(monkeypatch):
    real = cells.aco_config

    def halved(config, **over):
        cfg = real(config, **over)
        return dataclasses.replace(cfg, rho=cfg.rho / 2)
    monkeypatch.setattr(cells, "aco_config", halved)


def no_local_search(monkeypatch):
    from repro.core import aco
    monkeypatch.setattr(aco, "_apply_local_search",
                        lambda problem, res, iteration, cfg: res)


def unchanged(monkeypatch):
    from repro.solver import engine

    def step(problem, states, budgets, cfg, max_iters, patience=0,
             since=None, **kw):
        return (states, since) if not cfg.metrics else (states, since, None)
    monkeypatch.setattr(engine, "run_batch", step)


def test_sound_mmas_two_opt_run_is_correct(fresh_programs):
    ok, nums = mmas_run()
    assert ok, nums
    assert nums["ls_gain_left"] == 0.0 and nums["mmas_rows_under"] == 0.0


@pytest.mark.parametrize("fault,caught_by", [
    (every_ant, "mmas_rows_over"),
    (no_clamp, "mmas_bound_err"),
    (one_sided, "dep_asym"),
    (half_rho, "mmas_rows_over"),
    (no_local_search, "ls_gain_left"),
    (unchanged, "len_err"),
], ids=["every_ant", "no_clamp", "one_sided", "half_rho",
        "no_local_search", "state_unchanged"])
def test_mmas_fault_is_not_correct(monkeypatch, fresh_programs, fault,
                                   caught_by):
    fault(monkeypatch)
    ok, nums = mmas_run()
    assert not ok, nums
    assert nums[caught_by] > MMAS_CONF["limits"][caught_by], nums

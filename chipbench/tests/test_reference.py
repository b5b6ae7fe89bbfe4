"""The plain reference and the numbers the check reads off it."""
import math

import numpy as np
import pytest

from chipbench.harness import check, control, generator, reference


def instance(n=60, seed=0):
    xy = generator.coordinates({"kind": "uniform", "box": 1000,
                                "min_distance": 1.0}, n,
                               generator.rng_for(seed, 0))
    return xy, reference.distances(xy, "EUC_2D")


def tours(n, m, seed):
    rng = np.random.default_rng(seed)
    return np.stack([rng.permutation(n) for _ in range(m)])


def run_chunk(dist, m, c, rho=0.5, seed=0, deposit=reference.update_pheromone):
    n = dist.shape[0]
    tau = np.full((n, n), reference.initial_tau(dist, m))
    best = math.inf
    for it in range(3 + c):
        if it == 3:
            before = tau.copy()
        ts = tours(n, m, seed + it)
        ls = np.asarray([reference.tour_length(dist, t) for t in ts])
        best = min(best, ls.min())
        tau = deposit(tau, ts, ls, rho)
    return before, tau, best


def test_tour_length_and_nn():
    xy, d = instance()
    t = np.arange(60)
    assert reference.tour_length(d, t) == d[t, np.roll(t, -1)].sum()
    nn = reference.nn_tour_length(d)
    assert reference.tour_length(d, t) > nn > 0
    assert reference.is_permutation(t, 60)
    assert not reference.is_permutation(np.r_[t[:-1], 0], 60)


def test_update_matches_sequential_as_semantics():
    """Evaporate, then q / L_k on both directions of every edge."""
    _, d = instance(8)
    tau = np.ones((8, 8))
    ts = tours(8, 3, 1)
    ls = np.asarray([reference.tour_length(d, t) for t in ts])
    got = reference.update_pheromone(tau, ts, ls, 0.25)
    want = np.full((8, 8), 0.75)
    for t, l in zip(ts, ls):
        for a, b in zip(t, np.roll(t, -1)):
            want[a, b] += 1 / l
            want[b, a] += 1 / l
    np.testing.assert_allclose(got, want, rtol=1e-15)


@pytest.mark.parametrize("c", [1, 4])
def test_sound_deposit_reads_clean(c):
    _, d = instance()
    before, after, best = run_chunk(d, 40, c)
    nums = reference.deposit_numbers(before, after, 60, 40, c, 0.5, 1.0,
                                     best)
    assert nums["dep_asym"] < 1e-12
    assert nums["dep_rowsum_spread"] < 1e-12
    assert 0.5 < nums["dep_weight_over"] <= 1.0


def test_one_sided_deposit_is_asymmetric():
    def one_sided(tau, ts, ls, rho):
        tau = tau * (1 - rho)
        for t, l in zip(ts, ls):
            tau[t, np.roll(t, -1)] += 2 / l
        return tau
    _, d = instance()
    before, after, best = run_chunk(d, 40, 1, deposit=one_sided)
    nums = reference.deposit_numbers(before, after, 60, 40, 1, 0.5, 1.0,
                                     best)
    assert nums["dep_asym"] > 0.1


def test_invalid_tours_spread_the_row_sums():
    def repeat_zero(tau, ts, ls, rho):
        ts = ts.copy()
        ts[:, -1] = ts[:, 0]              # revisit, skip one city
        return reference.update_pheromone(tau, ts, ls, rho)
    _, d = instance()
    before, after, best = run_chunk(d, 40, 1, deposit=repeat_zero)
    nums = reference.deposit_numbers(before, after, 60, 40, 1, 0.5, 1.0,
                                     best)
    assert nums["dep_rowsum_spread"] > 0.01


def test_doubled_and_halved_deposits():
    def scaled(k):
        def dep(tau, ts, ls, rho):
            return reference.update_pheromone(tau, ts, ls / k, rho)
        return dep
    _, d = instance()
    for k, key in ((2.0, "dep_weight_over"), (0.4, "dep_weight_under")):
        before, after, best = run_chunk(d, 40, 1, deposit=scaled(k))
        nums = reference.deposit_numbers(before, after, 60, 40, 1, 0.5,
                                         1.0, best)
        assert nums[key] > 1.5


def test_served_numbers_catch_altered_answers():
    xy, d = instance()
    t = np.arange(60)
    good = {"coords": xy, "edge_weight_type": "EUC_2D", "tour": t,
            "best_len": reference.tour_length(d, t), "iterations": 5,
            "budget": 5}
    assert check.served_numbers([good])["len_err"] == 0.0
    bad_len = dict(good, best_len=good["best_len"] - 1)
    assert check.served_numbers([bad_len])["len_err"] == 1.0
    bad_tour = dict(good, tour=np.r_[t[:-1], 0])
    assert check.served_numbers([bad_tour])["tour_invalid"] == 1.0
    short = dict(good, iterations=4)
    assert check.served_numbers([short])["iters_short"] == 1.0


def test_compare_needs_every_number_and_limit():
    ok, rows = check.compare({"a": 0.0, "b": 1e-5}, {"a": 0, "b": 1e-4})
    assert ok and len(rows) == 2
    assert not check.compare({"a": 0.0}, {"a": 0, "b": 1})[0]
    assert not check.compare({"a": 0.0, "c": 1.0}, {"a": 0})[0]
    assert not check.compare({"a": math.inf}, {"a": 1})[0]
    assert not check.compare({"a": math.nan}, {"a": 1})[0]


def test_bf16_length_is_off():
    xy, d = instance(200, 3)
    t = np.arange(200)
    exact = reference.tour_length(d, t)
    assert abs(control.bf16_length(d, t) - exact) > 1.0

"""The traffic generator: one seed gives the same schedule and instances
exactly; seeds differ only in order and coordinates."""
import json
import os

import numpy as np
import pytest

from chipbench.harness import generator

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 2**31 + 11, 987654321987])
def test_same_seed_same_schedule(seed):
    t = load("lastmile-steady")
    a = generator.open_loop_schedule(t, seed, 10.0)
    b = generator.open_loop_schedule(t, seed, 10.0)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert (x.phase, x.due, x.n, x.solver_seed) == \
            (y.phase, y.due, y.n, y.solver_seed)
        np.testing.assert_array_equal(x.coords, y.coords)


def test_seeds_offer_the_same_load_on_other_data():
    t = load("lastmile-steady")
    a = generator.open_loop_schedule(t, 1, 10.0)
    b = generator.open_loop_schedule(t, 2, 10.0)
    assert [(r.phase, r.due, r.n) for r in a] == \
        [(r.phase, r.due, r.n) for r in b]
    assert not any(np.array_equal(x.coords, y.coords) for x, y in zip(a, b))
    c = generator.open_loop_schedule(dict(t, order_seed=1), 1, 10.0)
    assert [r.due for r in c] != [r.due for r in a]
    assert sorted(r.n for r in c if r.phase == "window") == \
        sorted(r.n for r in a if r.phase == "window")


def test_window_rate_and_sizes():
    t = load("lastmile-steady")
    s = generator.open_loop_schedule(t, 5, 30.0)
    win = [r for r in s if r.phase == "window"]
    assert len(win) == round(t["rate_per_s"] * 30.0)
    assert all(0.0 <= r.due < 30.0 for r in win)
    ns = np.asarray([r.n for r in win])
    assert ns.min() >= 33 and ns.max() <= 238
    assert abs(ns.mean() - 150) < 5


def test_single_instance_repeats():
    conf = {"n": 300, "instance": {"kind": "uniform", "box": 11500,
                                   "min_distance": 1.0}}
    a = generator.single_instance(conf, 42)
    b = generator.single_instance(conf, 42)
    np.testing.assert_array_equal(a.coords, b.coords)
    c = generator.single_instance(conf, 43)
    assert not np.array_equal(a.coords, c.coords)


@pytest.mark.parametrize("kind", ["uniform", "clustered"])
def test_no_two_cities_closer_than_min(kind):
    spec = {"kind": kind, "box": 2000, "points_per_centre": 10,
            "min_distance": 1.0}
    xy = generator.coordinates(spec, 400, generator.rng_for(3, 0))
    d = np.sqrt(((xy[:, None] - xy[None]) ** 2).sum(-1))
    np.fill_diagonal(d, np.inf)
    assert d.min() >= 1.0
    assert (xy >= 0).all() and (xy <= 2000).all()


def test_bursts_follow_the_rate():
    t = dict(load("lastmile-steady"), rate_per_s=10.0,
             bursts={"period_s": 10.0, "on_s": 2.0, "factor": 4.0})
    due = generator.due_times(t, 100.0, generator.rng_for(9, 0))
    assert len(due) == round(10 * (2 * 4 + 8) * 10)
    on = ((due % 10.0) < 2.0).sum()
    assert abs(on / len(due) - 80 / 160) < 0.05


def test_size_mixture_counts():
    mix = [{"weight": 0.95, "dist": "uniform", "min": 33, "max": 238},
           {"weight": 0.05, "dist": "uniform", "min": 500, "max": 1000}]
    ns = generator.sizes(mix, 200)
    assert len(ns) == 200 and (ns >= 500).sum() == 10


def test_ladder_rounds_cover_every_slot_count():
    t = load("lastmile-steady")
    rounds = generator.warm_ladder_requests(t, [32, 64], devices=4,
                                            slots=3, seed=0)
    assert [len(r) for r in rounds] == [8, 16, 24]
    assert {r.n for r in rounds[0]} == {32, 64}


def test_buckets_for_the_mix():
    assert generator.buckets_for(load("lastmile-steady"), 16) == \
        [64, 128, 256]

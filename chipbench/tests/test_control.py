"""The control — the reference in the program's place, one precision
down — comes out not correct; the same reference at the configuration's
own precision comes out correct, so the check is not failing everything.
"""
import numpy as np
import pytest

from chipbench import rehearse
from chipbench.harness import (cells, check, control, generator, reference,
                               single)


def cell():
    return rehearse.tiny(cells.resolve(cells.load_bench(), "tsp1002.as"))


def colony_numbers(dtype, seed):
    c = cell()
    conf = c.config
    req = generator.single_instance(conf, seed)
    answer, deposit = control.reference_colony(
        req.coords, conf["instance"]["edge_weight_type"], int(conf["m"]),
        conf["alpha"], conf["beta"], conf["rho"], conf["q"],
        int(c.traffic["iterations"]), int(conf["service"]["chunk"]), seed,
        dtype)
    return check.compare(single.numbers([answer], deposit, conf),
                         conf["limits"])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_colony_is_not_correct(seed):
    ok, rows = colony_numbers("bfloat16", seed)
    assert not ok, rows


@pytest.mark.parametrize("seed", [1, 2])
def test_fp32_colony_is_correct(seed):
    ok, rows = colony_numbers("float32", seed)
    assert ok, rows


@pytest.mark.parametrize("seed", [4, 5, 6])
def test_bf16_served_lengths_are_not_correct(seed):
    traffic = cells.resolve(cells.load_bench(), "route.steady").traffic
    answers = []
    for r in generator.open_loop_schedule(traffic, seed, 2.0)[:20]:
        d = reference.distances(r.coords, "EUC_2D")
        t = np.arange(r.n)
        answers.append({"coords": r.coords, "edge_weight_type": "EUC_2D",
                        "tour": t, "best_len": reference.tour_length(d, t),
                        "iterations": 1, "budget": 1})
    assert check.served_numbers(answers)["len_err"] == 0.0
    got = check.served_numbers(answers, lengths=control.bf16_length)
    assert got["len_err"] > 0.0

"""Supervisor fault-tolerance: injected crashes must not change the
trajectory; restart budget must be enforced."""
import jax
import numpy as np
import pytest

from repro import checkpoint as ck
from repro.core import aco, tsp
from repro.runtime import Supervisor, SupervisorConfig


def _colony_workload(tmp_path, crash_at=None, deadline=None):
    inst = tsp.circle_instance(24, seed=2)
    cfg = aco.ACOConfig(iterations=0, selection="gumbel")
    problem = aco.make_problem(inst, cfg.nn_k)
    crashes = {"left": 1 if crash_at is not None else 0}

    def init():
        return aco.init_colony(inst, cfg)

    def step(state, i):
        if crash_at is not None and i == crash_at and crashes["left"]:
            crashes["left"] -= 1
            raise RuntimeError("injected preemption")
        state, _ = aco.colony_step(problem, state, cfg)
        return state

    mgr = ck.CheckpointManager(str(tmp_path), keep=2, async_write=False)
    sup = Supervisor(SupervisorConfig(total_steps=12, ckpt_every=4,
                                      step_deadline_s=deadline),
                     mgr, init, step)
    return sup


def test_crash_recovery_reproduces_trajectory(tmp_path):
    clean = _colony_workload(tmp_path / "clean").run()
    crashed_sup = _colony_workload(tmp_path / "crash", crash_at=6)
    crashed = crashed_sup.run()
    assert crashed_sup.restarts == 1
    np.testing.assert_allclose(np.asarray(crashed.tau),
                               np.asarray(clean.tau), rtol=1e-6)
    assert float(crashed.best_len) == float(clean.best_len)
    assert int(crashed.iteration) == int(clean.iteration) == 12


def test_restart_budget_enforced(tmp_path):
    inst = tsp.circle_instance(16, seed=3)
    cfg = aco.ACOConfig()
    mgr = ck.CheckpointManager(str(tmp_path), async_write=False)

    def bad_step(state, i):
        raise RuntimeError("permanently broken node")

    sup = Supervisor(SupervisorConfig(total_steps=5, max_restarts=2),
                     mgr, lambda: aco.init_colony(inst, cfg), bad_step)
    with pytest.raises(RuntimeError, match="exceeded 2 restarts"):
        sup.run()
    assert sup.restarts == 3


def test_deadline_triggers_restart_path(tmp_path):
    import time
    slow = {"done": False}
    inst = tsp.circle_instance(16, seed=4)
    cfg = aco.ACOConfig()
    problem = aco.make_problem(inst, cfg.nn_k)

    def step(state, i):
        if i == 2 and not slow["done"]:
            slow["done"] = True
            time.sleep(0.05)          # straggler once
        st, _ = aco.colony_step(problem, state, cfg)
        return st

    # warm the jit cache so compile time doesn't trip the deadline
    aco.colony_step(problem, aco.init_colony(inst, cfg), cfg)

    mgr = ck.CheckpointManager(str(tmp_path), async_write=False)
    sup = Supervisor(SupervisorConfig(total_steps=6, ckpt_every=2,
                                      step_deadline_s=0.04),
                     mgr, lambda: aco.init_colony(inst, cfg), step)
    out = sup.run()
    assert sup.restarts == 1
    assert int(out.iteration) == 6


def test_peaks_keyed_by_device_kind():
    """Roofline peaks come from a table keyed by device_kind; a kind the
    table does not hold is an error, not a default."""
    from repro.launch import mesh
    v5e = mesh.peaks("TPU v5 lite")
    assert v5e["peak_flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        mesh.peaks("cpu")

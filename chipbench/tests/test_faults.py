"""The check fails a run whose timed path is broken underneath.

Each test drives the rest of a run (set-up, window, check) at a tiny size
on the CPU, skipping only the look for a chip, with one fault planted in
the program, and sees ``correct`` come out false; the same run without a
fault comes out true.
"""
import numpy as np
import pytest

from chipbench import rehearse, run
from chipbench.harness import cells, session


@pytest.fixture(autouse=True)
def fresh_programs():
    """Planted faults must reach the compiled programs: no program traced
    before (or with) a fault is reused."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def tiny_cell(name, chips=None, **traffic):
    """The cell at its rehearsal size; ``chips`` puts an open-loop cell on
    that many (virtual) devices, one pool per bucket on each."""
    cell = rehearse.tiny(cells.resolve(cells.load_bench(), name))
    cell.chips = chips or cell.chips
    if cell.traffic["kind"] == "open_loop":
        cell.config = dict(cell.config, service=dict(
            cell.config["service"], max_batch=2))
        cell.traffic = dict(cell.traffic, drain_s=4.0, rate_per_s=40.0,
                            sizes=[{"weight": 1.0, "dist": "uniform",
                                    "min": 10, "max": 30}])
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def correct(name, seconds=2.0, chips=None, **traffic):
    session.add_program_to_path()
    cell = tiny_cell(name, chips, **traffic)
    devs = session.devices(cell.chips, allow_cpu=True)
    _, ok, rows = run.run_cell(cell, 20241, seconds, False, devs,
                               session.now())
    return ok, {r[0]: r[1] for r in rows}


def patch_run_batch(monkeypatch, wrap):
    from repro.solver import engine
    real = engine.run_batch
    monkeypatch.setattr(engine, "run_batch",
                        lambda *a, **k: wrap(real, *a, **k))


def unchanged(real, problem, states, budgets, cfg, max_iters, patience=0,
              since=None, **kw):
    """A step that returns its state unchanged."""
    return (states, since) if not cfg.metrics else (states, since, None)


def half_slots(real, problem, states, budgets, *a, **kw):
    """Half of the batch left out: the upper half of the slots frozen."""
    import jax.numpy as jnp
    b = budgets.shape[0]
    keep = jnp.arange(b) < max(1, b // 2)
    return real(problem, states, jnp.where(keep, budgets, 0), *a, **kw)


def altered(real, *a, **kw):
    """An answer altered where it is produced: the best length is off."""
    out = real(*a, **kw)
    st = out[0]
    return (st._replace(best_len=st.best_len + 1.0),) + tuple(out[1:])


CELLS = [("tsp1002.as", None), ("route.steady", None), ("route.steady", 4)]


@pytest.mark.parametrize("name,chips", CELLS)
def test_sound_run_is_correct(name, chips):
    ok, nums = correct(name, chips=chips)
    assert ok, nums


@pytest.mark.parametrize("name,chips", CELLS)
@pytest.mark.parametrize("fault", [unchanged, altered],
                         ids=["state_unchanged", "answer_altered"])
def test_fault_is_not_correct(monkeypatch, name, chips, fault):
    patch_run_batch(monkeypatch, fault)
    ok, nums = correct(name, chips=chips)
    assert not ok, nums


def test_half_of_the_slots_left_out(monkeypatch):
    patch_run_batch(monkeypatch, half_slots)
    ok, nums = correct("route.steady", chips=4)
    assert not ok and nums["missing"] > 0, nums


def test_half_of_the_ants_left_out(monkeypatch):
    """AS deposits a sum over ants: half of them dropped reads as half
    the weight the tours' lengths allow."""
    from repro.core import pheromone
    real = pheromone.deposit

    def half(n, tours, w, *a, **k):
        m = w.shape[0]
        return real(n, tours, w.at[m // 2:].set(0.0), *a, **k)
    monkeypatch.setattr(pheromone, "deposit", half)
    ok, nums = correct("tsp1002.as")
    assert not ok and nums["dep_weight_under"] > 1.6, nums


def test_chips_other_than_the_first_left_out(monkeypatch):
    """The exchange between chips left out: pools placed on chips 1-3
    never step, so their requests never come back."""
    import jax
    from repro.solver import streaming
    real = streaming.StreamingPool.step_chunk
    first = jax.devices()[0]

    def step_chunk(self, chunk):
        if self.device is None or self.device == first:
            return real(self, chunk)
    monkeypatch.setattr(streaming.StreamingPool, "step_chunk", step_chunk)
    ok, nums = correct("route.steady", chips=4)
    assert not ok and nums["missing"] > 0, nums


def test_swapped_tour_is_caught(monkeypatch):
    """A served tour altered after its length was taken."""
    from repro.solver import streaming
    real = streaming.StreamingPool._free_slots

    def free(self, hits, expired=False):
        out = real(self, hits, expired)
        for r in out:
            t = np.array(r.best_tour)
            t[[0, -2]] = t[[-2, 0]]
            r.best_tour = t
        return out
    monkeypatch.setattr(streaming.StreamingPool, "_free_slots", free)
    ok, nums = correct("route.steady")
    assert not ok and nums["len_err"] > 0, nums

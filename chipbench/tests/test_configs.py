"""A configuration file reaches every solver setting it names, and a key
that is neither a setting nor descriptive is refused."""
import json

import pytest

from chipbench.harness import cells

# The settings the harness passed to ``ACOConfig`` before it read every
# field: both configurations must resolve to what these gave them.
OLD_KEYS = ("variant", "alpha", "beta", "rho", "q", "m", "selection",
            "construction", "deposit", "nn_k", "tau_dtype", "use_pallas",
            "draw_mode", "local_search")


def config_of(workload):
    return cells.resolve(cells.load_bench(), workload).config


@pytest.mark.parametrize("workload", ["tsp1002.as", "route.steady"])
def test_configurations_resolve_as_before(workload):
    from repro.core import aco
    conf = config_of(workload)
    old = aco.ACOConfig(iterations=50, **{k: conf[k] for k in OLD_KEYS
                                          if k in conf})
    assert cells.aco_config(conf, iterations=50) == old


def test_every_setting_reaches_the_program():
    from repro.core import aco
    conf = dict(config_of("tsp1002.as"), variant="mmas",
                local_search="2opt", ls_tours="iteration_best",
                ls_rounds=500, mmas_best="global", sparse=True, sparse_k=16,
                q0=0.5)
    cfg = cells.aco_config(conf, iterations=7)
    assert (cfg.ls_tours, cfg.ls_rounds, cfg.mmas_best, cfg.sparse,
            cfg.sparse_k, cfg.q0, cfg.iterations) == (
        "iteration_best", 500, "global", True, 16, 0.5, 7)
    assert set(cells.aco_fields()) == {
        f for f in aco.ACOConfig.__dataclass_fields__} - {"iterations",
                                                          "seed"}


@pytest.mark.parametrize("key", ["ls_tour", "iterations", "seed", "rhoo"])
def test_unknown_key_is_refused(key):
    conf = dict(config_of("tsp1002.as"), **{key: 1})
    with pytest.raises(ValueError, match=key):
        cells.aco_config(conf)


def test_unknown_key_is_refused_when_the_cell_resolves(tmp_path):
    """A misspelt key in a configuration file fails at load, before any
    run."""
    bench = cells.load_bench()
    conf = dict(config_of("tsp1002.as"), ls_tour="iteration_best")
    path = tmp_path / "typo.json"
    path.write_text(json.dumps(conf))
    bench["configs"][0]["file"] = str(path)     # an absolute path
    with pytest.raises(ValueError, match="ls_tour"):
        cells.resolve(bench, "tsp1002.as")


def test_a_file_setting_reaches_the_program(tmp_path):
    """A setting the harness used to drop, written in a configuration
    file, reaches ``ACOConfig`` through the cell."""
    bench = cells.load_bench()
    conf = dict(config_of("tsp1002.as"), variant="mmas",
                local_search="2opt", ls_tours="iteration_best")
    path = tmp_path / "mmas.json"
    path.write_text(json.dumps(conf))
    bench["configs"][0]["file"] = str(path)     # an absolute path
    cell = cells.resolve(bench, "tsp1002.as")
    cfg = cells.aco_config(cell.config, iterations=3)
    assert (cfg.variant, cfg.local_search, cfg.ls_tours) == (
        "mmas", "2opt", "iteration_best")

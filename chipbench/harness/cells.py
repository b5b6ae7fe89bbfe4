"""Find a cell's configuration, traffic and metric readers by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found from ``BENCHMARK.json``:

- configuration: the ``file`` its entry names (``chipbench/configs/``);
- traffic mix:   ``chipbench/traffic/<traffic>.json``;
- metric reader: ``chipbench/metrics/<metric name>.py``, a ``read(ctx)``
  that returns a number, or None when it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from . import session

BENCH_DIR = os.path.join(session.ROOT, "chipbench")

# Keys of a configuration file that are settings of ``repro.core.aco.
# ACOConfig``; the rest describe the instance, the service and the check.
ACO_KEYS = ("variant", "alpha", "beta", "rho", "q", "m", "selection",
            "construction", "deposit", "nn_k", "tau_dtype", "use_pallas",
            "draw_mode", "local_search")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_bench(root: str = session.ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, name: str) -> Cell:
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(session.ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def reader(metric_name: str):
    """The ``read`` function of ``chipbench/metrics/<metric_name>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", metric_name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def aco_config(config: dict, **over):
    from repro.core import aco
    kw = {k: config[k] for k in ACO_KEYS if k in config}
    kw.update(over)
    return aco.ACOConfig(**kw)


def instance(coords, edge_weight_type: str, name: str):
    from repro.core import tsp
    return tsp.TSPInstance(name=name, coords=coords,
                           edge_weight_type=edge_weight_type)

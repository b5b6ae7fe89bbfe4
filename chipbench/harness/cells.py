"""Find a cell's configuration, traffic and metric readers by name.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found from ``BENCHMARK.json``:

- configuration: the ``file`` its entry names (``chipbench/configs/``);
- traffic mix:   ``chipbench/traffic/<traffic>.json``;
- metric reader: ``chipbench/metrics/<metric name>.py``, a ``read(ctx)``
  that returns a number, or None when it finds nothing to read.

A configuration file is one JSON object.  Its top-level keys are:

- any field of ``repro.core.aco.ACOConfig`` but ``iterations`` and
  ``seed`` (the drivers set those): ``variant``, ``alpha``, ``beta``,
  ``rho``, ``q``, ``m``, ``selection``, ``construction``, ``deposit``,
  ``nn_k``, ``local_search``, ``ls_tours``, ``ls_rounds``, ``mmas_best``,
  ``sparse``, ``sparse_k`` and the rest; each reaches the program as it
  stands, and a field the file leaves out keeps the program's default;
- the descriptive keys ``DESCRIPTIVE``: ``name``, ``source``,
  ``deployment``, ``n``, ``service`` (the streaming service's
  ``max_batch``, ``min_bucket``, ``chunk``), ``instance`` (the closed-loop
  instance: ``kind``, ``box``, ``edge_weight_type``, ``min_distance``),
  ``guarantees``, ``reduced``, ``assumed``, ``limits`` (the numbers
  ``correct`` compares, each with its limit), ``limits_why``,
  ``not_checked`` and ``rehearse`` (overrides for a CPU rehearsal).

Any other key is refused with ``ValueError`` when the cell is resolved,
so a misspelt setting never runs the program's default in silence.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from . import session

BENCH_DIR = os.path.join(session.ROOT, "chipbench")

# Keys of a configuration file that describe the deployment, the service
# and the check; every other key has to name a setting of ``ACOConfig``.
DESCRIPTIVE = ("name", "source", "deployment", "n", "service", "instance",
               "guarantees", "reduced", "assumed", "limits", "limits_why",
               "not_checked", "rehearse")
# ``ACOConfig`` fields the drivers set themselves.
DRIVER_SET = ("iterations", "seed")


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
    session.add_program_to_path()
    check_keys(config)
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


def aco_fields() -> tuple[str, ...]:
    """The ``ACOConfig`` fields a configuration file may set."""
    from repro.core import aco
    return tuple(f.name for f in dataclasses.fields(aco.ACOConfig)
                 if f.name not in DRIVER_SET)


def check_keys(config: dict) -> None:
    """Refuse a top-level key that is neither descriptive nor a setting
    of ``ACOConfig`` (the drivers' own ``iterations`` and ``seed``
    included)."""
    known = set(DESCRIPTIVE) | set(aco_fields())
    bad = sorted(set(config) - known)
    if bad:
        raise ValueError(
            f"configuration {config.get('name')!r}: unknown key(s) {bad}; "
            f"a key is one of {list(DESCRIPTIVE)} or an ACOConfig field "
            f"other than {list(DRIVER_SET)}")


def aco_config(config: dict, **over):
    """The ``ACOConfig`` the configuration states: every key that names
    one of its fields, with the drivers' ``over`` (``iterations``)."""
    from repro.core import aco
    check_keys(config)
    fields = aco_fields()
    kw = {k: config[k] for k in fields if k in config}
    kw.update(over)
    return aco.ACOConfig(**kw)


def instance(coords, edge_weight_type: str, name: str):
    from repro.core import tsp
    return tsp.TSPInstance(name=name, coords=coords,
                           edge_weight_type=edge_weight_type)

"""Compile the cells' chunk programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu python3 chipbench/topology.py

For each cell it lowers ``engine.run_batch``'s donated chunk program at the
cell's real sizes (bucket, slots, ants, chunk) for a described ``v5e:2x2``
topology, on each of the chips the cell uses, and prints one JSON line per
program with the compiler's ``memory_analysis()``.  What the TPU compiler
refuses here costs no chip time; a compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench.harness import cells, generator, session  # noqa: E402


def shapes(bucket: int, batch: int, cfg, sharding):
    import jax
    import jax.numpy as jnp
    from repro.core import aco

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    n, b = bucket, batch
    k = min(cfg.nn_k, n - 1)
    problem = aco.Problem(dist=s((b, n, n), jnp.float32),
                          eta=s((b, n, n), jnp.float32),
                          nn=s((b, n, k), jnp.int32),
                          n_actual=s((b,), jnp.int32))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    states = aco.ColonyState(tau=s((b, n, n), jnp.float32),
                             best_tour=s((b, n), jnp.int32),
                             best_len=s((b,), jnp.float32),
                             iteration=s((b,), jnp.int32),
                             key=s((b,) + key.shape, key.dtype))
    return problem, states, s((b,), jnp.int32), s((b,), jnp.int32)


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    session.add_program_to_path()
    from repro.solver import engine
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = cells.load_bench()
    for w in bench["workloads"]:
        cell = cells.resolve(bench, w["name"])
        conf, traffic = cell.config, cell.traffic
        svc = conf["service"]
        if traffic["kind"] == "closed_loop":
            buckets = [generator.bucket_of(int(conf["n"]),
                                           int(svc["min_bucket"]))]
        else:
            buckets = generator.buckets_for(traffic, int(svc["min_bucket"]))
        cfg = cells.aco_config(conf)
        for chip in range(cell.chips):
            one = SingleDeviceSharding(topo.devices[chip])
            for bucket in buckets:
                problem, states, budgets, since = shapes(
                    bucket, int(svc["max_batch"]), cfg, one)
                compiled = engine.aot_lower(
                    problem, states, budgets, cfg, int(svc["chunk"]), 0,
                    since, None, donate=True).compile()
                ma = compiled.memory_analysis()
                print(json.dumps({
                    "cell": w["name"], "chip": chip, "bucket": bucket,
                    "slots": int(svc["max_batch"]),
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "alias_bytes": ma.alias_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "code_bytes": ma.generated_code_size_in_bytes}),
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

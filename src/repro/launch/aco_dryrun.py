"""ACO-at-scale dry-run: lower + compile the city-sharded colony step for a
large TSP instance on the production mesh, and report the same roofline
terms as the LM cells (EXPERIMENTS.md §Perf cell C — the cell most
representative of the paper's technique).

    PYTHONPATH=src python -m repro.launch.aco_dryrun --n 16384 \
        --variant ants_bf16 [--multi-pod]

Variants (the §Perf ladder):
    baseline   city axis sharded over `model`; ants replicated over `data`
               (the paper's data-parallel design, mesh-tiled)
    ants       + ant population sharded over `data` (deposit psum)
    ants_bf16  + bf16 choice matrix (halves the construction gather bytes)
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import json      # noqa: E402
import time      # noqa: E402

import jax                   # noqa: E402
import jax.numpy as jnp      # noqa: E402
import numpy as np           # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis import hlo as ha                  # noqa: E402
from repro.core import aco, islands                   # noqa: E402
from repro.launch.mesh import make_production_mesh, peaks  # noqa: E402

HW = peaks("TPU v5 lite")   # the production pods this dry run models


def lower_aco(n: int, variant: str, multi_pod: bool) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = aco.ACOConfig()                       # m = n ants, AS defaults
    ants_axis = None if variant == "baseline" else "data"
    cdt = jnp.bfloat16 if variant.endswith("bf16") else jnp.float32
    step = islands.sharded_colony_step_fn(
        mesh, n, cfg, axis="model", ants_axis=ants_axis, choice_dtype=cdt)

    nl = n // mesh.shape["model"]
    dsh = NamedSharding(mesh, P(None, "model"))
    rep = NamedSharding(mesh, P())
    dist = jax.ShapeDtypeStruct((n, n), jnp.float32)
    st = islands.ShardedColonyState(
        tau=jax.ShapeDtypeStruct((n, n), jnp.float32),
        best_tour=jax.ShapeDtypeStruct((n,), jnp.int32),
        best_len=jax.ShapeDtypeStruct((), jnp.float32),
        iteration=jax.ShapeDtypeStruct((), jnp.int32),
        key=jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    t0 = time.time()
    lowered = step.lower(dist, dist, st)
    compiled = lowered.compile()
    t_compile = time.time() - t0

    acc = ha.accumulate(compiled.as_text())
    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if k in ("flops", "bytes accessed")}
    except Exception as e:
        cost = {"error": str(e)}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes"):
            mem[k] = int(getattr(ma, k, 0))
    except Exception:
        pass

    n_dev = int(np.prod(list(mesh.shape.values())))
    # one full AS iteration = n construction steps + deposit
    terms = {
        "compute_s": acc["dot_flops"] / HW["peak_flops_bf16"],
        "memory_s": cost.get("bytes accessed", 0.0) / HW["hbm_bw"],
        "collective_s": acc["collective_total"] / HW["ici_bw"],
    }
    terms["bottleneck"] = max(terms, key=terms.get)
    return {
        "workload": f"aco_sharded_colony_n{n}", "variant": variant,
        "mesh": "multi" if multi_pod else "single", "devices": n_dev,
        "status": "ok", "compile_s": round(t_compile, 2),
        "roofline": terms, "collectives": acc["collective_bytes"],
        "collective_count": acc["collective_count"],
        "memory_analysis": mem, "cost_analysis": cost,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--variant", default="all",
                    choices=["baseline", "ants", "ants_bf16", "all"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="experiments/aco_dryrun")
    args = ap.parse_args()
    variants = (["baseline", "ants", "ants_bf16"] if args.variant == "all"
                else [args.variant])
    os.makedirs(args.out, exist_ok=True)
    for v in variants:
        rec = lower_aco(args.n, v, args.multi_pod)
        path = os.path.join(
            args.out, f"aco_n{args.n}__{v}__{rec['mesh']}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        t = rec["roofline"]
        print(f"[OK] {v:10s} compile={rec['compile_s']}s "
              f"c={t['compute_s']:.3e} m={t['memory_s']:.3e} "
              f"n={t['collective_s']:.3e} -> {t['bottleneck']}", flush=True)


if __name__ == "__main__":
    main()

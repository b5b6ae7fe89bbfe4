"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as a module entry point (PYTHONPATH=src python -m
repro.launch.dryrun ...). The first two lines below force 512 host-platform
devices BEFORE any jax import so jax.make_mesh can build the production
meshes; never import this module from tests (they must see 1 device).

Per cell it records to experiments/dryrun/<cell>.json:
  - compile ok/fail,
  - memory_analysis (bytes per device: args/outputs/temps/code),
  - cost_analysis (per-device HLO flops / bytes accessed),
  - per-collective byte totals parsed from the post-SPMD HLO,
  - analytic MODEL_FLOPS (6·N·D dense / 6·N_active·D MoE) for §Roofline.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse   # noqa: E402
import json       # noqa: E402
import re         # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax                             # noqa: E402
import numpy as np                     # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs              # noqa: E402
from repro.analysis import hlo as ha   # noqa: E402
from repro.launch import specs as sp   # noqa: E402
from repro.launch.mesh import make_production_mesh, peaks  # noqa: E402
from repro.launch import steps as st   # noqa: E402
from repro.models import sharding as sh  # noqa: E402
from repro.optim import adamw          # noqa: E402

HW = peaks("TPU v5 lite")   # the production pods this dry run models


def model_flops(cfg, cell: sp.ShapeCell) -> float:
    """6·N·D with N = active params (MoE) and D = processed tokens."""
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * cell.global_batch          # decode: 1 token/seq


def lower_cell(arch: str, shape: str, multi_pod: bool, tuned: bool = False):
    cfg = configs.get(arch)
    applied = None
    strategy = "2d"                       # fsdp(data) x tp(model)
    if tuned:
        from repro.launch import tuning
        import dataclasses
        applied = tuning.overrides_for(arch, shape)
        if applied:
            applied = dict(applied)
            strategy = applied.pop("mesh_strategy", "2d")
            if applied:
                cfg = dataclasses.replace(cfg, **applied)
            applied["mesh_strategy"] = strategy
    cell = sp.SHAPES[shape]
    ok, why = sp.cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    params_abs = sp.abstract_params(cfg)
    if strategy == "fsdp":
        # pure-FSDP: weights sharded over every axis, batch over every axis
        # that divides, no tensor parallelism.
        all_axes = tuple(mesh.shape.keys())
        pspecs = sh.param_specs(params_abs, cfg, mesh, fsdp_axis=all_axes,
                                model_axis=None)
        keep, rem = [], cell.global_batch
        for a in all_axes:
            if rem % mesh.shape[a] == 0:
                keep.append(a)
                rem //= mesh.shape[a]
        dspec = P(tuple(keep) if keep else None, None)
    else:
        pspecs = sh.param_specs(params_abs, cfg, mesh)
        dspec = sh.data_specs(cfg, mesh, cell.global_batch)
    psh = sh.to_shardings(pspecs, mesh)
    rep = NamedSharding(mesh, P())
    ins = sp.input_specs(cfg, shape)
    dsh = NamedSharding(mesh, dspec)

    ba = dspec[0]
    ba = (ba,) if isinstance(ba, str) else (tuple(ba) if ba else ())
    act_ctx = sh.activation_sharding(mesh, ba)
    act_ctx.__enter__()
    t0 = time.time()
    if cell.kind == "train":
        opt_abs = sp.abstract_opt_state(params_abs)
        osh = adamw.AdamWState(mu=psh, nu=psh, step=rep)
        step = st.make_train_step(cfg, adamw.AdamWConfig(), remat=True)
        args = [params_abs, opt_abs, ins["tokens"], ins["labels"]]
        in_sh = [psh, osh, dsh, dsh]
        if cfg.enc_dec:
            args.append(ins["enc_frames"])
            in_sh.append(NamedSharding(mesh, P(dspec[0], None, None)))
        lowered = jax.jit(step,
                          in_shardings=tuple(in_sh),
                          out_shardings=(psh, osh, rep)).lower(*args)
    elif cell.kind == "prefill":
        step = st.make_prefill_step(cfg)
        args = [params_abs, ins["tokens"]]
        in_sh = [psh, dsh]
        if cfg.enc_dec:
            args.append(ins["enc_frames"])
            in_sh.append(NamedSharding(mesh, P(dspec[0], None, None)))
        lowered = jax.jit(step, in_shardings=tuple(in_sh),
                          out_shardings=dsh).lower(*args)
    else:                                   # decode
        step = st.make_serve_step(cfg)
        cspec = sh.cache_specs(ins["caches"], cfg, mesh, cell.global_batch,
                               shard_seq=(cell.global_batch == 1))
        csh = sh.to_shardings(cspec, mesh)
        tok_sh = NamedSharding(mesh, P(dspec[0], None))
        lowered = jax.jit(step, in_shardings=(psh, tok_sh, csh),
                          out_shardings=(tok_sh, csh)).lower(
                              params_abs, ins["token"], ins["caches"])
    act_ctx.__exit__(None, None, None)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
    except Exception as e:                  # backend-dependent
        mem["error"] = str(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        for k, v in ca.items():
            if k in ("flops", "bytes accessed", "transcendentals",
                     "optimal_seconds") or k.startswith("bytes accessed"):
                cost[k] = float(v)
    except Exception as e:
        cost["error"] = str(e)

    # while-aware accounting: scan bodies multiplied by trip count
    acc = ha.accumulate(compiled.as_text())
    coll = dict(acc["collective_bytes"])
    coll["total"] = acc["collective_total"]
    coll["count"] = acc["collective_count"]

    n_dev = int(np.prod(list(mesh.shape.values())))
    flops_dev = acc["dot_flops"]                  # per-device MXU flops
    bytes_dev = cost.get("bytes accessed", 0.0)   # CPU-HLO upper bound
    mf = model_flops(cfg, cell)
    terms = {
        "compute_s": flops_dev / HW["peak_flops_bf16"],
        "memory_s": bytes_dev / HW["hbm_bw"],
        "collective_s": coll["total"] / HW["ici_bw"],
        "model_flops_total": mf,
        "model_flops_per_device": mf / n_dev,
        "useful_flops_ratio": (mf / n_dev) / flops_dev if flops_dev else None,
    }
    terms["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])

    return {
        "arch": arch, "shape": shape,
        "mesh": "multi" if multi_pod else "single",
        "status": "ok", "devices": n_dev, "tuning": applied,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": mem, "cost_analysis": cost,
        "collectives": coll, "roofline": terms,
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
    }


def run_cell(arch: str, shape: str, multi_pod: bool, outdir: str,
             force: bool = False, tuned: bool = False) -> dict:
    os.makedirs(outdir, exist_ok=True)
    mesh_tag = "multi" if multi_pod else "single"
    path = os.path.join(outdir, f"{arch}__{shape}__{mesh_tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    try:
        rec = lower_cell(arch, shape, multi_pod, tuned=tuned)
    except Exception as e:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_tag,
               "status": "fail", "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=2)
    os.replace(tmp, path)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tuned", action="store_true",
                    help="apply launch.tuning overrides (write to a "
                         "separate dir so baselines stay recorded)")
    args = ap.parse_args()
    if args.tuned and args.out == "experiments/dryrun":
        args.out = "experiments/dryrun_tuned"

    archs = list(configs.ARCHS) if args.arch == "all" else [
        configs.canonical(args.arch)]
    shapes = list(sp.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.out, args.force,
                               tuned=args.tuned)
                tag = f"{arch} x {shape} x {rec['mesh']}"
                if rec["status"] == "ok":
                    n_ok += 1
                    r = rec["roofline"]
                    print(f"[OK]   {tag}: compile={rec['compile_s']}s "
                          f"bottleneck={r['bottleneck']} "
                          f"(c={r['compute_s']:.3e} m={r['memory_s']:.3e} "
                          f"n={r['collective_s']:.3e})", flush=True)
                    print("  memory:", rec["memory_analysis"], flush=True)
                elif rec["status"] == "skipped":
                    n_skip += 1
                    print(f"[SKIP] {tag}: {rec['reason']}", flush=True)
                else:
                    n_fail += 1
                    print(f"[FAIL] {tag}: {rec['error']}", flush=True)
    print(f"done: {n_ok} ok / {n_skip} skipped / {n_fail} failed", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

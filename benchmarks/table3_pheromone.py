"""Paper Tables III/IV: pheromone-update strategy ladder.

Claims under test: C4 (scatter-to-gather is orders of magnitude worse than
the scatter/atomic-analogue, growing with n) and C5 (tiling / symmetric
reduction improve s2g but not its order of magnitude). Adds the TPU-native
one-hot-MXU deposit and the fused Pallas kernel — the beyond-paper rows that
invert the paper's conclusion on this hardware (DESIGN.md §6).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import aco, pheromone, strategies, tsp
from repro.kernels import ops as kops

from .timing import time_fn

SIZES = (48, 100, 280)
FULL_SIZES = (48, 100, 280, 442)


def _tours(n: int):
    inst = tsp.random_instance(n, seed=n)
    prob = aco.make_problem(inst, 8)
    tau0 = aco.initial_tau(inst, aco.ACOConfig())
    tau = jnp.full((n, n), tau0, jnp.float32)
    ci = strategies.choice_matrix(tau, prob.eta, 1.0, 2.0)
    res = strategies.construct_tours(jax.random.PRNGKey(3), prob.dist, ci, n)
    w = 1.0 / res.lengths
    return tau, res.tours, w


def rows(sizes=SIZES):
    out = []
    for n in sizes:
        tau, tours, w = _tours(n)
        upd = lambda strat: time_fn(
            jax.jit(lambda t: pheromone.update(t, tours, w, 0.5,
                                               strategy=strat)), tau,
            warmup=1, iters=3)
        r = {"n": n}
        # 1/2. atomic + shared-memory analogue: XLA scatter-add
        r["v1_scatter_atomic"] = upd("scatter")
        # 3. Instruction & thread Reduction (symmetry, half the updates)
        r["v3_reduction"] = upd("reduction")
        # 4. scatter-to-gather + tiling
        r["v4_s2g_tiled"] = upd("s2g_tiled")
        # 5. scatter-to-gather (honest O(n^4))
        r["v5_s2g"] = upd("s2g")
        # ours: one-hot MXU deposit, and the fused Pallas kernel
        # (interpret mode = Python speed; timed at small n for structure only)
        r["ours_onehot"] = upd("onehot")
        r["ours_pallas_fused"] = (time_fn(
            lambda t: kops.pheromone_update(t, tours, w, 0.5), tau,
            warmup=1, iters=3) if n <= 100 else float("nan"))
        r["slowdown_s2g_vs_atomic"] = r["v5_s2g"] / r["v1_scatter_atomic"]
        out.append(r)
    return out


def main(sizes=SIZES):
    print("table3_pheromone (ms per pheromone update, m=n ants)")
    hdr = None
    for r in rows(sizes):
        if hdr is None:
            hdr = list(r.keys())
            print(",".join(hdr))
        print(",".join(f"{r[k]:.2f}" if isinstance(r[k], float) else str(r[k])
                       for k in hdr))


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    main(FULL_SIZES if ap.parse_args().full else SIZES)

"""Paper Table II: tour-construction strategy ladder.

Reproduces the paper's code-version ladder on CPU-JAX (one iteration of m=n
ants). GPU-memory-placement versions (5/6: shared/texture) have no TPU/JAX
analogue — the nearest mapping is noted per row. The paper's claims under
test: C1 (data-parallel >> task-parallel), C2 (choice precompute win),
C3 (NN-list win).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import aco, strategies, tsp

from .timing import time_fn

SIZES = (48, 100, 280, 442)
FULL_SIZES = (48, 100, 280, 442, 657, 1002)


def _mk(n: int):
    inst = tsp.random_instance(n, seed=n)
    prob = aco.make_problem(inst, nn_k=min(30, n - 1))
    cfg = aco.ACOConfig()
    tau0 = aco.initial_tau(inst, cfg)
    tau = jnp.full((n, n), tau0, jnp.float32)
    ci = strategies.choice_matrix(tau, prob.eta, 1.0, 2.0)
    return inst, prob, tau, ci


def _construct(prob, ci, tau, m, method, selection="iroulette"):
    key = jax.random.PRNGKey(7)

    def run(k):
        return strategies.construct_tours(
            k, prob.dist, ci, m, method=method, selection=selection,
            nn=prob.nn, tau=tau, eta=prob.eta)

    return time_fn(run, key, warmup=1, iters=3)


def rows(sizes=SIZES):
    out = []
    for n in sizes:
        inst, prob, tau, ci = _mk(n)
        m = n
        r = {"n": n}
        # 1. task-based, recompute heuristic each step (paper baseline)
        r["v1_task_baseline"] = _construct(prob, ci, tau, m, "task_baseline")
        # 2. + Choice kernel (precompute tau^a*eta^b)
        r["v2_choice"] = _construct(prob, ci, tau, m, "task_choice",
                                    selection="roulette")
        # 3. device-side RNG: jax.random is already device-side; = v2 (noted)
        # 4. NN-list
        r["v4_nnlist"] = _construct(prob, ci, tau, m, "nn_list")
        # 7. data parallelism (paper's contribution): I-Roulette reduction
        r["v7_data_parallel"] = _construct(prob, ci, tau, m, "data_parallel")
        # 8. + Pallas tour_select kernel (VMEM-tiled fused selection;
        #    interpret mode on CPU — structural row, real perf needs TPU)
        r["v8_data_parallel_pallas"] = (
            _construct(prob, ci, tau, m, "pallas") if n <= 100
            else float("nan"))
        r["total_speedup_v1_over_v7"] = r["v1_task_baseline"] / r["v7_data_parallel"]
        out.append(r)
    return out


def main(sizes=SIZES):
    print("table2_tour_construction (ms per AS iteration's construction)")
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

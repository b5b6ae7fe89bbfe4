"""Paper Fig. 4: full-iteration speed-up of the accelerated AS over the
sequential CPU code (here: pure-NumPy SequentialAS standing in for Stützle's
ANSI-C, vs the jitted JAX colony step).

Fig 4(a): NN-list construction (NN=30). Fig 4(b): fully probabilistic
data-parallel construction. Absolute speed-ups are CPU-vs-CPU (one core) and
NOT comparable to the paper's GPU numbers; the claim under test is the
*shape*: speed-up grows with n, and data-parallel wins more at small n
than task-style at small n (C1).
"""
from __future__ import annotations

import jax

from repro.core import aco, sequential, tsp

from .timing import time_fn, time_host_fn

SIZES = (48, 100, 280)
FULL_SIZES = (48, 100, 280, 442)


def rows(sizes=SIZES):
    out = []
    for n in sizes:
        inst = tsp.random_instance(n, seed=n)
        d = inst.distances()
        seq = sequential.SequentialAS(d, m=n, seed=0)
        seq_ms = time_host_fn(seq.iterate, iters=1)
        seq_nn = sequential.SequentialAS(d, m=n, seed=0, nn_k=min(30, n - 1))
        seq_nn_ms = time_host_fn(seq_nn.iterate, iters=1)

        prob = aco.make_problem(inst, nn_k=min(30, n - 1))

        def one_iter(cfg):
            st = aco.init_colony(inst, cfg)
            step = lambda s: aco.colony_step(prob, s, cfg)[0]
            return time_fn(step, st, warmup=1, iters=3)

        dp_ms = one_iter(aco.ACOConfig(construction="data_parallel"))
        nn_ms = one_iter(aco.ACOConfig(construction="nn_list"))
        out.append({
            "n": n,
            "seq_full_ms": seq_ms, "jax_data_parallel_ms": dp_ms,
            "fig4b_speedup": seq_ms / dp_ms,
            "seq_nn_ms": seq_nn_ms, "jax_nnlist_ms": nn_ms,
            "fig4a_speedup": seq_nn_ms / nn_ms,
        })
    return out


def main(sizes=SIZES):
    print("fig4_overall (ms per full AS iteration; speedup vs sequential)")
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

"""The control: the reference put in the program's place, one precision down.

The configurations state float32 pheromone and exact tour lengths; the
control computes the same semantics in bfloat16, the step a later change
might be tempted to take, and the check has to call it not correct.

- ``bf16_length``: a tour's length summed in bfloat16 (what a served
  answer would report if its length were computed one precision down).
- ``reference_colony``: the plain Ant System (SequentialAS's semantics:
  tau0 = m / C_nn, I-Roulette construction over choice = tau^alpha *
  eta^beta, evaporation, symmetric q / L_k deposit) in ``jax.numpy`` at a
  given dtype, so it runs on the chip at the cell's size.  It imports
  nothing of the program.
"""
from __future__ import annotations

import numpy as np

from . import reference


def bf16_length(dist: np.ndarray, tour: np.ndarray) -> float:
    """Closed-tour length accumulated edge by edge in bfloat16."""
    from ml_dtypes import bfloat16
    t = np.asarray(tour, np.int64)
    edges = dist[t, np.roll(t, -1)].astype(bfloat16)
    total = bfloat16(0)
    for e in edges:
        total = bfloat16(total + e)
    return float(total)


FAULTS = ("half_ants", "one_sided", "random_tour")


def random_tour(answer: dict, seed: int) -> dict:
    """An answer altered where it is produced: a random tour, reported
    with its exact length."""
    dist = reference.distances(answer["coords"], answer["edge_weight_type"])
    tour = np.random.default_rng(seed).permutation(dist.shape[0])
    return dict(answer, tour=tour,
                best_len=reference.tour_length(dist, tour))


def reference_colony(coords, edge_weight_type: str, m: int, alpha: float,
                     beta: float, rho: float, q: float, iterations: int,
                     chunk: int, seed: int, dtype: str = "bfloat16",
                     fault=None):
    """Run the reference Ant System for ``iterations`` at ``dtype``.

    Returns (answer, deposit) in the shapes ``single.numbers`` reads: the
    best tour with its length as computed at ``dtype``, and the pheromone
    before and after the last full chunk of ``chunk`` iterations.
    ``fault`` plants one of ``FAULTS``: half of the ants' deposits left
    out, a one-sided deposit of twice the weight, or a random best tour."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    dist64 = reference.distances(coords, edge_weight_type)
    n = dist64.shape[0]
    tau0 = reference.initial_tau(dist64, m)
    dist = jnp.asarray(dist64, dt)
    eta = jnp.asarray(1.0 / np.maximum(dist64, 1e-10), dt)

    @jax.jit
    def iterate(tau, key):
        k_start, k_steps = jax.random.split(key)
        choice = (tau ** alpha * eta ** beta).astype(dt)
        start = jax.random.randint(k_start, (m,), 0, n)
        visited = jnp.zeros((m, n), bool).at[jnp.arange(m), start].set(True)
        tours = jnp.zeros((m, n), jnp.int32).at[:, 0].set(start)

        def body(s, carry):
            cur, visited, tours = carry
            u = jax.random.uniform(jax.random.fold_in(k_steps, s), (m, n),
                                   dt, minval=1e-6, maxval=1.0)
            w = jnp.where(visited, jnp.zeros((), dt), choice[cur]) * u
            nxt = jnp.argmax(w, axis=-1).astype(jnp.int32)
            return (nxt, visited.at[jnp.arange(m), nxt].set(True),
                    tours.at[:, s].set(nxt))

        _, _, tours = jax.lax.fori_loop(1, n, body, (start, visited, tours))
        nxt = jnp.roll(tours, -1, axis=-1)
        lengths = dist[tours, nxt].sum(axis=-1, dtype=dt)
        w = (q / lengths).astype(dt)
        if fault == "half_ants":
            w = w.at[m // 2:].set(0)
        dep = jnp.zeros((n, n), dt).at[tours, nxt].add(
            jnp.broadcast_to(w[:, None], (m, n)))
        sym = 2 * dep if fault == "one_sided" else dep + dep.T
        tau = ((1.0 - rho) * tau + sym).astype(dt)
        best = jnp.argmin(lengths)
        return tau, tours[best], lengths[best]

    tau = jnp.full((n, n), tau0, dt)
    key = jax.random.PRNGKey(seed)
    best_len, best_tour = np.inf, None
    full = (iterations // chunk) * chunk
    before = None
    for it in range(full):
        if it == full - chunk:
            before = np.asarray(tau.astype(jnp.float32))
        key, sub = jax.random.split(key)
        tau, tour, length = iterate(tau, sub)
        if float(length) < best_len:
            best_len, best_tour = float(length), np.asarray(tour)
        if it == full - 1:
            after = np.asarray(tau.astype(jnp.float32))
            chunk_best = best_len
    answer = {"coords": coords, "edge_weight_type": edge_weight_type,
              "tour": best_tour, "best_len": best_len,
              "iterations": full, "budget": full}
    deposit = {"tau_before": before, "tau_after": after, "chunk": chunk,
               "best_len": chunk_best}
    if fault == "random_tour":
        answer = random_tour(answer, seed)
    return answer, deposit

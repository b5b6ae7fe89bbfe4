"""The plain reference: NumPy, float64, independent of the program.

Copied from the semantics of ``repro.core.sequential.SequentialAS`` (the
paper's sequential Ant System: tau0 = m / C_nn, evaporation by (1 - rho),
a symmetric deposit of q / L_k on every edge of every tour) and of the
TSPLIB distance rules.  Nothing here imports the program or takes anything
it made other than the answers being checked: tours, lengths and the
pheromone matrix it returns.
"""
from __future__ import annotations

import numpy as np


def distances(coords: np.ndarray, edge_weight_type: str) -> np.ndarray:
    """(n, n) float64 distances under the TSPLIB rule."""
    xy = np.asarray(coords, np.float64)
    d = np.sqrt(((xy[:, None, :] - xy[None, :, :]) ** 2).sum(-1))
    if edge_weight_type == "EUC_2D":
        d = np.rint(d)
    elif edge_weight_type != "RAW":
        raise ValueError(f"unsupported edge weight type {edge_weight_type}")
    np.fill_diagonal(d, 0.0)
    return d


def is_permutation(tour: np.ndarray, n: int) -> bool:
    t = np.asarray(tour)
    return t.shape == (n,) and bool((np.sort(t) == np.arange(n)).all())


def tour_length(dist: np.ndarray, tour: np.ndarray) -> float:
    """Closed-tour length in float64."""
    t = np.asarray(tour, np.int64)
    return float(dist[t, np.roll(t, -1)].sum())


def nn_tour_length(dist: np.ndarray, start: int = 0) -> float:
    """Greedy nearest-neighbour tour length (SequentialAS._nn_tour_length)."""
    n = dist.shape[0]
    visited = np.zeros(n, bool)
    cur, total = start, 0.0
    visited[cur] = True
    for _ in range(n - 1):
        d = np.where(visited, np.inf, dist[cur])
        nxt = int(np.argmin(d))
        total += dist[cur, nxt]
        visited[nxt] = True
        cur = nxt
    return float(total + dist[cur, start])


def initial_tau(dist: np.ndarray, m: int) -> float:
    """Ant System tau0 = m / C_nn."""
    return m / nn_tour_length(dist)


def update_pheromone(tau: np.ndarray, tours: np.ndarray,
                     lengths: np.ndarray, rho: float,
                     q: float = 1.0) -> np.ndarray:
    """SequentialAS.update_pheromone: evaporate, then deposit q / L_k on
    both directions of every edge of every tour."""
    tau = tau * (1.0 - rho)
    for k in range(tours.shape[0]):
        w = q / lengths[k]
        t = tours[k]
        nxt = np.roll(t, -1)
        tau[t, nxt] += w
        tau[nxt, t] += w
    return tau


def deposit_numbers(tau_before: np.ndarray, tau_after: np.ndarray, n: int,
                    m: int, iterations: int, rho: float, q: float,
                    best_len: float) -> dict:
    """What any set of valid tours deposits, read off one chunk.

    Over ``iterations`` Ant System iterations, D = tau_after -
    (1 - rho)^iterations * tau_before, on the n real cities, is a weighted
    sum of closed tours: every tour puts its weight q / L on two entries of
    every row.  So, whatever the tours were:

    - ``dep_asym``: D is symmetric;
    - ``dep_rowsum_spread``: every row of D sums to the same value;
    - ``dep_weight_over``: that row sum is at most
      2 q m sum_t (1 - rho)^(iterations - t) / best_len, since no tour is
      shorter than the best one found;
    - ``dep_weight_under``: and, read as its inverse, not far below it
      (ants' tours are not many times longer than the best one).
    """
    b = np.asarray(tau_before, np.float64)[:n, :n]
    a = np.asarray(tau_after, np.float64)[:n, :n]
    d = a - (1.0 - rho) ** iterations * b
    scale = float(np.abs(d).max())
    rows = d.sum(axis=1)
    med = float(np.median(rows))
    decay = sum((1.0 - rho) ** (iterations - t)
                for t in range(1, iterations + 1))
    bound = 2.0 * q * m * decay / best_len
    ratio = med / bound if bound > 0 else float("inf")
    return {
        "dep_asym": float(np.abs(d - d.T).max()) / scale if scale else 1.0,
        "dep_rowsum_spread": (float(rows.max() - rows.min()) / med
                              if med > 0 else 1.0),
        "dep_weight_over": ratio,
        "dep_weight_under": 1.0 / ratio if ratio > 0 else float("inf"),
    }

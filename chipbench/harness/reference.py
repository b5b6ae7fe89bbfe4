"""The plain reference: NumPy, float64, independent of the program.

Copied from the semantics of ``repro.core.sequential.SequentialAS`` (the
paper's sequential Ant System: tau0 = m / C_nn, evaporation by (1 - rho),
a symmetric deposit of q / L_k on every edge of every tour) and of the
TSPLIB distance rules; written from the definitions of the MAX-MIN Ant
System (Stuetzle & Hoos 2000; Dorigo & Stuetzle 2004, section 3.3.4: one
tour deposits, then tau is clamped to [tau_min, tau_max]) and of the
nearest-neighbour-restricted 2-opt move (Dorigo & Stuetzle 2004, section
3.7.2).  Nothing here imports the program or takes anything
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


def mmas_update(tau: np.ndarray, tour: np.ndarray, length: float,
                best_len: float, rho: float, q: float = 1.0) -> np.ndarray:
    """One MAX-MIN Ant System update: evaporate, deposit q / length on
    both directions of every edge of the one tour, then clamp to
    [tau_max / (2 n), tau_max], tau_max = q / (rho best_len)."""
    n = tau.shape[0]
    tau = tau * (1.0 - rho)
    t = np.asarray(tour)
    nxt = np.roll(t, -1)
    tau[t, nxt] += q / length
    tau[nxt, t] += q / length
    tau_max = q / (rho * best_len)
    return np.clip(tau, tau_max / (2.0 * n), tau_max)


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


def mmas_numbers(tau_before: np.ndarray, tau_after: np.ndarray, n: int,
                 iterations: int, rho: float, q: float, best_len: float,
                 eps: float = 1e-5) -> dict:
    """What a MAX-MIN Ant System chunk leaves, read off its pheromone.

    Each of the ``iterations`` MMAS iterations evaporates tau by
    (1 - rho), deposits q / L on both directions of every edge of one
    tour (the iteration best, or the best so far), then clamps tau to
    [tau_min, tau_max] with tau_max = q / (rho L_best) and tau_min =
    tau_max / (2 n) for the best length L_best at that iteration.  On the
    n real cities, off the diagonal, with ``best_len`` the best length
    after the chunk (the last clamp's):

    - ``dep_asym``: as for Ant System, D = tau_after - (1 - rho)^iterations
      * tau_before is symmetric;
    - ``mmas_bound_err``: the largest amount by which tau_after leaves
      [tau_min, tau_max], over tau_max;
    - ``mmas_rows_over``: L_best never grows, so tau_min never falls, and
      an entry no deposit touched ends at most at max((1 - rho)^iterations
      * tau_before, tau_min).  One tour a iteration touches two entries of
      every row, so no row has more than 2 * iterations entries above
      that (by a factor 1 + ``eps``): the largest count in a row, over
      2 * iterations.  A deposit by every ant reads about m;
    - ``mmas_rows_under``: the last iteration's tour touches two entries
      of every row, each then at least q / L_iter > tau_min, or tau_max:
      the share of rows with fewer than two entries above that bound.
    """
    if not 0.0 < best_len < np.inf:     # no tour found: nothing to hold
        return dict.fromkeys(("dep_asym", "mmas_bound_err",
                              "mmas_rows_over", "mmas_rows_under"), np.inf)
    b = np.asarray(tau_before, np.float64)[:n, :n]
    a = np.asarray(tau_after, np.float64)[:n, :n]
    off = ~np.eye(n, dtype=bool)
    decay = (1.0 - rho) ** iterations
    d = np.where(off, a - decay * b, 0.0)
    scale = float(np.abs(d).max())
    tau_max = q / (rho * best_len)
    tau_min = tau_max / (2.0 * n)
    over = np.maximum(a - tau_max, tau_min - a)[off]
    risen = (a > np.maximum(decay * b, tau_min) * (1.0 + eps)) & off
    counts = risen.sum(axis=1)
    return {
        "dep_asym": float(np.abs(d - d.T).max()) / scale if scale else 1.0,
        "mmas_bound_err": max(0.0, float(over.max())) / tau_max,
        "mmas_rows_over": float(counts.max()) / (2.0 * iterations),
        "mmas_rows_under": float((counts < 2).mean()),
    }


def nn_candidates(dist: np.ndarray, k: int) -> np.ndarray:
    """(n, min(k, n - 1)) nearest neighbours of every city, itself left
    out, ties broken by the lower city index."""
    n = dist.shape[0]
    d = np.where(np.eye(n, dtype=bool), np.inf, dist)
    return np.argsort(d, axis=1, kind="stable")[:, :max(1, min(k, n - 1))]


def _two_opt_deltas(dist: np.ndarray, tour: np.ndarray, cand: np.ndarray):
    """(n, k) change of the tour's length by the move of each city a and
    candidate ``cand[a]``, 0 for a move that changes nothing, and the
    position of each city in the tour."""
    n = tour.shape[0]
    pos = np.empty(n, np.int64)
    pos[tour] = np.arange(n)
    succ = tour[(pos + 1) % n]
    a = np.arange(n)[:, None]
    a2, c2 = succ[a], succ[cand]
    delta = dist[a, cand] + dist[a2, c2] - dist[a, a2] - dist[cand, c2]
    return np.where((cand == a2) | (c2 == a), 0.0, delta), pos


def two_opt_gain(dist: np.ndarray, tour: np.ndarray, k: int) -> float:
    """The largest relative gain (L - L') / L of one 2-opt move over the
    ``k`` nearest neighbours, 0 at a local optimum.

    For each city a with successor a' in the tour, and each c among a's
    ``k`` nearest neighbours with successor c', the move replaces the
    edges (a, a') and (c, c') by (a, c) and (a', c') (the segment between
    them reversed).  A move that shares an edge with the tour (c = a' or
    c' = a) changes nothing."""
    t = np.asarray(tour, np.int64)
    if t.shape[0] < 4:
        return 0.0
    delta, _ = _two_opt_deltas(dist, t, nn_candidates(dist, k))
    return max(0.0, -float(delta.min())) / tour_length(dist, t)


def two_opt_polish(dist: np.ndarray, tour: np.ndarray, k: int
                   ) -> np.ndarray:
    """The tour after best-improvement 2-opt moves over the ``k`` nearest
    neighbours, one at a time, until none shortens it."""
    t = np.asarray(tour, np.int64).copy()
    cand = nn_candidates(dist, k)
    while t.shape[0] >= 4:
        delta, pos = _two_opt_deltas(dist, t, cand)
        a, c = np.unravel_index(np.argmin(delta), delta.shape)
        if delta[a, c] >= 0:
            break
        i, j = sorted((pos[a], pos[cand[a, c]]))
        t[i + 1:j + 1] = t[i + 1:j + 1][::-1]
    return t

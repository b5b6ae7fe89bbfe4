"""Traffic and instances, made from the seed.

One general generator reads a traffic file (``chipbench/traffic/<name>.json``)
and a configuration file, and returns the requests of one run.  Two kinds:

- ``closed_loop``: the configuration's own instance, solved back to back
  (``single_instance``);
- ``open_loop``: requests due on an open-loop schedule, whatever the server
  does.  The rate may switch on and off (bursts), and the sizes are a
  mixture of distributions.

Steadiness: every run offers the same load.  The sizes and inter-arrival
gaps of each phase (warm-up, window, drain) are drawn at stratified
quantiles and put in an order drawn from the traffic file's
``order_seed``; ``--seed`` draws the stops' coordinates and the solver's
seeds.  So two seeds do the same work on different data.

Extends ``repro.solver.streaming.make_poisson_trace`` (uniform sizes,
circle/uniform coordinates only); this copy is the benchmark's own, so no
change to the program can move the yardstick.
"""
from __future__ import annotations

import dataclasses
import math
import numpy as np

SEED_MOD = 2**31 - 1


@dataclasses.dataclass(frozen=True)
class Request:
    """One request of the schedule.  ``due`` is seconds from the start of
    its phase; ``phase`` is ``warm``, ``window`` or ``drain``."""
    index: int
    phase: str
    due: float
    n: int
    coords: np.ndarray           # (n, 2) float64
    solver_seed: int


def rng_for(seed: int, *path: int) -> np.random.Generator:
    """Independent stream for (seed, path...): any whole seed, 64-bit and
    beyond, maps to a reproducible generator."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *path]))


def _min_separated(xy: np.ndarray, rng: np.random.Generator, sample,
                   min_d: float) -> np.ndarray:
    """Redraw points closer than ``min_d`` to an earlier point, so no two
    cities share a rounded distance of 0 (eta = 1/d would blow up)."""
    for _ in range(100):
        d = np.sqrt(((xy[:, None, :] - xy[None, :, :]) ** 2).sum(-1))
        np.fill_diagonal(d, np.inf)
        close = np.triu(d < min_d).any(axis=0)   # later point of a pair
        if not close.any():
            return xy
        xy[close] = sample(int(close.sum()), rng)
    raise RuntimeError("could not separate points")


def coordinates(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """City coordinates by ``spec["kind"]``:

    - ``uniform``: uniform in a ``box`` x ``box`` square;
    - ``clustered``: DIMACS TSP Challenge style, ``n / points_per_centre``
      centres uniform in the box, each point Gaussian around a uniformly
      chosen centre with standard deviation ``sd_scale * box / sqrt(n)``.
    """
    box = float(spec["box"])
    kind = spec["kind"]
    if kind == "uniform":
        def sample(k, r):
            return r.uniform(0.0, box, size=(k, 2))
    elif kind == "clustered":
        centres = rng.uniform(0.0, box, size=(
            max(1, round(n / spec["points_per_centre"])), 2))
        sd = spec.get("sd_scale", 1.0) * box / math.sqrt(n)

        def sample(k, r):
            c = centres[r.integers(0, len(centres), size=k)]
            return np.clip(c + r.normal(0.0, sd, size=(k, 2)), 0.0, box)
    else:
        raise ValueError(f"unknown coordinate kind {kind!r}")
    return _min_separated(sample(n, rng), rng, sample,
                          float(spec.get("min_distance", 1.0)))


def _quantiles(k: int) -> np.ndarray:
    return (np.arange(k) + 0.5) / k


def _size_quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of one size distribution, rounded to whole cities."""
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "uniform":
        return np.clip(np.floor(lo + u * (hi - lo + 1)), lo, hi).astype(int)
    if kind == "truncnorm":
        from statistics import NormalDist
        nd = NormalDist(dist["mean"], dist["sd"])
        a, b = nd.cdf(lo - 0.5), nd.cdf(hi + 0.5)
        x = [nd.inv_cdf(a + v * (b - a)) for v in u]
        return np.clip(np.rint(x), lo, hi).astype(int)
    raise ValueError(f"unknown size distribution {kind!r}")


def sizes(mix: list[dict], k: int) -> np.ndarray:
    """k sizes at stratified quantiles of a weighted mixture.  Component j
    gets round(weight_j * k) of them (largest remainders), so the mix of a
    phase is the same multiset for every seed."""
    w = np.asarray([c["weight"] for c in mix], float)
    w = w / w.sum()
    raw = w * k
    cnt = np.floor(raw).astype(int)
    for j in np.argsort(-(raw - cnt))[: k - cnt.sum()]:
        cnt[j] += 1
    return np.concatenate([_size_quantile(c, _quantiles(c_k))
                           for c, c_k in zip(mix, cnt) if c_k > 0] or
                          [np.zeros(0, int)])


def _cumulative(traffic: dict, t: float) -> float:
    """Expected arrivals in [0, t): ``rate_per_s`` times t, with the rate
    multiplied by ``bursts.factor`` in the first ``on_s`` of every
    ``period_s``."""
    r = float(traffic["rate_per_s"])
    b = traffic.get("bursts")
    if not b:
        return r * t
    p, on, f = b["period_s"], b["on_s"], b["factor"]
    full, rem = divmod(t, p)
    per = r * (on * f + (p - on))
    return full * per + r * (min(rem, on) * f + max(0.0, rem - on))


def _invert(traffic: dict, lam: float, horizon: float) -> float:
    lo, hi = 0.0, horizon
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        if _cumulative(traffic, mid) < lam:
            lo = mid
        else:
            hi = mid
    return hi


def due_times(traffic: dict, duration: float,
              rng: np.random.Generator) -> np.ndarray:
    """Open-loop due times in [0, duration): an inhomogeneous Poisson
    process by time rescaling, with the unit-rate gaps taken at stratified
    exponential quantiles in an order ``rng`` shuffles."""
    total = _cumulative(traffic, duration)
    k = int(round(total))
    if k == 0:
        return np.zeros(0)
    gaps = -np.log1p(-_quantiles(k))           # unit-exponential quantiles
    gaps *= total / gaps.sum()                 # exactly k arrivals
    lam = np.cumsum(rng.permutation(gaps)) - gaps.mean() * rng.uniform()
    lam = np.clip(lam, 0.0, np.nextafter(total, 0.0))
    return np.asarray([_invert(traffic, x, duration) for x in lam])


def open_loop_schedule(traffic: dict, seed: int, seconds: float
                       ) -> list[Request]:
    """The whole schedule of one run: warm-up, window and drain phases,
    each with its own stratified sizes and gaps."""
    phases = [("warm", float(traffic["warm_s"])), ("window", float(seconds)),
              ("drain", float(traffic["drain_s"]))]
    out: list[Request] = []
    for p_i, (phase, dur) in enumerate(phases):
        order = rng_for(int(traffic["order_seed"]), 1, p_i)
        due = due_times(traffic, dur, order)
        ns = order.permutation(sizes(traffic["sizes"], len(due)))
        for due_t, n in zip(due, ns):
            idx = len(out)
            out.append(Request(
                index=idx, phase=phase, due=float(due_t), n=int(n),
                coords=coordinates(traffic["coords"], int(n),
                                   rng_for(seed, 2, idx)),
                solver_seed=int(rng_for(seed, 3, idx).integers(SEED_MOD))))
    return out


def single_instance(config: dict, seed: int) -> Request:
    """The configuration's one instance (``config["instance"]``)."""
    spec = config["instance"]
    n = int(config["n"])
    return Request(index=0, phase="window", due=0.0, n=n,
                   coords=coordinates(spec, n, rng_for(seed, 2, 0)),
                   solver_seed=int(rng_for(seed, 3, 0).integers(SEED_MOD)))


def warm_ladder_requests(traffic: dict, buckets: list[int], devices: int,
                         slots: int, seed: int) -> list[list[Request]]:
    """Set-up rounds that touch every shape the window can use: round k
    puts k requests of each bucket on every device's pool, for k = 1 ..
    slots, so refills and harvests of k slots at once are compiled before
    the window.  Sizes are each bucket's largest."""
    rounds = []
    idx = 0
    for k in range(1, slots + 1):
        reqs = []
        for b in buckets:
            for _ in range(k * devices):
                reqs.append(Request(
                    index=idx, phase="ladder", due=0.0, n=b,
                    coords=coordinates(traffic["coords"], b,
                                       rng_for(seed, 4, idx)),
                    solver_seed=idx))
                idx += 1
        rounds.append(reqs)
    return rounds


def bucket_of(n: int, min_bucket: int) -> int:
    b = min_bucket
    while b < n:
        b <<= 1
    return b


def buckets_for(traffic: dict, min_bucket: int) -> list[int]:
    """Every bucket the traffic's size mix can land in."""
    lo = min(int(c["min"]) for c in traffic["sizes"])
    hi = max(int(c["max"]) for c in traffic["sizes"])
    out = [bucket_of(lo, min_bucket)]
    while out[-1] < bucket_of(hi, min_bucket):
        out.append(out[-1] * 2)
    return out

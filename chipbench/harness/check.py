"""The comparison that decides ``correct``.

Each cell's numbers are computed here from the answers its timed path gave
and the plain reference (``reference.py``), then compared with the limits
its configuration file states under ``limits``: a number is within its
limit when it is no larger.  A number a run cannot compute reads ``inf``.
"""
from __future__ import annotations

import math
from typing import Callable, Optional

import numpy as np

from . import reference


def two_opt_k(conf: Optional[dict]) -> Optional[int]:
    """The 2-opt neighbourhood width of a configuration whose local search
    includes 2-opt (``nn_k``, the program's default 30 where unset), else
    None."""
    if conf is None or "2opt" not in str(conf.get("local_search", "none")):
        return None
    return int(conf.get("nn_k") or 30)


def served_numbers(answers: list[dict], lengths: Optional[Callable] = None,
                   conf: Optional[dict] = None) -> dict:
    """Numbers over served answers, each a dict with ``coords``,
    ``edge_weight_type``, ``tour``, ``best_len``, ``iterations`` and
    ``budget``; one marked ``partial`` (a solve the window's close cut
    short) is held to its tour and length only.  ``lengths(dist, tour)``
    replaces the program's reported length (the control puts the
    reference in the program's place).  Where the configuration ``conf``
    runs 2-opt, ``ls_gain_left`` is the largest relative gain one 2-opt
    move would still make on any answer's tour, the partial one
    included."""
    invalid = 0
    len_err = 0.0
    over_nn = 0.0
    short = 0
    k = two_opt_k(conf)
    gain = 0.0
    for a in answers:
        dist = reference.distances(a["coords"], a["edge_weight_type"])
        n = dist.shape[0]
        tour = np.asarray(a["tour"])
        if not reference.is_permutation(tour, n):
            invalid += 1
            len_err = math.inf
            over_nn = math.inf
            continue
        reported = (a["best_len"] if lengths is None
                    else lengths(dist, tour))
        exact = reference.tour_length(dist, tour)
        len_err = max(len_err, abs(float(reported) - exact))
        if k is not None:
            gain = max(gain, reference.two_opt_gain(dist, tour, k))
        if a.get("partial"):        # cut by the window's close
            continue
        over_nn = max(over_nn, exact / reference.nn_tour_length(dist))
        if a["iterations"] != a["budget"]:
            short += 1
    out = {"tour_invalid": float(invalid), "len_err": len_err,
           "best_over_nn": over_nn, "iters_short": float(short)}
    if k is not None:
        out["ls_gain_left"] = math.inf if invalid else gain
    return out


def compare(numbers: dict, limits: dict) -> tuple[bool, list[list]]:
    """``(correct, [[name, value, limit], ...])``; a number without a
    limit, or a limit without a number, is not correct."""
    rows = []
    ok = True
    for name in sorted(set(numbers) | set(limits)):
        v = numbers.get(name, math.inf)
        lim = limits.get(name)
        v = float(v) if v is not None else math.inf
        good = lim is not None and not math.isnan(v) and v <= float(lim)
        ok = ok and good
        rows.append([name, v, lim])
    return ok, rows


def report_lines(rows: list[list]) -> list[str]:
    return [f"check {name}: {value!r} (limit {limit!r})"
            for name, value, limit in rows]

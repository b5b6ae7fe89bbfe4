"""Driver for ``closed_loop`` traffic: back-to-back solves of one instance.

The configuration's instance is solved again and again, each solve a
request of ``iterations`` colony iterations with a seed of its own, through
the streaming service the configuration names.  One request sits in the
slot and the next one waits, so the chip never waits for a client.

Set-up builds the service, warms its chunk program ahead of time and runs
the first solve to its end and into the next, so the refill surgery and
the harvest have run before the window.  The window calls ``step`` until
``--seconds`` have passed; ``iters_per_s`` is the colony iterations
completed in the window over the window's length.  A traced run measures
the same window and captures its first ``trace_s`` (a long capture
overflows the device's trace buffers), so it harvests and checks as many
solves as an untraced one; the colony scopes of the captured operations
are read from it once the window has closed.

Before every step the pheromone matrix is copied (one device copy), so the
window's last chunk that was not a refill can be checked against the
reference once the window has closed.  The check follows the
configuration's ``variant``: Ant System's deposit (every ant, q / L each)
for ``as``, the MAX-MIN Ant System's one deposited tour and clamp for
``mmas``; any other variant is refused, never passed in silence.  Where
the configuration runs 2-opt, every answer's tour is also held to a 2-opt
local optimum (``check.served_numbers``).
"""
from __future__ import annotations

import numpy as np

from . import cells, check, generator, phases, reference, session
from .result import RunRecord


def run(cell: cells.Cell, seed: int, seconds: float, trace: bool, devs,
        t_start: float, evaluate=None) -> RunRecord:
    import jax.numpy as jnp
    from repro.solver import programs as programs_mod
    from repro.solver import streaming

    conf, traffic = cell.config, cell.traffic
    pheromone_check(conf)           # refuse an unchecked variant up front
    svc_conf = conf["service"]
    chunk = int(svc_conf["chunk"])
    budget = int(traffic["iterations"])
    cfg = cells.aco_config(conf, iterations=budget)
    req = generator.single_instance(conf, seed)
    ewt = conf["instance"]["edge_weight_type"]
    inst = cells.instance(req.coords, ewt, f"{cell.name}-{seed}")
    bucket = generator.bucket_of(req.n, int(svc_conf["min_bucket"]))
    solver_seeds = generator.rng_for(seed, 3, 1)

    progs = programs_mod.ProgramCache()
    svc = streaming.StreamingSolverService(
        cfg, max_batch=int(svc_conf["max_batch"]),
        min_bucket=int(svc_conf["min_bucket"]), chunk=chunk,
        programs=progs)
    svc.warm_programs(req.n, req.n, ladder=[bucket])

    def top_up():
        while svc.waiting + svc.resident < 2:
            svc.submit(inst, iterations=budget, seed=int(
                solver_seeds.integers(generator.SEED_MOD)))

    results = []
    top_up()
    # A solve takes ceil(budget / chunk) steps; a program that never
    # finishes one still leaves set-up, and the check then fails it.
    for _ in range(2 * int(traffic["warm_solves"]) * -(-budget // chunk)):
        if len(results) >= int(traffic["warm_solves"]):
            break
        results += svc.step()
        top_up()
    svc.step()
    top_up()
    # The service keeps its pools private; the benchmark reads the one
    # resident pool's state to count iterations and check the deposit.
    pool = svc._pools[bucket][0]

    # the window's per-step snapshot copies, compiled in set-up
    jnp.copy(pool.states.tau).block_until_ready()
    jnp.copy(pool.states.best_len).block_until_ready()

    def position():
        r = pool.requests[0]
        return (None if r is None else r.request_id,
                int(np.asarray(pool.states.iteration)[0]))

    counter = session.CompileCounter()
    capture = session.Capture() if trace else None
    trace_s = min(seconds, float(traffic["trace_s"]))
    if capture is not None:
        capture.start()
    traced = session.span("bench.traced")
    traced_its = None   # iterations completed in the traced part
    summary = data = None
    steps = iterations = 0
    plain = None        # (tau before, iterations) of the last plain step
    pair = None         # (tau before, tau after, iterations, best after)
    harvested = []
    pos0 = position()
    traced.__enter__()
    t0 = session.now()
    with counter.counting():
        while True:
            with session.span("bench.snapshot"):
                before = (jnp.copy(pool.states.tau),
                          jnp.copy(pool.states.best_len))
            if plain is not None:
                pair = (plain[0], before[0], plain[1], before[1])
            with session.span("bench.step", resident=1):
                harvested += svc.step()
            with session.span("bench.submit"):
                top_up()
            pos1 = position()
            steps += 1
            plain = None
            if pos0[0] is None:              # admitted, then stepped
                iterations += pos1[1]
            elif pos1[0] is None:            # ran to its budget, harvested
                iterations += budget - pos0[1]
            else:
                iterations += pos1[1] - pos0[1]
                plain = (before[0], pos1[1] - pos0[1])
            pos0 = pos1
            if traced_its is None and session.now() - t0 >= trace_s:
                traced.__exit__(None, None, None)
                traced_its = iterations
                if capture is not None:
                    from . import xplane
                    data = capture.stop()
                    summary = xplane.reduce(xplane.extract(data))
                    capture.cleanup()
            if session.now() - t0 >= seconds:
                break
    t1 = session.now()

    rec = RunRecord(device=session.device_record(devs))
    rec.setup_s = t0 - t_start
    rec.window_s = t1 - t0
    rec.e2e["iters_per_s"] = iterations / (t1 - t0)
    rec.memory_peak_bytes = session.memory_peak_bytes(devs)
    rec.layer_ctx = {"summary": summary, "iterations": traced_its,
                     "window_compiles": counter.count, "chips": len(devs),
                     **phases.layer_ctx(data, [progs])}
    counter.close()

    # ---- the timed path's answers; then the program's state is freed
    if plain is not None:
        pair = (plain[0], pool.states.tau, plain[1], pool.states.best_len)
    deposit = {"tau_before": None}
    if pair is not None:
        deposit = {"tau_before": np.asarray(pair[0])[0],
                   "tau_after": np.asarray(pair[1])[0], "chunk": pair[2],
                   "best_len": float(np.asarray(pair[3])[0])}
    best_len = float(np.asarray(pool.states.best_len)[0])
    best_tour = np.asarray(pool.states.best_tour)[0][:req.n]
    resident_its = int(np.asarray(pool.states.iteration)[0])
    del before, plain, pair, pool, svc, progs
    answers = [{"coords": req.coords, "edge_weight_type": ewt,
                "tour": r.best_tour, "best_len": r.best_len,
                "iterations": r.iterations, "budget": budget}
               for r in harvested]
    # the resident solve, cut by the window's close: its best so far
    answers.append({"coords": req.coords, "edge_weight_type": ewt,
                    "tour": best_tour, "best_len": best_len,
                    "iterations": resident_its, "budget": budget,
                    "partial": True})
    # every answer checked, the resident solve's included
    rec.attempted = len(answers)
    rec.numbers = (evaluate or numbers)(answers, deposit, conf)
    return rec


def numbers(answers: list[dict], deposit: dict, conf: dict,
            lengths=None) -> dict:
    """The cell's numbers: every solve's best tour and reported length
    (with the 2-opt gain left where the configuration runs 2-opt), and
    the pheromone check that the configuration's ``variant`` calls for,
    on the window's last chunk that was not a refill."""
    names, pheromone_numbers = pheromone_check(conf)
    out = check.served_numbers(answers, lengths, conf)
    if deposit["tau_before"] is None:
        out.update(dict.fromkeys(names, float("inf")))
    else:
        out.update(pheromone_numbers(deposit, conf))
    return out


def _as_numbers(deposit: dict, conf: dict) -> dict:
    """Ant System: every ant deposits (``reference.deposit_numbers``)."""
    m = int(conf["m"]) if conf.get("m") else int(conf["n"])
    return reference.deposit_numbers(
        deposit["tau_before"], deposit["tau_after"], int(conf["n"]), m,
        int(deposit["chunk"]), float(conf["rho"]),
        float(conf.get("q", 1.0)), deposit["best_len"])


def _mmas_numbers(deposit: dict, conf: dict) -> dict:
    """MAX-MIN Ant System: one tour deposits, then the clamp
    (``reference.mmas_numbers``)."""
    return reference.mmas_numbers(
        deposit["tau_before"], deposit["tau_after"], int(conf["n"]),
        int(deposit["chunk"]), float(conf["rho"]),
        float(conf.get("q", 1.0)), deposit["best_len"])


# variant -> (the numbers its pheromone check reads, the check)
PHEROMONE_CHECKS = {
    "as": (("dep_asym", "dep_rowsum_spread", "dep_weight_over",
            "dep_weight_under"), _as_numbers),
    "mmas": (("dep_asym", "mmas_bound_err", "mmas_rows_over",
              "mmas_rows_under"), _mmas_numbers),
}


def pheromone_check(conf: dict):
    """The configuration's variant's pheromone check; ValueError for a
    variant that has none."""
    if conf["variant"] not in PHEROMONE_CHECKS:
        raise ValueError(f"no pheromone check for variant "
                         f"{conf['variant']!r}; known: "
                         f"{sorted(PHEROMONE_CHECKS)}")
    return PHEROMONE_CHECKS[conf["variant"]]

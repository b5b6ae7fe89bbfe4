"""AOT program cache tests (solver/programs.py, DESIGN.md §16).

The load-bearing claims: (1) a warmed signature dispatches the AOT
executable and the result is bitwise the jit path's; (2) neighbour-bucket
routing — padding an unwarmed native bucket into the nearest larger warmed
one — is bitwise exact for counter-mode configs across AS/MMAS/ACS,
quantised and sparse routes, and is *refused* for any config whose
numerics depend on the bucket width; (3) the persistent XLA cache and the
hit/miss/warmup counters are actually wired.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import aco, tsp
from repro.kernels.ops import UnsupportedKernelRoute
from repro.solver import batch as batch_mod
from repro.solver import engine, programs, service, streaming

# The AOT warm/dispatch tests compile dozens of distinct engine programs.
# Run in the long-lived suite process, that much extra JIT code has
# destabilised *later, unrelated* XLA CPU compiles (deterministic
# segfault in test_system's construct_tours compile — reproduced 3/3
# with these tests in-process, 0/2 without).  So the compile-heavy tests
# are marked `_HEAVY` and executed in their own interpreter by
# test_aot_service_suite_isolated below (the test_distributed.py
# subprocess idiom); set REPRO_PROGRAMS_HEAVY=1 to run them directly.
_HEAVY = os.environ.get("REPRO_PROGRAMS_HEAVY") == "1"
heavy = pytest.mark.skipif(
    not _HEAVY, reason="runs via test_aot_service_suite_isolated")


def _counter_cfg(**kw):
    """Neighbour-routable base config: pinned ants + width-invariant
    counter draws, no local search."""
    base = dict(iterations=4, m=4, draw_mode="counter",
                local_search="none", seed=0)
    base.update(kw)
    return aco.ACOConfig(**base)


# Keep every ProgramCache (and so every AOT LoadedExecutable) alive for
# the whole process — a service holds its cache until exit, and tests
# should exercise that lifetime, not a create-and-GC churn production
# never does.
_LIVE_CACHES: list = []


def _cache(**kw) -> programs.ProgramCache:
    pc = programs.ProgramCache(**kw)
    _LIVE_CACHES.append(pc)
    return pc


# ------------------------------------------------------------ bucket ladder
def test_bucket_ladder_enumeration():
    assert batch_mod.bucket_ladder(10, 100) == [16, 32, 64, 128]
    assert batch_mod.bucket_ladder(20, 20) == [32]
    assert batch_mod.bucket_ladder(3, 17, min_bucket=4) == [4, 8, 16, 32]
    with pytest.raises(ValueError):
        batch_mod.bucket_ladder(10, 9)


def test_bucket_ladder_covers_bucket_size():
    """Every instance size in range lands in a ladder rung."""
    ladder = batch_mod.bucket_ladder(5, 70)
    for n in range(5, 71):
        assert batch_mod.bucket_size(n) in ladder


# ------------------------------------------------------ keying / canonical
def test_effective_max_iters_canonicalisation():
    pc = programs.ProgramCache(iters_cap=8)
    assert pc.effective_max_iters(3) == 8    # shared warmed loop bound
    assert pc.effective_max_iters(8) == 8
    assert pc.effective_max_iters(9) == 9    # over the cap: exact budget
    assert programs.ProgramCache().effective_max_iters(5) == 5


def test_signature_reads_operand_shapes():
    cfg = _counter_cfg()
    insts = [tsp.circle_instance(10, seed=0)] * 2
    b = batch_mod.make_batch(insts, 16, cfg.nn_k)
    states = engine.init_states(insts, cfg, [0, 1], 16)
    budgets = jnp.zeros((2,), jnp.int32)
    key = programs.ProgramCache.signature(
        b.problem, states, budgets, cfg, 4, 0, False, "dense", "EUC_2D")
    assert key.n_pad == 16 and key.batch == 2
    assert key.cfg == cfg and not key.hyper
    assert key.mesh == programs.MESH_NONE


def test_mesh_label():
    assert programs.mesh_label(None) == "-"


# --------------------------------------------------------- rejection matrix
@pytest.mark.parametrize("cfg,why", [
    (aco.ACOConfig(), "cfg.m"),                               # m follows n_pad
    (_counter_cfg(draw_mode="packed"), "draw_mode"),
    (_counter_cfg(local_search="2opt"), "local search"),
    (_counter_cfg(construction="nn_list"), "nn_list"),
    (_counter_cfg(sparse=True, sparse_k=8, construction="partial"),
     "Partial-ACO"),
    (_counter_cfg(tau_dtype="int8", tau_round="stochastic"), "tau_round"),
])
def test_neighbour_route_rejections(cfg, why):
    with pytest.raises(UnsupportedKernelRoute, match=why):
        programs.check_neighbour_route(cfg)
    assert not programs.neighbour_supported(cfg)


@pytest.mark.parametrize("cfg", [
    _counter_cfg(),
    _counter_cfg(variant="acs"),
    _counter_cfg(tau_dtype="int8", tau_round="nearest"),
    _counter_cfg(sparse=True, sparse_k=8),
])
def test_neighbour_route_accepted(cfg):
    programs.check_neighbour_route(cfg)     # must not raise
    assert programs.neighbour_supported(cfg)


def test_route_bucket_policy():
    pc = _cache()
    pc._warmed_buckets[("dense", "-")] = {32, 64}
    ok = _counter_cfg()
    bad = aco.ACOConfig()                    # m=None: not width-invariant
    assert pc.route_bucket(32, ok) == 32     # native warmed: stay
    assert pc.route_bucket(16, ok) == 32     # nearest larger warmed
    assert pc.route_bucket(16, bad) == 16    # unsupported cfg: never route
    assert pc.route_bucket(128, ok) == 128   # nothing larger: native


# ---------------------------------------------------- warm / AOT dispatch
@heavy
def test_warm_hit_is_bitwise_jit_path():
    """A warmed drain service must return bitwise what the plain service
    returns, with every job an AOT hit and zero misses."""
    cfg = aco.ACOConfig(iterations=4, variant="mmas", seed=0)
    insts = [tsp.random_instance(10, seed=1), tsp.circle_instance(12, seed=2),
             tsp.random_instance(14, seed=3)]

    plain = service.SolverService(cfg, max_batch=2)
    for k, inst in enumerate(insts):
        plain.submit(inst, seed=50 + k)
    want = plain.run()

    pc = _cache()
    svc = service.SolverService(cfg, max_batch=2, programs=pc)
    summary = svc.warm_programs(10, 14)
    assert set(summary["buckets"]) == {"16"} and not summary["errors"]
    for k, inst in enumerate(insts):
        svc.submit(inst, seed=50 + k)
    got = svc.run()

    st = svc.stats["programs"]
    assert st["hits"] == 2 and st["misses"] == 0       # 2 jobs of max_batch=2
    assert st["warmup_programs"] == 1 and st["warmup_compile_s"] > 0
    assert pc.warmed_buckets("dense") == (16,)
    for a, b in zip(want, got):
        assert a.best_len == b.best_len
        np.testing.assert_array_equal(a.best_tour, b.best_tour)


@heavy
def test_drain_phantom_padding_is_exact():
    """One real request padded with budget-0 phantom slots to max_batch
    must surface exactly the solo result, and only that result."""
    cfg = aco.ACOConfig(iterations=4, seed=0)
    inst = tsp.random_instance(11, seed=7)

    plain = service.SolverService(cfg, max_batch=4)
    plain.submit(inst, seed=9)
    want = plain.run()

    pc = _cache()
    svc = service.SolverService(cfg, max_batch=4, programs=pc)
    svc.warm_programs(11, 11)
    svc.submit(inst, seed=9)
    got = svc.run()

    assert len(got) == len(want) == 1
    assert svc.stats["programs"]["hits"] == 1
    assert got[0].best_len == want[0].best_len
    np.testing.assert_array_equal(got[0].best_tour, want[0].best_tour)
    assert tsp.is_valid_tour(got[0].best_tour)


@heavy
def test_background_warm_and_miss_fallback():
    """Before a background warm lands, calls miss and take the jit path;
    wait() joins the thread and subsequent calls hit."""
    cfg = aco.ACOConfig(iterations=3, seed=0)
    inst = tsp.random_instance(10, seed=4)

    pc = _cache()
    svc = service.SolverService(cfg, max_batch=2, programs=pc)
    t = svc.warm_programs(10, 10, background=True)
    assert t is not None
    pc.wait()
    assert pc.warmed_buckets("dense") == (16,)

    svc.submit(inst, seed=3)
    got = svc.run()
    assert svc.stats["programs"]["hits"] == 1
    assert svc.stats["programs"]["misses"] == 0

    # An unwarmed signature (different bucket) misses but still solves.
    svc.submit(tsp.random_instance(20, seed=5), seed=6)
    got2 = svc.run()
    st = svc.stats["programs"]
    assert st["misses"] == 1
    assert st["missed_signatures"][0]["bucket"] == 32
    assert np.isfinite(got[0].best_len) and np.isfinite(got2[0].best_len)


# ------------------------------------------------- neighbour-bucket routing
@pytest.mark.parametrize("variant", ["as", "mmas", "acs"])
@heavy
def test_neighbour_bucket_bitwise_exact_variants(variant):
    """n=12 (native bucket 16) routed into a warmed-only bucket 32 must be
    bitwise the native-bucket run, for every pheromone variant."""
    cfg = _counter_cfg(variant=variant, iterations=5)
    inst = tsp.random_instance(12, seed=31)

    plain = service.SolverService(cfg, max_batch=2)
    plain.submit(inst, seed=8)
    want = plain.run()

    pc = _cache()
    svc = service.SolverService(cfg, max_batch=2, programs=pc)
    svc.warm_programs(20, 20)                 # ladder = [32] only
    assert pc.warmed_buckets("dense") == (32,)
    assert svc._route_bucket(inst.n) == 32    # 16 is cold -> neighbour
    svc.submit(inst, seed=8)
    got = svc.run()

    assert svc.stats["programs"]["hits"] == 1
    assert svc.stats["programs"]["misses"] == 0
    assert got[0].best_len == want[0].best_len
    np.testing.assert_array_equal(got[0].best_tour, want[0].best_tour)


@heavy
def test_neighbour_bucket_bitwise_exact_quantised():
    cfg = _counter_cfg(variant="mmas", iterations=4,
                       tau_dtype="int8", tau_round="nearest")
    inst = tsp.random_instance(12, seed=13)

    plain = service.SolverService(cfg, max_batch=2)
    plain.submit(inst, seed=2)
    want = plain.run()

    pc = _cache()
    svc = service.SolverService(cfg, max_batch=2, programs=pc)
    svc.warm_programs(20, 20)
    svc.submit(inst, seed=2)
    got = svc.run()
    assert svc.stats["programs"]["hits"] == 1
    assert got[0].best_len == want[0].best_len
    np.testing.assert_array_equal(got[0].best_tour, want[0].best_tour)


@heavy
def test_neighbour_bucket_bitwise_exact_sparse():
    cfg = _counter_cfg(variant="mmas", iterations=4, sparse=True,
                       sparse_k=8)
    inst = tsp.random_instance(12, seed=17)

    plain = service.SolverService(cfg, max_batch=2)
    plain.submit(inst, seed=5)
    want = plain.run()

    pc = _cache()
    svc = service.SolverService(cfg, max_batch=2, programs=pc)
    svc.warm_programs(20, 20)
    assert pc.warmed_buckets("sparse") == (32,)
    svc.submit(inst, seed=5)
    got = svc.run()
    assert svc.stats["programs"]["hits"] == 1
    assert got[0].best_len == want[0].best_len
    np.testing.assert_array_equal(got[0].best_tour, want[0].best_tour)


@heavy
def test_packed_draw_mode_never_neighbour_routes():
    """The default packed draws are width-dependent: an attached cache
    must keep the native bucket (compile-on-demand) rather than route."""
    cfg = aco.ACOConfig(iterations=3, seed=0)      # packed, m=None
    pc = _cache()
    svc = service.SolverService(cfg, max_batch=2, programs=pc)
    svc.warm_programs(20, 20)                      # warmed: {32}
    assert svc._route_bucket(12) == 16             # refused, stays native


# ----------------------------------------------------------- streaming svc
@heavy
def test_streaming_warmed_hits_and_bucket_stamp():
    """Streaming: warmed chunks dispatch AOT (hits, zero misses), results
    bitwise the plain pool's; the request bucket is stamped at submit."""
    cfg = aco.ACOConfig(iterations=4, seed=0, selection="gumbel")
    insts = [tsp.random_instance(10, seed=1), tsp.circle_instance(12, seed=2)]

    plain = streaming.StreamingSolverService(cfg, max_batch=2, chunk=2)
    for k, inst in enumerate(insts):
        plain.submit(inst, iterations=4, seed=40 + k)
    want = {r.request_id: r for r in plain.run_until_drained()}

    pc = _cache()
    svc = streaming.StreamingSolverService(cfg, max_batch=2, chunk=2,
                                           programs=pc)
    svc.warm_programs(10, 12)
    for k, inst in enumerate(insts):
        svc.submit(inst, iterations=4, seed=40 + k)
    got = {r.request_id: r for r in svc.run_until_drained()}

    st = svc.stats["programs"]
    assert st["hits"] > 0 and st["misses"] == 0
    for k in want:
        assert got[k].best_len == want[k].best_len
        np.testing.assert_array_equal(got[k].best_tour, want[k].best_tour)


@heavy
def test_streaming_neighbour_route_stamped_at_submit():
    """A neighbour-routed streaming request records its routed bucket on
    the request at submit time and solves bitwise-identically."""
    cfg = _counter_cfg(iterations=4)
    inst = tsp.random_instance(12, seed=23)

    plain = streaming.StreamingSolverService(cfg, max_batch=2, chunk=2)
    plain.submit(inst, iterations=4, seed=6)
    want = plain.run_until_drained()

    pc = _cache()
    svc = streaming.StreamingSolverService(cfg, max_batch=2, chunk=2,
                                           programs=pc)
    svc.warm_programs(20, 20)                 # warmed: {32}
    svc.submit(inst, iterations=4, seed=6)
    assert svc._waiting[0].bucket == 32       # stamped once, at submit
    got = svc.run_until_drained()

    assert svc.stats["programs"]["hits"] > 0
    assert got[0].best_len == want[0].best_len
    np.testing.assert_array_equal(got[0].best_tour, want[0].best_tour)


# ---------------------------------------------------- counter-mode draws
@heavy
def test_counter_draw_mode_is_width_invariant():
    """The exactness basis itself: the same instance solved at n_pad 16
    and 32 under counter draws yields bitwise the same trajectory."""
    cfg = _counter_cfg(iterations=3)
    inst = tsp.random_instance(10, seed=11)
    outs = []
    for n_pad in (16, 32):
        st, _ = engine.solve_instances([inst], cfg, iterations=[3],
                                       seeds=[9], n_pad=n_pad)
        outs.append((float(np.asarray(st.best_len)[0]),
                     np.asarray(st.best_tour)[0][:inst.n]))
    assert outs[0][0] == outs[1][0]
    np.testing.assert_array_equal(outs[0][1], outs[1][1])


@heavy
def test_packed_draw_mode_is_width_dependent():
    """Sanity check that the gate is load-bearing: packed draws really do
    change with the padded width (if this ever starts passing, the
    rejection matrix can be relaxed)."""
    cfg = aco.ACOConfig(iterations=3, m=4, seed=0)   # packed
    inst = tsp.random_instance(10, seed=11)
    diverged = False
    for seed in range(6):        # any one divergence proves dependence
        tours = []
        for n_pad in (16, 32):
            st, _ = engine.solve_instances([inst], cfg, iterations=[3],
                                           seeds=[seed], n_pad=n_pad)
            tours.append(np.asarray(st.best_tour)[0][:inst.n])
        if not np.array_equal(tours[0], tours[1]):
            diverged = True
            break
    assert diverged


# ------------------------------------------------------- persistent cache
def test_persistent_cache_config_roundtrip(tmp_path):
    """enable_persistent_cache points JAX at the directory and zeroes the
    size/time admission gates (restored afterwards — process-global)."""
    old_dir = jax.config.jax_compilation_cache_dir
    old_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    old_bytes = jax.config.jax_persistent_cache_min_entry_size_bytes
    d = str(tmp_path / "xla")
    try:
        got = programs.enable_persistent_cache(d)
        assert got == os.path.abspath(d) and os.path.isdir(d)
        assert jax.config.jax_compilation_cache_dir == got
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_secs)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          old_bytes)


def test_persistent_cache_populates_and_reuses(tmp_path):
    """The executable cache must be populated by a fresh process that
    enables it before its first compile, and a second process over the
    same directory must reuse it (entry count stable, not re-written).
    Subprocesses because the persistent-cache singleton binds at the
    process's first compile — exactly the serve-time usage."""
    import subprocess
    import sys
    d = str(tmp_path / "xla")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = (
        "import sys, jax, jax.numpy as jnp\n"
        "from repro.solver import programs\n"
        "programs.enable_persistent_cache(sys.argv[1])\n"
        "jax.jit(lambda x: jnp.cumsum(x * 3.0) + 1.0)"
        "(jnp.arange(64, dtype=jnp.float32)).block_until_ready()\n"
        "print(programs.persistent_cache_stats(sys.argv[1])['files'])\n")
    env = dict(os.environ, PYTHONPATH=src)
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", code, d],
                             capture_output=True, text=True, env=env,
                             timeout=300)
        assert out.returncode == 0, out.stderr
        runs.append(int(out.stdout.strip().splitlines()[-1]))
    assert runs[0] > 0                 # first run wrote executables
    assert runs[1] == runs[0]          # second run loaded, didn't re-write


def test_compile_cache_dir_env_wins(monkeypatch, capsys):
    """JAX_COMPILATION_CACHE_DIR, when set, is the directory (a requested
    one is ignored with a note); otherwise the requested directory, else
    the fixed checkout path."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    assert programs.compile_cache_dir() == "/placed/outside"
    assert programs.compile_cache_dir("elsewhere") == "/placed/outside"
    assert "ignoring cache dir elsewhere" in capsys.readouterr().err
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert programs.compile_cache_dir() == os.path.join(root, ".jax_cache")
    assert programs.compile_cache_dir("elsewhere") == "elsewhere"


def test_solve_serve_writes_cache_only_to_env_dir(tmp_path):
    """A solve_serve run under JAX_COMPILATION_CACHE_DIR writes its
    compiled executables there, and a requested --cache-dir stays empty."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    placed, requested = tmp_path / "placed", tmp_path / "requested"
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_COMPILATION_CACHE_DIR=str(placed))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.solve_serve", "--warmup",
         "--dry", "--min-n", "12", "--max-n", "12", "--iterations", "2",
         "--cache-dir", str(requested)],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)
    assert out.returncode == 0, out.stderr
    assert f"ignoring cache dir {requested}" in out.stderr
    assert programs.persistent_cache_stats(str(placed))["files"] > 0
    assert not requested.exists()


def test_foreground_warmup_error_exits_nonzero():
    """A bucket that fails to compile makes a foreground --warmup exit 1
    and names the bucket on stderr, instead of serving it cold."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.solve_serve", "--warmup",
         "--dry", "--bucket-ladder", "0", "--min-n", "12", "--max-n", "12",
         "--iterations", "2"],
        capture_output=True, text=True, env=env, cwd=root, timeout=300)
    assert out.returncode == 1
    assert "solve_serve: warmup error b0" in out.stderr


def test_persistent_cache_stats_missing_dir():
    st = programs.persistent_cache_stats("/nonexistent/xla-cache")
    assert st["files"] == 0 and st["bytes"] == 0


# --------------------------------------------------- subprocess harness
@pytest.mark.skipif(_HEAVY, reason="already inside the harness")
def test_aot_service_suite_isolated():
    """Run every @heavy test in a fresh interpreter (see the _HEAVY note
    at the top of this file).  One subprocess amortises the import cost
    across all of them; -p no:cacheprovider keeps the child from
    touching the parent's .pytest_cache."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, REPRO_PROGRAMS_HEAVY="1")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         os.path.abspath(__file__)],
        capture_output=True, text=True, env=env, cwd=root, timeout=1200)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    tail = out.stdout.strip().splitlines()[-1]
    assert " passed" in tail and "failed" not in tail, tail

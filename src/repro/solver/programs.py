"""Ahead-of-time program cache: kill first-request compile latency.

Every (bucket, batch, config, kind, ewt, hyper-mode, donation, mesh) tuple
the solver fabric touches is a distinct XLA program, and the first request
that needs one pays the full compile on the serving critical path — the
cold-start problem ROADMAP names (aphrodite pre-captures CUDA graphs at
``_BATCH_SIZES_TO_CAPTURE`` for exactly this reason).  This module closes
it on three layers (DESIGN.md §16):

1. **Persistent compilation cache** — ``enable_persistent_cache`` points
   JAX's executable cache at the directory ``compile_cache_dir`` picks
   (``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``),
   so compiled programs survive process restarts: the second cold start of
   the same service pays a cache *load*, not a compile.
2. **Warmup ladder** — ``ProgramCache.warm`` AOT-lowers-and-compiles the
   engine program for every bucket of ``batch.bucket_ladder`` before the
   service accepts traffic (optionally on a background thread), holding
   the compiled executables for direct dispatch.  ``engine.run_batch``
   routes through ``ProgramCache.call``: a warmed signature dispatches the
   AOT executable (``jit_cache_hit``), any other signature takes the
   ordinary jit path (``jit_cache_miss``) and compiles on demand exactly
   as before.  A warmed executable that refuses its operands raises.
3. **Neighbour-bucket routing** — ``route_bucket`` pads a request whose
   native bucket is *not* warmed into the nearest larger warmed bucket
   instead of blocking the stream on a compile.  Exactness contract: the
   neighbour route is bitwise identical to the native route, which holds
   only under width-invariant randomness — ``check_neighbour_route``
   gates it on ``cfg.draw_mode == "counter"`` (core/sampling.py), a
   pinned ant count ``cfg.m``, no local search (NN candidate width is
   bucket-dependent), non-candidate-list construction, and nearest
   rounding for quantised tau (stochastic rounding draws over the full
   (n_pad, n_pad) matrix).  Tested across AS/MMAS/ACS, quantised and
   sparse routes in tests/test_programs.py.
"""
from __future__ import annotations

import os
import sys
import threading
import time
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import aco

Array = jax.Array

MESH_NONE = "-"


def mesh_label(mesh=None) -> str:
    """Stable cache-key label for a topology: "-" for single-device,
    else the mesh's axis:size pairs (per-mesh cache keys, DESIGN.md §16)."""
    if mesh is None:
        return MESH_NONE
    return ",".join(f"{k}:{v}" for k, v in mesh.shape.items())


class ProgramKey(NamedTuple):
    """Full static signature of one compiled ``engine._run_batch_impl``.

    Everything that forces a recompile is in here: the padded bucket and
    batch width (operand shapes), the frozen ``ACOConfig`` (every static
    knob: strategy/variant/selection/draw_mode, tau_dtype/round/
    compensation, sparse geometry, metrics, ...), the loop statics, the
    donation mode, dense/sparse kind + TSPLIB rounding rule, whether the
    problem carries per-instance Hyper operands, and the mesh topology.
    """
    n_pad: int
    batch: int
    cfg: aco.ACOConfig
    max_iters: int
    patience: int
    donate: bool
    kind: str          # "dense" | "sparse"
    ewt: str
    hyper: bool
    mesh: str          # mesh_label()


# ------------------------------------------------- persistent XLA cache

# A fixed path: the cache key holds the directory, so a path that moves
# between runs (temp, pid, time) never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def compile_cache_dir(requested: Optional[str] = None) -> str:
    """The persistent compilation cache's directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is placed from outside and
    wins: ``requested`` is then ignored, with a one-line note on stderr.
    Otherwise ``requested``, else the fixed ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        if requested:
            print(f"programs: JAX_COMPILATION_CACHE_DIR={env} is set; "
                  f"ignoring cache dir {requested}", file=sys.stderr)
        return env
    return requested or DEFAULT_CACHE_DIR


def enable_persistent_cache(cache_dir: str) -> str:
    """Point JAX's persistent compilation cache at ``cache_dir``.

    Thresholds are zeroed so *every* executable is cached (the default
    min-compile-time gate would skip the small-bucket programs that
    dominate high-QPS traffic).  Process-global; call before the first
    compile.  Executables are keyed by HLO + compile options + jax/XLA
    version, so a stale directory is never wrong, only useless.
    """
    cache_dir = os.path.abspath(cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def persistent_cache_stats(cache_dir: str) -> dict:
    """Entry count + byte total of a persistent cache directory."""
    files = 0
    size = 0
    if os.path.isdir(cache_dir):
        for name in os.listdir(cache_dir):
            p = os.path.join(cache_dir, name)
            if os.path.isfile(p):
                files += 1
                size += os.path.getsize(p)
    return {"dir": cache_dir, "files": files, "bytes": size}


# --------------------------------------------- neighbour-route support

def check_neighbour_route(cfg: aco.ACOConfig) -> None:
    """Raise ``UnsupportedKernelRoute`` unless neighbour-bucket routing is
    bitwise-exact for this config (the route checker idiom, DESIGN.md §10).

    The padding invariants (phantom cities at inf distance, masked
    lengths/deposits) make the *deterministic* numerics width-invariant;
    the conditions here close the *stochastic* side.
    """
    from repro.kernels.ops import UnsupportedKernelRoute

    def reject(reason: str) -> None:
        raise UnsupportedKernelRoute(
            f"neighbour-bucket routing needs bucket-width-invariant "
            f"numerics: {reason}")

    if cfg.m is None:
        reject("cfg.m is None, so the ant count follows the padded bucket "
               "width (m = n_pad); pin cfg.m")
    if cfg.draw_mode != "counter":
        reject(f"draw_mode {cfg.draw_mode!r} derives per-(ant, city) "
               "randomness from flat array counters; use "
               "draw_mode='counter'")
    if cfg.local_search != "none":
        reject(f"local search {cfg.local_search!r} scans NN candidate "
               "lists of width min(nn_k, n_pad - 1), which varies per "
               "bucket")
    if cfg.sparse:
        if cfg.construction == "partial":
            reject("Partial-ACO windows are unpadded-only (masked "
                   "instances are rejected upstream)")
    elif cfg.construction in ("nn_list", "nn_list_eager"):
        reject("nn_list construction selects over candidate lists of "
               "width min(nn_k, n_pad - 1), which varies per bucket")
    from repro.core import quant
    if quant.is_quantised(cfg.tau_dtype) and cfg.tau_round != "nearest":
        reject(f"tau_round {cfg.tau_round!r} draws rounding bits over the "
               "full (n_pad, n_pad) matrix; use tau_round='nearest'")


def neighbour_supported(cfg: aco.ACOConfig) -> bool:
    from repro.kernels.ops import UnsupportedKernelRoute
    try:
        check_neighbour_route(cfg)
        return True
    except UnsupportedKernelRoute:
        return False


# ------------------------------------------------------- program cache

class ProgramCache:
    """AOT-compiled engine programs keyed by their full static signature.

    One cache serves one service (drain or streaming): ``warm`` fills it
    over a bucket ladder, ``call`` is the hot path ``engine.run_batch``
    routes through, ``route_bucket`` is the admission-time neighbour
    lookup.  Thread-safe: the warmup may run on a background thread while
    the service admits traffic (misses take the jit path, so a
    half-warmed ladder is never wrong, only slower).

    ``iters_cap``: warmed programs are compiled with this ``max_iters``
    loop bound; ``effective_max_iters`` canonicalises a drain job's
    max(budgets) up to the cap so jobs of different budget mixes share one
    program.  Sound because the while_loop exits on the per-instance done
    masks — a larger static bound never changes the trajectory.
    """

    def __init__(self, telemetry=None, iters_cap: Optional[int] = None):
        from repro import obs
        self.tel = telemetry if telemetry is not None else obs.Telemetry()
        self.iters_cap = iters_cap
        self._lock = threading.Lock()
        self._programs: dict[ProgramKey, object] = {}
        self._warmed_buckets: dict[tuple[str, str], set[int]] = {}
        self._missed_keys: list[tuple] = []     # first-sight ring, bounded
        self._warm_thread: Optional[threading.Thread] = None
        self._warm_errors: list[str] = []
        self._c_hit = self.tel.registry.counter("jit_cache_hit")
        self._c_miss = self.tel.registry.counter("jit_cache_miss")
        self._c_warm_s = self.tel.registry.counter("warmup_compile_s")
        self._c_warm_programs = self.tel.registry.counter("warmup_programs")

    # ---------------------------------------------------------- key/sig
    @staticmethod
    def signature(problem, states, budgets, cfg: aco.ACOConfig,
                  max_iters: int, patience: int, donate: bool,
                  kind: str, ewt: str, mesh: str = MESH_NONE) -> ProgramKey:
        """ProgramKey of one ``run_batch`` call, read off its operands."""
        return ProgramKey(
            n_pad=int(states.best_tour.shape[-1]),
            batch=int(budgets.shape[0]),
            cfg=cfg, max_iters=int(max_iters), patience=int(patience),
            donate=bool(donate), kind=kind, ewt=ewt,
            hyper=getattr(problem, "hyper", None) is not None,
            mesh=mesh)

    def effective_max_iters(self, want: int) -> int:
        """Canonical loop bound: the warm-time cap whenever it covers the
        requested budget (one shared program), the exact budget otherwise
        (a miss, but correct)."""
        if self.iters_cap is not None and want <= self.iters_cap:
            return self.iters_cap
        return want

    # ----------------------------------------------------------- warmup
    def _templates(self, bucket: int, batch: int, cfg: aco.ACOConfig,
                   kind: str, hyper: bool):
        """Concrete template operands with exactly the production pytree
        structure — built through the same factories the services use
        (batch.make_batch / engine.init_states), so the AOT-lowered
        signature cannot drift from the live one."""
        from repro.core import tsp
        from . import batch as batch_mod
        from . import engine
        insts = [tsp.circle_instance(bucket, seed=0)] * batch
        seeds = list(range(batch))
        if kind == "sparse":
            b = batch_mod.make_sparse_batch(insts, cfg.sparse_k, bucket)
            states = engine.init_sparse_states(insts, cfg, seeds, bucket)
            ewt = b.ewt
        else:
            hypers = [aco.Hyper.make(cfg)] * batch if hyper else None
            b = batch_mod.make_batch(insts, bucket, cfg.nn_k, hypers=hypers)
            states = engine.init_states(insts, cfg, seeds, bucket, hypers)
            ewt = "EUC_2D"
        budgets = jnp.zeros((batch,), jnp.int32)
        since = jnp.zeros((batch,), jnp.int32)
        mets = None
        if cfg.metrics:
            from repro.obs import metrics as obs_metrics
            mets = obs_metrics.zeros_batch(batch)
        return b.problem, states, budgets, since, mets, ewt

    def warm_one(self, bucket: int, batch: int, cfg: aco.ACOConfig,
                 max_iters: int, patience: int, donate: bool,
                 kind: str = "dense", hyper: bool = False) -> float:
        """AOT-lower-and-compile one program; returns compile seconds
        (0.0 when the signature is already cached)."""
        from . import engine
        problem, states, budgets, since, mets, ewt = self._templates(
            bucket, batch, cfg, kind, hyper)
        key = self.signature(problem, states, budgets, cfg, max_iters,
                             patience, donate, kind, ewt)
        with self._lock:
            if key in self._programs:
                return 0.0
        t0 = time.perf_counter()
        compiled = engine.aot_lower(problem, states, budgets, cfg,
                                    max_iters, patience, since, mets,
                                    kind=kind, ewt=ewt,
                                    donate=donate).compile()
        dt = time.perf_counter() - t0
        with self._lock:
            self._programs[key] = compiled
            self._warmed_buckets.setdefault((kind, MESH_NONE),
                                            set()).add(bucket)
        self._c_warm_s.inc(dt)
        self._c_warm_programs.inc()
        self.tel.tracer.complete(f"compile b{bucket}x{batch}",
                                 self.tel.tracer.to_us(t0), dt * 1e6,
                                 process="programs", thread=kind,
                                 bucket=bucket, batch=batch,
                                 donate=donate)
        return dt

    def warm_mesh_one(self, bucket: int, batch: int, cfg: aco.ACOConfig,
                      max_iters: int, patience: int, mesh,
                      donate: bool = False, kind: str = "dense",
                      hyper: bool = False) -> float:
        """Warm the sharded route for one bucket by *executing* a budget-0
        batch through the placement layer (AOT direct dispatch is skipped
        on the mesh route — placement keeps its own per-mesh jit cache —
        so warming means populating that cache; with every budget at 0 the
        while_loop exits before the first step and the run costs only the
        compile)."""
        from . import engine
        problem, states, budgets, since, mets, ewt = self._templates(
            bucket, batch, cfg, kind, hyper)
        label = mesh_label(mesh)
        with self._lock:
            if bucket in self._warmed_buckets.get((kind, label), set()):
                return 0.0
        t0 = time.perf_counter()
        out = engine.run_batch(problem, states, budgets, cfg, max_iters,
                               patience, since, donate=donate, mesh=mesh,
                               kind=kind, ewt=ewt, mets=mets)
        out[0].best_len.block_until_ready()
        dt = time.perf_counter() - t0
        with self._lock:
            self._warmed_buckets.setdefault((kind, label),
                                            set()).add(bucket)
        self._c_warm_s.inc(dt)
        self._c_warm_programs.inc()
        self.tel.tracer.complete(f"compile b{bucket}x{batch}@{label}",
                                 self.tel.tracer.to_us(t0), dt * 1e6,
                                 process="programs", thread=kind,
                                 bucket=bucket, batch=batch, mesh=label)
        return dt

    def warm(self, buckets: Sequence[int], batch: int, cfg: aco.ACOConfig,
             max_iters: int, patience: int = 0, donate: bool = False,
             kind: str = "dense", hyper: bool = False, mesh=None,
             background: bool = False):
        """Compile the whole bucket ladder; returns a summary dict, or —
        with ``background=True`` — the started thread (``wait()`` joins
        it; misses before it finishes just take the jit path)."""
        if background:
            t = threading.Thread(
                target=self._warm_ladder,
                args=(tuple(buckets), batch, cfg, max_iters, patience,
                      donate, kind, hyper, mesh),
                name="programs-warmup", daemon=True)
            with self._lock:
                self._warm_thread = t
            t.start()
            return t
        return self._warm_ladder(tuple(buckets), batch, cfg, max_iters,
                                 patience, donate, kind, hyper, mesh)

    def _warm_ladder(self, buckets, batch, cfg, max_iters, patience,
                     donate, kind, hyper, mesh):
        per_bucket = {}
        t0 = time.perf_counter()
        for b in buckets:
            try:
                if mesh is not None:
                    per_bucket[b] = self.warm_mesh_one(
                        b, batch, cfg, max_iters, patience, mesh,
                        donate=donate, kind=kind, hyper=hyper)
                else:
                    per_bucket[b] = self.warm_one(
                        b, batch, cfg, max_iters, patience, donate,
                        kind=kind, hyper=hyper)
            except Exception as e:            # noqa: BLE001 — background
                # thread must not die silently: the bucket stays cold and
                # the error is reported in the summary and ``stats()``.
                with self._lock:
                    self._warm_errors.append(f"b{b}: {type(e).__name__}: {e}")
                self.tel.events.emit("warmup_error", bucket=b,
                                     error=f"{type(e).__name__}: {e}")
        summary = {"buckets": {str(b): round(s, 4)
                               for b, s in per_bucket.items()},
                   "batch": batch, "kind": kind,
                   "mesh": mesh_label(mesh),
                   "wall_s": time.perf_counter() - t0,
                   "errors": list(self._warm_errors)}
        self.tel.events.emit("warmup", buckets=summary["buckets"],
                             batch=batch, route=kind,
                             mesh=summary["mesh"],
                             wall_s=summary["wall_s"])
        return summary

    def wait(self, timeout: Optional[float] = None) -> None:
        """Join a background warmup, if one is running."""
        with self._lock:
            t = self._warm_thread
        if t is not None:
            t.join(timeout)

    # --------------------------------------------------------- admission
    def warmed_buckets(self, kind: str = "dense",
                       mesh: str = MESH_NONE) -> tuple[int, ...]:
        with self._lock:
            return tuple(sorted(self._warmed_buckets.get((kind, mesh), ())))

    def route_bucket(self, native: int, cfg: aco.ACOConfig,
                     kind: str = "dense", mesh: str = MESH_NONE) -> int:
        """Admission-time bucket choice: the native bucket when warmed (or
        when neighbour routing is unsupported for this config), else the
        nearest larger warmed bucket, else native (compile-on-demand,
        exactly the pre-cache behaviour)."""
        warmed = self._warmed_buckets.get((kind, mesh), ())
        if native in warmed:
            return native
        if not neighbour_supported(cfg):
            return native
        bigger = [b for b in warmed if b > native]
        return min(bigger) if bigger else native

    # ---------------------------------------------------------- hot path
    def call(self, fn, problem, states, budgets, cfg, max_iters, patience,
             since, mets, kind: str, ewt: str, donate: bool):
        """Dispatch one ``run_batch`` call: AOT executable on a warmed
        signature (``jit_cache_hit``), the ordinary jit path otherwise
        (``jit_cache_miss`` — jax compiles and caches on first sight, so
        a missed signature costs one compile, exactly as before).  A
        warmed executable that refuses its operands (layout or sharding
        drift) raises: re-running the request on the jit path would hide
        a recompile on every call."""
        key = self.signature(problem, states, budgets, cfg, max_iters,
                             patience, donate, kind, ewt)
        with self._lock:
            compiled = self._programs.get(key)
        if compiled is not None:
            out = compiled(problem, states, budgets, since, mets)
            self._c_hit.inc()
            return out
        self._c_miss.inc()
        self._note_miss(key)
        return fn(problem, states, budgets, cfg, max_iters, patience,
                  since, mets, kind=kind, ewt=ewt)

    def note_mesh_call(self, key: ProgramKey) -> None:
        """Hit/miss accounting for the sharded route (dispatch itself
        stays with the placement layer's own per-mesh cache)."""
        warmed = self._warmed_buckets.get((key.kind, key.mesh), ())
        if key.n_pad in warmed:
            self._c_hit.inc()
        else:
            self._c_miss.inc()
            self._note_miss(key)

    def _note_miss(self, key: ProgramKey) -> None:
        sig = (key.n_pad, key.batch, key.kind, key.ewt, key.mesh,
               key.max_iters, key.donate)
        with self._lock:
            if sig not in self._missed_keys and len(self._missed_keys) < 32:
                self._missed_keys.append(sig)

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        with self._lock:
            buckets = {f"{kind}@{mesh}": sorted(bs)
                       for (kind, mesh), bs in self._warmed_buckets.items()}
            missed = [
                {"bucket": s[0], "batch": s[1], "kind": s[2], "ewt": s[3],
                 "mesh": s[4], "max_iters": s[5], "donate": s[6]}
                for s in self._missed_keys]
            n_programs = len(self._programs)
            errors = list(self._warm_errors)
        return {
            "programs": n_programs,
            "warmed_buckets": buckets,
            "hits": self._c_hit.value,
            "misses": self._c_miss.value,
            "warmup_compile_s": self._c_warm_s.value,
            "warmup_programs": self._c_warm_programs.value,
            "missed_signatures": missed,
            "warm_errors": errors,
        }

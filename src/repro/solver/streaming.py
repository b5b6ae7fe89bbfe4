"""Continuous-batching streaming solver: slot-based engine, mid-run admission.

The drain-the-queue scheduler (service.py) admits work only at batch
boundaries: a straggler holds its whole batch, and newly arrived requests
wait for the full drain.  This module removes that barrier the way LM
serving engines do (continuous batching): each bucket owns a *resident*
stacked ``ColonyState`` of ``max_batch`` slots, and a step loop runs
fixed-size chunks of the vmapped ``colony_step`` (engine.run_batch).  After
every chunk, slots whose per-slot done mask fires (absolute iteration
counter >= budget, or patience) are harvested into ``SolveResult``s and
immediately refilled from the pending queue by **state surgery** — the
slot's rows of the stacked Problem/ColonyState pytrees are overwritten via
``.at[idx].set`` with a fresh padded problem and ``engine.init_state`` — so
one compiled program per (bucket, slots, cfg, chunk) serves an unbounded
request stream with no drain barrier.

Exactness contract (tests/test_streaming.py): any request solved through
the streaming pool yields *bitwise* the same best tour as a solo
``engine.run_batch`` call with the same seed.  Three properties compose to
give this:

- refill surgery is a pure functional ``.at[idx].set`` — sibling slots'
  leaves are untouched bitwise;
- ``run_batch`` freezes finished slots against their own *absolute*
  iteration counter, so chunked stepping composes exactly with one long
  call (the crash-recovery property of DESIGN.md §8, reused);
- a refilled slot starts from exactly the state a solo run starts from
  (``engine.init_state``: tau0 from the real instance, PRNGKey(seed)).

Admission control: waiting requests are ordered by (priority desc,
deadline asc, arrival); ``max_waiting`` bounds the queue (backpressure —
``submit`` raises AdmissionError so callers can shed load upstream).
DESIGN.md §9 records the slot lifecycle and invariants.

Telemetry (repro.obs, DESIGN.md §13): the service records everything into
a ``Telemetry`` bundle — counters/gauges/**bounded** histograms behind
``stats`` (occupancy and latency samples no longer grow without bound;
exact count/total fields keep the means and rates exact), the full slot
lifecycle (submit → admit → chunk-step → harvest/evict) as JSON-lines
events, the host phases of a tick (``step``, ``admit``, ``prep``,
``chunk_dispatch``, ``harvest``) and slot residencies as Chrome-trace
spans on per-device/per-bucket tracks — the phases also land in any live
``jax.profiler`` capture as ``aco.*`` events, beside the device's
operations — and, with ``cfg.metrics``, the in-jit
StepMetrics rows carried next to the resident ColonyState, surfaced per
result and in periodic snapshots.  Pass a ``telemetry=`` instance to
export; the default private bundle costs microseconds per event.
"""
from __future__ import annotations

import dataclasses
import time
import uuid
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import aco, pheromone, tsp
from repro.obs import metrics as obs_metrics

from . import batch as batch_mod
from . import engine
from . import placement
from .service import SolveResult


class AdmissionError(RuntimeError):
    """Raised by submit() when the waiting queue is at max_waiting."""


@dataclasses.dataclass
class StreamRequest:
    request_id: int
    instance: tsp.TSPInstance
    iterations: int
    seed: int
    priority: int = 0                  # higher admitted first
    # Latency budget in seconds after submission; tighter budgets admit
    # first.  Once ``expires_at`` (= submitted_at + deadline, stamped at
    # submit) passes, the request is *evicted* at the next step — from the
    # waiting queue or from its running slot — as an ``expired`` result.
    deadline: Optional[float] = None
    hyper: Optional[aco.Hyper] = None
    submitted_at: float = 0.0
    expires_at: Optional[float] = None  # absolute perf_counter seconds
    # Request-scoped observability (DESIGN.md §14): ``trace_id`` is minted
    # at submit and carried — with ``request_id`` and the optional
    # ``tenant`` label — on every lifecycle event and span the request
    # touches, so its full submit -> admit -> slot -> harvest journey is
    # reconstructable from one trace/event log.  Host-side only: neither
    # field reaches the solve (bitwise on==off, tests/test_serving.py).
    trace_id: str = ""
    tenant: Optional[str] = None
    # Admission bucket, stamped once at submit: the native power-of-two
    # bucket, or — with an attached program cache — the neighbour-routed
    # warmed bucket (DESIGN.md §16).  Stamped rather than recomputed so a
    # warmup finishing mid-queue can't re-route a request whose padded
    # problem was already prepped for another width.
    bucket: int = 0
    # Prepped at submit time (off the stepping critical path): the padded
    # Problem and fresh ColonyState the refill surgery writes into a slot.
    prob: Optional[aco.Problem] = None
    state: Optional[aco.ColonyState] = None

    def order_key(self):
        return (-self.priority,
                self.expires_at if self.expires_at is not None
                else float("inf"),
                self.request_id)

    def prep(self, bucket: int, cfg: aco.ACOConfig, nn_k: int,
             tracer: obs.Tracer) -> None:
        """Build the padded problem, tau0 and initial state once, in a
        ``prep`` span."""
        if self.prob is None:
            with tracer.span("prep", n=self.instance.n, bucket=bucket):
                self.prob = batch_mod.padded_problem(
                    self.instance, bucket, nn_k, self.hyper)
                self.state = engine.init_state(
                    self.instance, cfg, self.seed, bucket, self.hyper)


class StreamingPool:
    """One bucket's resident slots: a stacked Problem/ColonyState of
    ``slots`` rows stepped together; empty slots hold a frozen dummy
    (budget 0 => done => the engine's where-merge discards their step).
    """

    def __init__(self, bucket: int, slots: int, cfg: aco.ACOConfig,
                 patience: int = 0, nn_k: Optional[int] = None,
                 per_instance_hyper: bool = False, device=None,
                 telemetry: Optional[obs.Telemetry] = None,
                 dev_label: str = "dev0",
                 slo: Optional[obs.SloTracker] = None,
                 programs=None):
        self.bucket = bucket
        self.slots = slots
        self.cfg = cfg
        self.patience = patience
        # AOT program cache (solver/programs.py): chunk steps dispatch a
        # warmed executable directly; None keeps the plain jit path.
        self.programs = programs
        self.nn_k = cfg.nn_k if nn_k is None else nn_k
        self.per_instance_hyper = per_instance_hyper
        # Telemetry sink (DESIGN.md §13): standalone pools get a private
        # in-memory bundle; the service shares one across its pools so
        # traces/events land on one timeline.  ``dev_label`` names this
        # pool's Chrome-trace process track.
        self.tel = telemetry if telemetry is not None else obs.Telemetry()
        self.dev_label = dev_label
        # Per-tenant SLO accounting (DESIGN.md §14): the service shares
        # one tracker across its pools; a standalone pool gets a private
        # one over its own registry.
        self.slo = slo if slo is not None else obs.SloTracker(
            self.tel.registry)
        # Per-device placement (DESIGN.md §11): committing the resident
        # pytrees to one device pins every chunk step there — the
        # topology-aware service runs one pool per mesh device and the
        # host dispatches all pools' (async) chunk steps before reading
        # any result back, so pools step concurrently.
        self.device = device
        # Dummy resident for empty slots: any small valid instance works —
        # budget 0 keeps it permanently frozen, so its trajectory is never
        # observed; it only has to be finite so the discarded vmap lanes
        # stay numerically tame.
        dummy = tsp.random_instance(2, seed=0)
        dhyper = aco.Hyper.make(cfg) if per_instance_hyper else None
        dprob = batch_mod.padded_problem(dummy, bucket, self.nn_k, dhyper)
        dstate = engine.init_state(dummy, cfg, 0, bucket, dhyper)
        stack = lambda x: jnp.broadcast_to(x[None], (slots,) + x.shape)
        self.problem: aco.Problem = jax.tree.map(stack, dprob)
        self.states: aco.ColonyState = jax.tree.map(stack, dstate)
        self.budgets = jnp.zeros((slots,), jnp.int32)
        self.since = jnp.zeros((slots,), jnp.int32)
        # In-jit metrics rows ride next to the resident state through the
        # same donate/freeze/refill machinery (None with metrics off).
        self.mets = obs_metrics.zeros_batch(slots) if cfg.metrics else None
        if device is not None:
            put = lambda t: jax.device_put(t, device)
            self.problem = put(self.problem)
            self.states = put(self.states)
            self.budgets = put(self.budgets)
            self.since = put(self.since)
            if self.mets is not None:
                self.mets = put(self.mets)
        self.requests: list[Optional[StreamRequest]] = [None] * slots
        self.filled_at: list[float] = [0.0] * slots
        self.fills = 0
        self.chunks = 0

    # ---------------------------------------------------------- occupancy
    @property
    def occupied(self) -> int:
        return sum(r is not None for r in self.requests)

    def free_slots(self) -> list[int]:
        return [i for i, r in enumerate(self.requests) if r is None]

    # ------------------------------------------------------ refill surgery
    def fill_slots(self, assignments: Sequence[tuple[int, StreamRequest]]
                   ) -> None:
        """Overwrite each (slot, request) pair's rows of the resident
        pytrees with a fresh problem + initial state.  One batched
        ``.at[idx].set`` per leaf; sibling slots are untouched bitwise."""
        if not assignments:
            return
        now = time.perf_counter()
        probs, states, idx, buds = [], [], [], []
        for i, req in assignments:
            assert self.requests[i] is None, f"slot {i} occupied"
            req.prep(self.bucket, self.cfg, self.nn_k, self.tel.tracer)
            probs.append(req.prob)
            states.append(req.state)
            idx.append(i)
            buds.append(req.iterations)
            self.requests[i] = req
            self.filled_at[i] = now
            self.fills += 1
        ix = jnp.asarray(idx, jnp.int32)
        newp = jax.tree.map(lambda *xs: jnp.stack(xs), *probs)
        news = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
        self.problem = jax.tree.map(lambda P, x: P.at[ix].set(x),
                                    self.problem, newp)
        self.states = jax.tree.map(lambda S, x: S.at[ix].set(x),
                                   self.states, news)
        self.budgets = self.budgets.at[ix].set(jnp.asarray(buds, jnp.int32))
        self.since = self.since.at[ix].set(0)
        if self.mets is not None:          # fresh slot, fresh metrics row
            self.mets = jax.tree.map(lambda M: M.at[ix].set(0), self.mets)
        for i, req in assignments:        # resident copies own the data now
            req.prob = req.state = None
            wait_s = now - req.submitted_at
            self.slo.on_admit(req.tenant, wait_s)
            self.tel.events.emit(
                "admit", request_id=req.request_id,
                trace_id=req.trace_id,
                tenant=obs.SloTracker.tenant_label(req.tenant), slot=i,
                bucket=self.bucket, device=self.dev_label,
                n=req.instance.n, iterations=req.iterations,
                wait_s=wait_s)
            # Retroactive queue-wait span (submit -> admit) on the shared
            # "queue" track: together with the residency span stamped at
            # harvest, the request's whole journey is one span chain
            # findable by request_id/trace_id (DESIGN.md §14).
            self.tel.tracer.complete(
                f"queued req{req.request_id}",
                self.tel.tracer.to_us(req.submitted_at), wait_s * 1e6,
                process="queue", thread=f"b{self.bucket}",
                request_id=req.request_id, trace_id=req.trace_id,
                tenant=obs.SloTracker.tenant_label(req.tenant))

    # ------------------------------------------------------------ stepping
    def step_chunk(self, chunk: int) -> None:
        """Advance every active slot by up to ``chunk`` iterations.

        The resident stacked ColonyState, stagnation counters and metrics
        rows are *donated* to the jitted chunk step: the old buffers alias
        the new ones (in-place on TPU, copy-free), which is safe because
        the only references — ``self.states``/``self.since``/``self.mets``
        — are immediately rebound to the outputs (DESIGN.md §10).

        The dispatch is recorded as a ``chunk_dispatch`` span on this
        pool's device/bucket track (async: the span covers the enqueue, not
        device wall time), with the pool's occupancy (``occupied`` of
        ``slots``) and padding (``cities``, the real cities of the occupied
        slots, of ``occupied * bucket``)."""
        live = [r for r in self.requests if r is not None]
        with self.tel.tracer.span("chunk_dispatch", process=self.dev_label,
                                  thread=f"b{self.bucket}",
                                  occupied=len(live), slots=self.slots,
                                  bucket=self.bucket,
                                  cities=sum(r.instance.n for r in live),
                                  chunk=chunk,
                                  request_ids=[r.request_id for r in live]):
            out = engine.run_batch(
                self.problem, self.states, self.budgets, self.cfg, chunk,
                self.patience, self.since, donate=True, mets=self.mets,
                programs=self.programs)
        if self.cfg.metrics:
            self.states, self.since, self.mets = out
        else:
            self.states, self.since = out
        self.chunks += 1

    def harvest(self) -> list[SolveResult]:
        """Collect every occupied slot whose done mask fired; free the slot
        (budget 0 refreezes it) so the next admit round can refill it.
        A ``harvest`` span covers the read-backs and the freeing."""
        with self.tel.tracer.span("harvest", process=self.dev_label,
                                  thread=f"b{self.bucket}",
                                  bucket=self.bucket) as span:
            it = np.asarray(self.states.iteration)
            done = it >= np.asarray(self.budgets)
            if self.patience > 0:
                done = done | (np.asarray(self.since) >= self.patience)
            out = self._free_slots(
                [i for i, r in enumerate(self.requests)
                 if r is not None and done[i]])
            span["harvested"] = len(out)
        return out

    def evict_expired(self, now: float) -> list[SolveResult]:
        """Evict occupied slots whose request deadline has passed: the
        freed slot returns a SolveResult flagged ``expired`` holding the
        best tour found so far (deadline-bounded anytime behaviour), and
        budget 0 refreezes the slot so the ordinary refill surgery can
        reuse it.  Sibling slots are untouched bitwise — freeing is the
        same ``.at[idx].set`` path harvest uses."""
        hits = [i for i, r in enumerate(self.requests)
                if r is not None and r.expires_at is not None
                and r.expires_at <= now]
        return self._free_slots(hits, expired=True)

    def _free_slots(self, hits: list[int],
                    expired: bool = False) -> list[SolveResult]:
        if not hits:
            return []
        now = time.perf_counter()
        it = np.asarray(self.states.iteration)
        lens = np.asarray(self.states.best_len)
        tours = np.asarray(self.states.best_tour)
        out = []
        freed = []
        for i in hits:
            req = self.requests[i]
            inst = req.instance
            opt = inst.known_optimum
            best_len = float(lens[i])
            latency_s = now - req.submitted_at
            tenant = obs.SloTracker.tenant_label(req.tenant)
            mrow = (obs_metrics.to_host(self.mets, i)
                    if self.mets is not None else None)
            out.append(SolveResult(
                request_id=req.request_id, name=inst.name, n=inst.n,
                bucket=self.bucket, best_len=best_len,
                best_tour=batch_mod.trim_tour(tours[i], inst.n),
                iterations=int(it[i]),
                gap_pct=(100.0 * (best_len / opt - 1.0) if opt else None),
                latency_s=latency_s,
                solve_s=now - self.filled_at[i], expired=expired,
                metrics=mrow, trace_id=req.trace_id, tenant=req.tenant))
            self.requests[i] = None
            freed.append(i)
            self.slo.on_outcome(
                req.tenant,
                "expired_running" if expired else "completed",
                latency_s, req.deadline)
            # slot-lifecycle record + a residency span on this slot's
            # Chrome-trace lane (fill -> free, stamped retroactively)
            kind = "evict" if expired else "harvest"
            ev = dict(request_id=req.request_id, trace_id=req.trace_id,
                      tenant=tenant, slot=i,
                      bucket=self.bucket, device=self.dev_label,
                      iterations=int(it[i]), best_len=best_len,
                      latency_s=latency_s)
            if mrow is not None:
                ev["metrics"] = mrow
            self.tel.events.emit(kind, **ev)
            self.tel.tracer.complete(
                f"req{req.request_id}" + ("!" if expired else ""),
                self.tel.tracer.to_us(self.filled_at[i]),
                (now - self.filled_at[i]) * 1e6,
                process=self.dev_label, thread=f"b{self.bucket}/s{i}",
                request_id=req.request_id, trace_id=req.trace_id,
                tenant=tenant, n=inst.n,
                iterations=int(it[i]), expired=expired)
        self.budgets = self.budgets.at[jnp.asarray(freed)].set(0)
        return out

    def latest_metrics(self) -> dict[int, dict]:
        """Host view of the occupied slots' in-jit metrics rows (one
        device read-back), keyed by request id — the live convergence
        snapshot the service's periodic stats emit.  Empty with
        ``cfg.metrics`` off."""
        if self.mets is None:
            return {}
        return {r.request_id: obs_metrics.to_host(self.mets, i)
                for i, r in enumerate(self.requests) if r is not None}


class StreamingSolverService:
    """Mid-run-admission request loop over per-bucket streaming pools.

    submit() only queues; admission happens at each step(): waiting
    requests (priority/deadline ordered) fill free slots of their bucket's
    pool, every non-empty pool advances one chunk, finished slots are
    harvested and immediately refillable.  ``max_waiting`` bounds the
    queue (AdmissionError).  ``per_instance_hyper=True`` makes every slot
    carry alpha/beta/rho/q operands so one bucket mixes tuning profiles
    (requests may pass a Hyper or override dict; others run the config
    profile).

    ``mesh`` places one resident pool per mesh device for every bucket
    (DESIGN.md §11): admissions route to the least-occupied pool, all
    pools' chunk steps are dispatched before any harvest, and every
    result stays bitwise what the single-pool service returns for the
    same request.  Requests whose ``deadline`` passes are evicted from
    the waiting queue and from running slots at the next step(), returned
    as ``expired``-flagged results and counted in stats().
    """

    def __init__(self, cfg: Optional[aco.ACOConfig] = None,
                 max_batch: int = 8, min_bucket: int = 16, chunk: int = 5,
                 patience: int = 0, max_waiting: Optional[int] = None,
                 per_instance_hyper: bool = False, mesh=None,
                 telemetry: Optional[obs.Telemetry] = None,
                 snapshot_every: float = 0.0, programs=None):
        if cfg is None:
            cfg = aco.ACOConfig()
        if cfg.use_pallas and per_instance_hyper:
            # the one genuinely unsupported kernel route (DESIGN.md §10):
            # per-slot Hyper operands need traced exponents, kernels need
            # static ones.  Fail eagerly with the kernels' own typed error.
            from repro.kernels import ops as kops
            kops.check_kernel_route(hyper=True, tau_dtype=cfg.tau_dtype)
        if per_instance_hyper and cfg.tau_dtype != "fp32":
            # quantised x per-slot Hyper is unsupported on every route;
            # fail at construction, not at the first admitted request.
            from repro.kernels import ops as kops
            kops.check_kernel_route(hyper=True, tau_dtype=cfg.tau_dtype)
        if cfg.sparse:
            # slot surgery assumes dense (n, n) ColonyState buffers
            from repro.kernels import ops as kops
            kops.check_kernel_route(sparse=True, streaming=True,
                                    selection=cfg.selection,
                                    local_search=cfg.local_search,
                                    construction=cfg.construction)
        if cfg.deposit not in pheromone.STRATEGIES:
            raise ValueError(f"unknown deposit strategy {cfg.deposit!r}; "
                             f"supported: {', '.join(pheromone.STRATEGIES)}")
        if chunk < 1:
            raise ValueError(f"chunk {chunk} < 1")
        if max_waiting is not None and max_waiting < 1:
            raise ValueError(f"max_waiting {max_waiting} < 1")
        self.cfg = cfg
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        self.chunk = chunk
        self.patience = patience
        self.max_waiting = max_waiting
        self.per_instance_hyper = per_instance_hyper
        # Prep (padded Problem + initial state) is eager only for the head
        # of the queue: it keeps refill surgery off the stepping critical
        # path without letting a deep backlog pin O(waiting * n_pad^2)
        # device memory — requests beyond the window are prepped when they
        # reach the head (at admit time) or, worst case, at fill.
        self.prep_ahead = 4 * max_batch
        # Topology (DESIGN.md §11): with a mesh, each bucket owns one
        # resident pool *per mesh device* (committed buffers pin its chunk
        # steps to that device); admissions go to the least-occupied pool
        # and every step dispatches all pools before harvesting any, so
        # the D async chunk programs overlap across devices.  Without a
        # mesh there is exactly one device slot (None = default device)
        # and behaviour is unchanged.
        self.mesh = mesh
        self._devices = (list(mesh.devices.flat) if mesh is not None
                         else [None])
        self._pools: dict[int, list[StreamingPool]] = {}
        self._waiting: list[StreamRequest] = []
        self._next_id = 0
        # Telemetry bundle (DESIGN.md §13): every ad-hoc stat lives in the
        # registry now — counters for lifecycle totals, **bounded**
        # histograms (exact count/total, windowed percentiles) for the
        # latency and occupancy samples that previously grew one float per
        # completion forever.  stats reads from here; pass ``telemetry=``
        # to share the bundle (and its trace/event exports) with a caller.
        self.tel = telemetry if telemetry is not None else obs.Telemetry()
        self.snapshot_every = snapshot_every
        # Serving observability plane (DESIGN.md §14): one per-tenant SLO
        # tracker shared by every pool, and a monotonic service birth
        # stamp every stats_snapshot carries as ``uptime_s``.
        self.slo = obs.SloTracker(self.tel.registry)
        # AOT program cache (solver/programs.py, DESIGN.md §16): resident
        # pools dispatch warmed chunk executables directly, and admission
        # neighbour-routes an unwarmed bucket into the nearest larger
        # warmed one when the config's numerics are bucket-width
        # invariant (programs.check_neighbour_route).  Streaming pools
        # always step full-width (slots = max_batch, loop bound = chunk,
        # donated), so one warmed program per bucket covers every chunk
        # the pool will ever dispatch.
        self.programs = programs
        self._t_started = time.perf_counter()
        self._c_submitted = self.tel.registry.counter("submitted")
        self._c_rejected = self.tel.registry.counter("rejected")
        self._c_completed = self.tel.registry.counter("completed")
        self._c_expired_running = self.tel.registry.counter("expired_running")
        self._c_expired_waiting = self.tel.registry.counter("expired_waiting")
        self._h_latency = self.tel.registry.histogram("latency_s")
        self._h_occupancy = self.tel.registry.histogram("occupancy")
        self._per_bucket_done: dict[int, int] = {}
        self._t_first_submit: Optional[float] = None
        self._t_last_harvest: Optional[float] = None
        self._t_last_snapshot: Optional[float] = None

    # -------------------------------------------------------------- queue
    def submit(self, instance: tsp.TSPInstance,
               iterations: Optional[int] = None,
               seed: Optional[int] = None, priority: int = 0,
               deadline: Optional[float] = None,
               hyper: Union[aco.Hyper, dict, None] = None,
               tenant: Optional[str] = None) -> int:
        """Queue a request; returns its id.  Raises AdmissionError when the
        waiting queue is full (backpressure) — resident slots don't count,
        only un-admitted requests.  ``deadline`` is a latency budget in
        seconds from now: it orders admission (tighter first) and, once
        exceeded, the request is evicted at the next step() as an
        ``expired`` result.  ``tenant`` is a pure observability label
        (per-tenant SLO accounting, DESIGN.md §14): it never influences
        ordering, placement or the solve itself."""
        if deadline is not None and deadline <= 0:
            raise ValueError(f"deadline {deadline} <= 0")
        if self.max_waiting is not None and \
                len(self._waiting) >= self.max_waiting:
            self._c_rejected.inc()
            self.slo.on_reject(tenant)
            self.tel.events.emit("reject", waiting=len(self._waiting),
                                 max_waiting=self.max_waiting,
                                 tenant=obs.SloTracker.tenant_label(tenant))
            raise AdmissionError(
                f"waiting queue full ({len(self._waiting)} >= "
                f"{self.max_waiting})")
        its = iterations if iterations is not None else self.cfg.iterations
        if its < 1:
            raise ValueError(f"iterations {its} < 1")
        if hyper is not None and not self.per_instance_hyper:
            raise ValueError("per-request hyper requires "
                             "per_instance_hyper=True")
        if self.per_instance_hyper:
            if isinstance(hyper, dict):
                hyper = aco.Hyper.make(self.cfg, **hyper)
            elif hyper is None:
                hyper = aco.Hyper.make(self.cfg)
        rid = self._next_id
        self._next_id += 1
        now = time.perf_counter()
        if self._t_first_submit is None:
            self._t_first_submit = now
        req = StreamRequest(
            request_id=rid, instance=instance, iterations=its,
            seed=seed if seed is not None else self.cfg.seed + rid,
            priority=priority, deadline=deadline, hyper=hyper,
            submitted_at=now,
            expires_at=None if deadline is None else now + deadline,
            trace_id=uuid.uuid4().hex[:16], tenant=tenant)
        req.bucket = self._route_bucket(instance.n)
        # Prep the padded problem + initial state at enqueue time (so
        # refill surgery on the stepping critical path is only .at[ix].set)
        # — but only within the bounded look-ahead window.
        if len(self._waiting) < self.prep_ahead:
            req.prep(req.bucket, self.cfg, self.cfg.nn_k, self.tel.tracer)
        self._waiting.append(req)
        self._c_submitted.inc()
        self.slo.on_submit(tenant)
        self.tel.events.emit(
            "submit", request_id=rid, trace_id=req.trace_id,
            tenant=obs.SloTracker.tenant_label(tenant), n=instance.n,
            bucket=req.bucket,
            iterations=its, priority=priority, deadline=deadline)
        return rid

    def _route_bucket(self, n: int) -> int:
        """Admission bucket for an ``n``-city instance: the native
        power-of-two bucket, possibly neighbour-routed into the nearest
        larger warmed bucket by an attached program cache (bitwise-exact
        per programs.check_neighbour_route)."""
        native = batch_mod.bucket_size(n, self.min_bucket)
        if self.programs is None:
            return native
        return self.programs.route_bucket(native, self.cfg, kind="dense")

    def warm_programs(self, min_n: int, max_n: int,
                      background: bool = False, ladder=None):
        """Precompile the chunk-step program for every bucket instances
        in [min_n, max_n] can land in (batch.bucket_ladder; ``ladder``
        overrides with an explicit bucket list) — the exact signature the
        resident pools dispatch: slots = max_batch, loop bound = chunk,
        donated buffers, metrics per cfg.metrics."""
        if self.programs is None:
            raise ValueError("no ProgramCache attached (programs=)")
        if ladder is None:
            ladder = batch_mod.bucket_ladder(min_n, max_n, self.min_bucket)
        return self.programs.warm(
            ladder, batch=self.max_batch, cfg=self.cfg,
            max_iters=self.chunk, patience=self.patience, donate=True,
            kind="dense", hyper=self.per_instance_hyper,
            background=background)

    @property
    def waiting(self) -> int:
        return len(self._waiting)

    @property
    def resident(self) -> int:
        return sum(p.occupied for p in self._all_pools())

    @property
    def busy(self) -> bool:
        return bool(self._waiting) or self.resident > 0

    # ---------------------------------------------------------- admission
    def _bucket_pools(self, bucket: int) -> list[StreamingPool]:
        if bucket not in self._pools:
            # AOT dispatch only for the default-device pool: the warmed
            # executables were compiled for the default device, and a
            # pool committed elsewhere would fall back (exception per
            # chunk) — those pools keep the plain jit path.
            self._pools[bucket] = [
                StreamingPool(bucket, self.max_batch, self.cfg,
                              self.patience,
                              per_instance_hyper=self.per_instance_hyper,
                              device=dev, telemetry=self.tel,
                              dev_label=placement.device_label(dev, j),
                              slo=self.slo,
                              programs=self.programs if j == 0 else None)
                for j, dev in enumerate(self._devices)]
        return self._pools[bucket]

    def _all_pools(self):
        for pools in self._pools.values():
            yield from pools

    def _admit(self) -> int:
        """Move waiting requests (priority desc, deadline asc, arrival)
        into free slots of their bucket's pools, each to the currently
        least-occupied pool (deterministic: ties break to the lowest
        device index), in an ``admit`` span.  Returns #admitted."""
        if not self._waiting:
            return 0
        with self.tel.tracer.span("admit") as span:
            self._waiting.sort(key=StreamRequest.order_key)
            fills: dict[tuple[int, int],
                        list[tuple[int, StreamRequest]]] = {}
            free: dict[int, list[list[int]]] = {}  # bucket -> per-pool slots
            leftover: list[StreamRequest] = []
            for req in self._waiting:
                b = req.bucket
                if b not in free:
                    free[b] = [p.free_slots()
                               for p in self._bucket_pools(b)]
                # least-occupied == most free slots (all pools are same
                # size); the running pop keeps in-flight assignments
                # counted.
                j = max(range(len(free[b])), key=lambda k: len(free[b][k]))
                if free[b][j]:
                    fills.setdefault((b, j), []).append(
                        (free[b][j].pop(0), req))
                else:
                    leftover.append(req)
            self._waiting = leftover
            n = 0
            for (b, j), assignments in fills.items():
                self._pools[b][j].fill_slots(assignments)
                n += len(assignments)
            # Prefetch prep for the queue head (next harvest's refills) —
            # between chunks, not inside the surgery itself.
            for req in leftover[:self.prep_ahead]:
                req.prep(req.bucket, self.cfg, self.cfg.nn_k,
                         self.tel.tracer)
            span["admitted"] = n
        return n

    # ----------------------------------------------------------- eviction
    def _evict_expired(self) -> list[SolveResult]:
        """Deadline hardening (ROADMAP): drop deadline-expired requests
        from the waiting queue (never ran: empty tour, inf length) and
        from running slots (partial best so far); every eviction returns a
        SolveResult flagged ``expired`` and is counted in stats()."""
        now = time.perf_counter()
        out: list[SolveResult] = []
        if any(r.expires_at is not None and r.expires_at <= now
               for r in self._waiting):
            keep: list[StreamRequest] = []
            for req in self._waiting:
                if req.expires_at is not None and req.expires_at <= now:
                    wait_s = now - req.submitted_at
                    bucket = req.bucket
                    out.append(SolveResult(
                        request_id=req.request_id, name=req.instance.name,
                        n=req.instance.n, bucket=bucket,
                        best_len=float("inf"),
                        best_tour=np.zeros((0,), np.int32), iterations=0,
                        gap_pct=None, latency_s=wait_s,
                        solve_s=0.0, expired=True,
                        trace_id=req.trace_id, tenant=req.tenant))
                    self._c_expired_waiting.inc()
                    self.slo.on_outcome(req.tenant, "expired_waiting",
                                        wait_s, req.deadline)
                    tenant = obs.SloTracker.tenant_label(req.tenant)
                    self.tel.events.emit(
                        "evict_waiting", request_id=req.request_id,
                        trace_id=req.trace_id, tenant=tenant,
                        n=req.instance.n, wait_s=wait_s)
                    # never admitted: its whole life is one queue span
                    self.tel.tracer.complete(
                        f"queued req{req.request_id}!",
                        self.tel.tracer.to_us(req.submitted_at),
                        wait_s * 1e6, process="queue",
                        thread=f"b{bucket}",
                        request_id=req.request_id, trace_id=req.trace_id,
                        tenant=tenant, expired=True)
                else:
                    keep.append(req)
            self._waiting = keep
        for pool in self._all_pools():
            if pool.occupied:
                got = pool.evict_expired(now)
                self._c_expired_running.inc(len(got))
                out.extend(got)
        return out

    # ------------------------------------------------------------ stepping
    def step(self) -> list[SolveResult]:
        """One scheduler tick: evict expired deadlines, admit, advance
        every non-empty pool by one chunk, harvest.  Returns newly
        finished results (completion order, expired ones included).

        All pools' chunk steps are dispatched before any harvest reads a
        result back: jax dispatch is async, so with per-device pools the
        D chunk programs execute concurrently across the mesh while the
        host is still enqueueing/harvesting.  The tick is a ``step`` span
        holding the ``admit``, ``chunk_dispatch`` and ``harvest`` spans."""
        with self.tel.tracer.span("step", resident=self.resident,
                                  waiting=self.waiting):
            results: list[SolveResult] = list(self._evict_expired())
            self._admit()
            stepped: list[StreamingPool] = []
            for pool in self._all_pools():
                if pool.occupied == 0:
                    continue
                self._h_occupancy.observe(pool.occupied / pool.slots)
                pool.step_chunk(self.chunk)         # async dispatch
                stepped.append(pool)
            for pool in stepped:
                results.extend(pool.harvest())      # first device read-back
            if results:
                done = [r for r in results if not r.expired]
                if done:
                    self._t_last_harvest = time.perf_counter()
                    self._c_completed.inc(len(done))
                for r in done:
                    self._h_latency.observe(r.latency_s)
                    self._per_bucket_done[r.bucket] = \
                        self._per_bucket_done.get(r.bucket, 0) + 1
            self._maybe_snapshot()
        return results

    def _maybe_snapshot(self) -> None:
        """Periodic stats_snapshot event (``snapshot_every`` seconds):
        the stats dict plus — with ``cfg.metrics`` — every resident
        request's live convergence row.  The event log mirrors it to the
        ``--events-out`` file, so a long replay leaves a time series.

        The *first* snapshot fires immediately (the old anchor-on-
        previous-emit skipped it until one full period had passed), and
        every snapshot stamps a monotonic-clock ``uptime_s`` measured
        from service construction."""
        if self.snapshot_every <= 0:
            return
        now = time.perf_counter()
        if self._t_last_snapshot is not None and \
                now - self._t_last_snapshot < self.snapshot_every:
            return
        self._t_last_snapshot = now
        ev = {"stats": self.stats, "uptime_s": now - self._t_started}
        if self.cfg.metrics:
            live = {}
            for pool in self._all_pools():
                live.update({str(k): v
                             for k, v in pool.latest_metrics().items()})
            ev["resident_metrics"] = live
        self.tel.events.emit("stats_snapshot", **ev)

    def run_until_drained(self, max_steps: Optional[int] = None
                          ) -> list[SolveResult]:
        """Step until queue and pools are empty (or max_steps)."""
        out: list[SolveResult] = []
        steps = 0
        while self.busy:
            out.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return out

    # --------------------------------------------------------------- stats
    @property
    def stats(self) -> dict:
        """Same keys as ever, now read from the telemetry registry.  Means
        and rates come from the histograms' exact running aggregates, so
        they are what the old unbounded lists reported; percentiles are
        estimated over the bounded recent-sample window (DESIGN.md §13)."""
        lat = self._h_latency
        completed = self._c_completed.value
        expired = (self._c_expired_waiting.value
                   + self._c_expired_running.value)
        wall = None
        if self._t_first_submit is not None and \
                self._t_last_harvest is not None:
            wall = self._t_last_harvest - self._t_first_submit
        programs = ({"programs": self.programs.stats()}
                    if self.programs is not None else {})
        return {
            **programs,
            "submitted": self._c_submitted.value,
            "rejected": self._c_rejected.value,
            "completed": completed,
            "expired": expired,
            "expired_waiting": self._c_expired_waiting.value,
            "expired_running": self._c_expired_running.value,
            "waiting": self.waiting,
            "resident": self.resident,
            "devices": len(self._devices),
            "pools": sum(len(ps) for ps in self._pools.values()),
            "chunks": sum(p.chunks for p in self._all_pools()),
            "fills": sum(p.fills for p in self._all_pools()),
            "slots": {str(b): sum(p.slots for p in ps)
                      for b, ps in sorted(self._pools.items())},
            "buckets": {str(b): c
                        for b, c in sorted(self._per_bucket_done.items())},
            "occupancy_mean": self._h_occupancy.mean(),
            "instances_per_s": (completed / wall
                                if wall and wall > 0 else 0.0),
            "latency_mean_s": lat.mean(),
            "latency_p50_s": lat.percentile(50),
            "latency_p95_s": lat.percentile(95),
            "latency_max_s": lat.max(),
            "uptime_s": time.perf_counter() - self._t_started,
            "tenants": self.slo.summary(),
        }

    def health(self) -> dict:
        """Liveness + occupancy view for the ``/healthz`` endpoint
        (obs.serving.MetricsServer): one row per resident pool plus
        queue depth — everything a scraper needs to decide the service
        is alive and how loaded it is."""
        return {
            "mode": "streaming",
            "uptime_s": time.perf_counter() - self._t_started,
            "waiting": self.waiting,
            "resident": self.resident,
            "devices": len(self._devices),
            "tenants": sorted(self.slo.tenants),
            "pools": [
                {"bucket": p.bucket, "device": p.dev_label,
                 "slots": p.slots, "occupied": p.occupied,
                 "chunks": p.chunks, "fills": p.fills}
                for p in self._all_pools()],
        }


# ------------------------------------------------------------ trace replay
@dataclasses.dataclass(frozen=True)
class TraceItem:
    """One arrival of a replayable request trace."""
    at: float                      # seconds from replay start
    instance: tsp.TSPInstance
    iterations: int
    seed: int
    priority: int = 0
    tenant: Optional[str] = None   # observability label (DESIGN.md §14)


def make_poisson_trace(num: int, rate: float, min_n: int, max_n: int,
                       seed: int = 0,
                       iterations: Union[int, Sequence[int]] = 20,
                       tenants: Optional[Sequence[str]] = None
                       ) -> list[TraceItem]:
    """Poisson arrivals (exponential inter-arrival at ``rate`` req/s) of
    mixed circle/random instances; ``iterations`` may be a sequence of
    budgets cycled deterministically over the arrivals (heterogeneous
    stragglers are what streaming wins on).  ``tenants`` cycles tenant
    labels over the arrivals the same way — instances, seeds and budgets
    are unchanged by the labels, so a multi-tenant replay solves exactly
    the single-tenant workload (per-tenant SLO parity tests rely on it)."""
    rng = np.random.RandomState(seed)
    t = 0.0
    out = []
    for i in range(num):
        t += float(rng.exponential(1.0 / rate))
        n = int(rng.randint(min_n, max_n + 1))
        inst = (tsp.circle_instance(n, seed=seed + i) if i % 2 == 0
                else tsp.random_instance(n, seed=seed + i))
        its = (int(iterations) if np.isscalar(iterations)
               else int(iterations[i % len(iterations)]))
        out.append(TraceItem(at=t, instance=inst, iterations=its,
                             seed=seed + i,
                             tenant=(tenants[i % len(tenants)]
                                     if tenants else None)))
    return out


def replay_trace(svc: StreamingSolverService, trace: Sequence[TraceItem]
                 ) -> list[SolveResult]:
    """Wall-clock replay: submit each item once its arrival time passes,
    stepping the engine in between (mid-run admission); sleeps only when
    the engine is idle and the next arrival is in the future.  When the
    service's waiting queue is full (``max_waiting`` backpressure), the
    item is held and retried after the next step drains the queue — a
    client that waits on backpressure rather than dropping the request, so
    the service's ``rejected`` stat is not inflated by retry spam."""
    start = time.perf_counter()
    i = 0
    results: list[SolveResult] = []
    while i < len(trace) or svc.busy:
        now = time.perf_counter() - start
        while i < len(trace) and trace[i].at <= now:
            if svc.max_waiting is not None and \
                    svc.waiting >= svc.max_waiting:
                break          # queue full: step to drain, then retry
            it = trace[i]
            svc.submit(it.instance, iterations=it.iterations,
                       seed=it.seed, priority=it.priority,
                       tenant=it.tenant)
            i += 1
        if svc.busy:
            results.extend(svc.step())
        elif i < len(trace):
            time.sleep(max(0.0, trace[i].at - (time.perf_counter() - start)))
    return results

"""The program's phases in a trace: colony scopes on the device's
operations and ``aco.*`` host spans, on small traces whose answers are
known and on pieces of traces recorded on the chip."""
import glob
import json
import os

import numpy as np
import pytest

from chipbench.harness import cells, phases, xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000
SCOPED = sorted(glob.glob(os.path.join(DATA, "trace_*_scopes_*.json")))
PLAIN = [p for p in sorted(glob.glob(os.path.join(DATA, "trace_*.json")))
         if p not in SCOPED]


def synthetic():
    """Window 0..100 ms on one device, busy [10,30] [60,70] [80,85]; the
    host waits in [90,100].  Operations: a construct loop [10,30] with a
    nested op, a deposit [60,70] with a nested unscoped op, an unscoped op
    [80,85].  A step [0,50] holds an admit [2,20] (holding a prep [5,15]),
    a dispatch [20,25] and a harvest [25,45]; a second dispatch [55,58]
    lies outside any step."""
    iv = np.array([[10, 30], [12, 18], [60, 70], [62, 64], [80, 85]]) * MS
    ops = {"/device:TPU:0": (iv, ["while.1", "fusion.2", "fusion.3",
                                  "copy.4", "fusion.5"])}
    spans = [("bench.traced", 0, 100 * MS, {}),
             ("bench.wait", 90 * MS, 100 * MS, {}),
             ("aco.step", 0, 50 * MS, {"resident": 2, "waiting": 1}),
             ("aco.admit", 2 * MS, 20 * MS, {"admitted": 1}),
             ("aco.prep", 5 * MS, 15 * MS, {"n": 100, "bucket": 128}),
             ("aco.chunk_dispatch", 20 * MS, 25 * MS,
              {"occupied": 2, "slots": 8, "bucket": 128, "cities": 200,
               "chunk": 4}),
             ("aco.harvest", 25 * MS, 45 * MS,
              {"bucket": 128, "harvested": 0}),
             ("aco.chunk_dispatch", 55 * MS, 58 * MS,
              {"occupied": 1, "slots": 8, "bucket": 256, "cities": 150,
               "chunk": 4})]
    scopes = {"/device:TPU:0": {
        "construct": np.array([[10, 30]], float) * MS,
        "deposit": np.array([[60, 70]], float) * MS}}
    return xplane.Trace(device_ops=ops, spans=spans), scopes


def test_scope_of_takes_the_innermost_colony_scope():
    assert phases.scope_of("jit(f)/while/body/construct/while/body/mul") \
        == "construct"
    assert phases.scope_of("jit(f)/deposit/pallas_call/choice/x") \
        == "choice"
    assert phases.scope_of("jit(f)/while/body/argmin") is None
    assert phases.scope_of("jit(choice_info)/construct_x") is None


def test_scope_busy_and_share():
    trace, scopes = synthetic()
    s = xplane.reduce(trace)
    busy = phases.scope_busy_s(s, scopes)
    assert busy == pytest.approx({"construct": 0.020, "deposit": 0.010})
    assert phases.scoped_share(s, scopes) == pytest.approx(30 / 35)


def test_self_time_subtracts_children():
    trace, _ = synthetic()
    parts = phases.self_time(phases.program_spans(xplane.reduce(trace)))
    want = {"aco.step": [[0, 2], [45, 50]],
            "aco.admit": [[2, 5], [15, 20]], "aco.prep": [[5, 15]],
            "aco.chunk_dispatch": [[20, 25], [55, 58]],
            "aco.harvest": [[25, 45]]}
    assert set(parts) == set(want)
    for name, iv in want.items():
        np.testing.assert_array_equal(parts[name], np.array(iv) * MS)


def test_phase_idle_splits_resident_idle():
    trace, _ = synthetic()
    s = xplane.reduce(trace)
    idle = phases.phase_idle(s)
    # resident [0, 90]; idle in it [0,10] [30,60] [70,80] [85,90] = 55 ms
    want = {"aco.step": 7, "aco.admit": 3, "aco.prep": 5,
            "aco.chunk_dispatch": 3, "aco.harvest": 15, phases.OUTSIDE: 22}
    assert idle == pytest.approx({k: v / 90 for k, v in want.items()})
    assert sum(idle.values()) == pytest.approx(
        s.idle_share_within(s.resident_intervals()), abs=1e-12)


def test_dispatch_fill_from_span_args():
    trace, _ = synthetic()
    occ, fill = phases.dispatch_fill(xplane.reduce(trace))
    assert occ == pytest.approx(3 / 16)
    assert fill == pytest.approx(350 / (2 * 128 + 256))


def test_extract_reads_program_spans_from_a_capture(tmp_path):
    import jax
    from repro import obs
    t = obs.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with t.span("step", resident=2, request_ids=[1, 2]):
        with t.span("admit") as args:
            args["admitted"] = 1
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    ph = phases.extract(jax.profiler.ProfileData.from_file(path), [])
    assert [(n, a) for n, _, _, a in ph.spans] == [
        ("aco.step", {"resident": 2}), ("aco.admit", {"admitted": 1})]
    (_, s0, e0, _), (_, s1, e1, _) = ph.spans
    assert s0 <= s1 <= e1 <= e0


class _Ev:
    def __init__(self, name, start, end, stats=()):
        self.name, self.start_ns = name, start
        self.duration_ns, self.stats = end - start, list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


STEP = "jit(_run_batch_impl)/while/body/vmap(jit(colony_step))"
HLO = f"""HloModule jit__run_batch_impl, is_scheduled=true

%fused_computation.2 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %mul.1 = f32[8]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{STEP}/deposit/mul"}}
  ROOT %add.2 = f32[8]{{0}} add(%mul.1, %param_0), metadata={{op_name="{STEP}/deposit/add"}}
}}

%fused_computation.3 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  ROOT %fusion.4 = f32[8]{{0}} fusion(%param_0), kind=kLoop, calls=%fused_computation.2
}}

ENTRY %main.1 (p: f32[8]) -> f32[8] {{
  %fusion.7 = f32[8]{{0}} fusion(%p), kind=kCustom, calls=%fused_computation.3
  ROOT %while.3 = (f32[8]{{0}}) while(%t), condition=%c, body=%b, metadata={{op_name="{STEP}/construct/jit(_construct)/while"}}
}}"""


def test_hlo_paths_of_fusions_without_their_own():
    """A fusion with no op_name of its own takes the scope of the
    operations it calls, through nested fusions."""
    paths = phases.hlo_paths([HLO])
    key = ("jit__run_batch_impl", "%fusion.7")
    assert phases.scope_of(paths[key]) == "deposit"
    assert phases.scope_of(paths[("jit__run_batch_impl", "%while.3")]) \
        == "construct"
    # one name, two meanings in modules of one name: no path
    other = HLO.replace("/construct/", "/choice/")
    assert phases.hlo_paths([HLO, other])[
        ("jit__run_batch_impl", "%while.3")] is None


def test_extract_scopes_from_compiled_text():
    """Each operation takes the scope its instruction has in the compiled
    module it ran in; the scope unions merge nested operations."""
    ops = [_Ev("%while.3 = (f32[8]) while(t)", 10, 30),
           _Ev("%fusion.9 = f32[8] fusion(x)", 12, 20),
           _Ev("%fusion.7 = f32[8] fusion(p)", 40, 45),
           _Ev("%copy.1 = f32[8] copy(p)", 50, 52),
           _Ev("%fusion.7 = f32[8] fusion(p)", 57, 58)]   # another module
    data = type("D", (), {"planes": [
        _Plane("/host:CPU", [_Line("python", [
            _Ev("aco.step", 0, 60, [("resident", 1)]),
            _Ev("bench.step", 0, 61)])]),
        _Plane("/device:TPU:0", [
            _Line(xplane.MODULE_LINE,
                  [_Ev("jit__run_batch_impl(42)", 5, 55),
                   _Ev("jit_copy(7)", 56, 59)]),
            _Line(xplane.OP_LINE, ops)])]})()
    ph = phases.extract(data, [HLO])
    assert [s[0] for s in ph.spans] == ["aco.step"]
    o = ph.ops["/device:TPU:0"]
    assert o.names[0] == "%while.3"
    assert o.scopes == ["construct", None, "deposit", None, None]
    sc = ph.scopes()["/device:TPU:0"]
    np.testing.assert_array_equal(sc["construct"], [[10, 30]])
    np.testing.assert_array_equal(sc["deposit"], [[40, 45]])
    assert phases.extract(data, []).ops["/device:TPU:0"].scopes == [
        None] * 5


def _load(path):
    with open(path) as f:
        rec = json.load(f)
    trace = xplane.Trace.from_json(rec["trace"])
    return rec, trace, phases.scopes_from_json(rec["trace"].get("scopes",
                                                                {}))


@pytest.mark.parametrize("path", SCOPED,
                         ids=[os.path.basename(p) for p in SCOPED])
def test_recorded_scopes_and_phases(path):
    """A piece of a chip trace with scopes and aco.* spans: per-scope busy
    agrees with a direct count, the phase idles plus the idle outside
    every span add up to the resident idle, and the numbers are those the
    piece gave when it was recorded."""
    rec, trace, scopes = _load(path)
    s = xplane.reduce(trace)
    lo, hi = s.window_ns
    busy = phases.scope_busy_s(s, scopes)
    for sc, v in busy.items():
        direct, slack = [], 2e-6
        for d in s.busy:
            iv = scopes.get(d, {}).get(sc, np.zeros((0, 2)))
            grid = np.zeros(int((hi - lo) // 1000) + 1, bool)  # 1 us cells
            for a, b in np.clip(iv, lo, hi):
                grid[int((a - lo) // 1000):int((b - lo) // 1000)] = True
            direct.append(grid.sum() * 1e-6)
            slack += len(iv) * 2e-6
        assert v == pytest.approx(np.mean(direct), abs=slack)
    want = rec["expect_phases"]
    assert busy == pytest.approx(want["scope_busy_s"], rel=1e-9)
    resident_idle = s.idle_share_within(s.resident_intervals())
    assert resident_idle == pytest.approx(want["resident_idle"], rel=1e-9)
    idle = phases.phase_idle(s)
    assert idle == pytest.approx(want["phase_idle"], rel=1e-9, abs=1e-12)
    assert abs(sum(idle.values()) - resident_idle) < 1e-9
    occ, fill = phases.dispatch_fill(s)
    args = [a for n, st, _, a in s.spans
            if n == "aco.chunk_dispatch" and lo <= st < hi]
    if args:
        assert occ == pytest.approx(sum(a["occupied"] for a in args)
                                    / sum(a["slots"] for a in args))
        assert fill == pytest.approx(
            sum(a["cities"] for a in args)
            / sum(a["occupied"] * a["bucket"] for a in args))
    assert (occ, fill) == pytest.approx(
        (want["slot_occupancy"], want["pad_fill"]), rel=1e-9)


@pytest.mark.parametrize("path", SCOPED,
                         ids=[os.path.basename(p) for p in SCOPED])
def test_program_spans_leave_existing_readers_unchanged(path):
    """The benchmark's per-layer readers read the same whether or not the
    summary's spans include the program's aco.* spans."""
    _, trace, _ = _load(path)
    bare = xplane.Trace(device_ops=trace.device_ops,
                        spans=[sp for sp in trace.spans
                               if not sp[0].startswith(phases.PREFIX)],
                        op_ns=trace.op_ns)
    assert len(bare.spans) < len(trace.spans)
    for name in ("iter_device_ms.single", "device_idle.single",
                 "resident_idle.serve", "device_ms_per_req.serve"):
        ctxs = [{"summary": xplane.reduce(t), "iterations": 3,
                 "completed": 2, "chips": 1} for t in (trace, bare)]
        read = cells.reader(name)
        assert read(ctxs[0]) == read(ctxs[1]), name


@pytest.mark.parametrize("path", PLAIN,
                         ids=[os.path.basename(p) for p in PLAIN])
def test_plain_recorded_traces_reduce_as_before(path):
    """Traces recorded before the program had spans or scopes reduce to
    exactly the numbers they were recorded with; the phase helpers find
    nothing in them but idle outside every span."""
    rec, trace, scopes = _load(path)
    assert scopes == {}
    s = xplane.reduce(trace)
    assert s.busy_s == rec["expect"]["busy_s"]
    assert s.idle_share == rec["expect"]["idle_share"]
    assert phases.scope_busy_s(s, scopes) == {}
    assert phases.scoped_share(s, scopes) == 0.0
    assert phases.dispatch_fill(s) == (None, None)
    assert phases.phase_idle(s) == pytest.approx({
        phases.OUTSIDE: s.idle_share_within(s.resident_intervals())},
        rel=0, abs=1e-12)


def test_benchmark_extract_keeps_program_spans():
    """``xplane.extract`` keeps the program's ``aco.*`` host spans beside
    the benchmark's ``bench.*`` ones, and no other host event."""
    data = type("D", (), {"planes": [
        _Plane("/host:CPU", [_Line("python", [
            _Ev("aco.step", 0, 60, [("resident", 1)]),
            _Ev("bench.step", 0, 61), _Ev("PjitFunction(f)", 1, 2)])]),
        _Plane("/device:TPU:0", [
            _Line(xplane.MODULE_LINE, [_Ev("jit_f(1)", 5, 55)])])]})()
    spans = xplane.extract(data).spans
    assert [(n, a) for n, _, _, a in spans] == [
        ("aco.step", {"resident": 1}), ("bench.step", {})]


IDLE_READERS = {"prep_idle.serve": "aco.prep",
                "admit_idle.serve": "aco.admit",
                "dispatch_idle.serve": "aco.chunk_dispatch",
                "harvest_idle.serve": "aco.harvest"}
NEW_READERS = ("construct_device_ms.single", "deposit_device_ms.single",
               *IDLE_READERS, "slot_occupancy.serve", "pad_fill.serve")


def test_new_readers_on_the_synthetic_trace():
    trace, scopes = synthetic()
    ctx = {"summary": xplane.reduce(trace), "scopes": scopes,
           "iterations": 4}
    read = cells.reader
    assert read("construct_device_ms.single")(ctx) == pytest.approx(20 / 4)
    assert read("deposit_device_ms.single")(ctx) == pytest.approx(10 / 4)
    want = {"aco.admit": 3, "aco.prep": 5, "aco.chunk_dispatch": 3,
            "aco.harvest": 15}
    for name, span in IDLE_READERS.items():
        assert read(name)(ctx) == pytest.approx(want[span] / 90), name
    assert read("slot_occupancy.serve")(ctx) == pytest.approx(3 / 16)
    assert read("pad_fill.serve")(ctx) == pytest.approx(350 / 512)


@pytest.mark.parametrize("path", SCOPED,
                         ids=[os.path.basename(p) for p in SCOPED])
def test_new_readers_on_recorded_scopes(path):
    """The eight readers give the numbers the piece of a chip capture was
    recorded with; the four host-phase idle shares, ``aco.step``'s own
    and the idle outside every span add up to ``resident_idle.serve``."""
    rec, trace, scopes = _load(path)
    want = rec["expect_phases"]
    ctx = {"summary": xplane.reduce(trace), "scopes": scopes,
           "iterations": 2, "completed": 2, "chips": 1}
    got = {name: cells.reader(name)(ctx) for name in NEW_READERS}
    for sc in ("construct", "deposit"):
        v = want["scope_busy_s"].get(sc)
        assert got[f"{sc}_device_ms.single"] == (
            None if v is None else pytest.approx(v * 1e3 / 2, rel=1e-12))
    for name, span in IDLE_READERS.items():
        assert got[name] == pytest.approx(want["phase_idle"].get(span, 0.0),
                                          rel=1e-9, abs=1e-12), name
    rest = want["phase_idle"]["aco.step"] + want["phase_idle"][
        phases.OUTSIDE]
    assert abs(sum(got[n] for n in IDLE_READERS) + rest
               - cells.reader("resident_idle.serve")(ctx)) < 1e-9
    assert got["slot_occupancy.serve"] == pytest.approx(
        want["slot_occupancy"], rel=1e-12)
    assert got["pad_fill.serve"] == pytest.approx(want["pad_fill"],
                                                  rel=1e-12)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing(name):
    """No capture, or (for a colony scope) no scopes or no iterations:
    nothing to read."""
    assert cells.reader(name)({"summary": None}) is None
    if name.endswith(".single"):
        trace, scopes = synthetic()
        s = xplane.reduce(trace)
        assert cells.reader(name)({"summary": s, "scopes": None,
                                   "iterations": 4}) is None
        assert cells.reader(name)({"summary": s, "scopes": scopes,
                                   "iterations": 0}) is None

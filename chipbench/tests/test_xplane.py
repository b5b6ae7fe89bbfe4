"""The trace reduction, on small traces whose answers are known."""
import glob
import json
import os

import numpy as np
import pytest

from chipbench.harness import cells, xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
MS = 1_000_000


def synthetic():
    """Window 0..100 ms.  Device A runs ops [10,30] [20,40] [60,70]: busy
    40 ms.  Device B runs [0,20] [50,100]: busy 70 ms (clipped at 100).
    The host waits (nothing to serve) in [80, 100]; a step spans [0, 60]
    with a submit inside it at [42, 58]."""
    ops = {
        "/device:TPU:0": (np.array([[10, 30], [20, 40], [60, 70]]) * MS,
                          ["fusion.1", "scatter.2", "fusion.1"]),
        "/device:TPU:1": (np.array([[0, 20], [50, 110]]) * MS,
                          ["fusion.1", "while.3"]),
    }
    spans = [("bench.traced", 0, 100 * MS, {}),
             ("bench.step", 0, 60 * MS, {"resident": 2}),
             ("bench.submit", 42 * MS, 58 * MS, {}),
             ("bench.wait", 80 * MS, 100 * MS, {})]
    op_ns = {"/device:TPU:0": {"%fusion.1": 30.0 * MS, "%scatter.2": 20.0 * MS},
             "/device:TPU:1": {"%fusion.1": 20.0 * MS, "%while.3": 50.0 * MS}}
    return xplane.Trace(device_ops=ops, spans=spans, op_ns=op_ns)


def test_union_merges_and_clips():
    iv = np.array([[5, 10], [1, 3], [2, 4], [9, 12]], float)
    u = xplane.union(iv, 0, 11)
    np.testing.assert_array_equal(u, [[1, 4], [5, 11]])
    assert xplane.length(u) == 9


def test_intersect_and_complement():
    a = np.array([[0, 5], [10, 20]], float)
    b = np.array([[3, 12], [15, 30]], float)
    np.testing.assert_array_equal(xplane.intersect(a, b),
                                  [[3, 5], [10, 12], [15, 20]])
    np.testing.assert_array_equal(xplane.complement(a, 0, 25),
                                  [[5, 10], [20, 25]])


def test_busy_and_idle():
    s = xplane.reduce(synthetic())
    assert s.window_s == pytest.approx(0.1)
    per = s.busy_s_per_device()
    assert per["/device:TPU:0"] == pytest.approx(0.040)
    assert per["/device:TPU:1"] == pytest.approx(0.070)
    assert s.busy_s == pytest.approx(0.055)
    assert s.idle_share == pytest.approx(0.45)


def test_resident_window_idle():
    s = xplane.reduce(synthetic())
    res = s.resident_intervals()
    np.testing.assert_array_equal(res, [[0, 80 * MS]])
    # busy inside [0, 80]: A 40 ms, B 20 + 30 = 50 ms; mean 45 of 80
    assert s.idle_share_within(res) == pytest.approx(1 - 45 / 80)


def test_top_ops_per_device():
    s = xplane.reduce(synthetic())
    top = s.top_ops()
    assert [n for n, _ in top] == ["%fusion.1", "%while.3", "%scatter.2"]
    assert dict(top)["%fusion.1"] == pytest.approx((30 + 20) * 1e-3 / 2)
    assert dict(top)["%while.3"] == pytest.approx(50e-3 / 2)


def test_short_names():
    assert xplane.short_name("%fusion.12 = f32[8]{0} fusion(x)") == \
        "%fusion.12"
    assert xplane.short_name("jit_copy(123)") == "jit_copy(123)"


def test_gaps_labelled_by_innermost_span():
    s = xplane.reduce(synthetic())
    gaps = dict((n, v) for n, v in s.idle_gaps())
    # A idles [0,10] step, [40,60] step (mid 50 is in submit), [70,100]
    # (mid 85: wait); B idles [20,50] (mid 35: step).
    assert gaps["bench.step"] == pytest.approx((10 + 30) * 1e-3 / 2)
    assert gaps["bench.submit"] == pytest.approx(20e-3 / 2)
    assert gaps["bench.wait"] == pytest.approx(30e-3 / 2)


def test_readers_on_the_summary():
    s = xplane.reduce(synthetic())
    ctx = {"summary": s, "iterations": 11, "completed": 5, "chips": 2,
           "gen_late_s": [0.001, 0.002, 0.004], "queue_wait_s": [0.1],
           "window_compiles": 0}
    read = cells.reader
    assert read("iter_device_ms.single")(ctx) == pytest.approx(55 / 11)
    assert read("device_idle.single")(ctx) == pytest.approx(0.45)
    assert read("resident_idle.serve")(ctx) == pytest.approx(1 - 45 / 80)
    assert read("device_ms_per_req.serve")(ctx) == pytest.approx(110 / 5)
    assert read("window_compiles.serve")(ctx) == 0
    assert read("gen_late_p95_ms.serve")(ctx) == pytest.approx(
        np.quantile([1, 2, 4], 0.95))
    assert read("queue_wait_p95_ms.serve")(ctx) == pytest.approx(100)


def test_readers_find_nothing():
    ctx = {"summary": None, "iterations": 0, "completed": 0, "chips": 1}
    for name in ("iter_device_ms.single", "device_idle.single",
                 "resident_idle.serve", "device_ms_per_req.serve",
                 "gen_late_p95_ms.serve",
                 "queue_wait_p95_ms.serve"):
        assert cells.reader(name)(ctx) is None


def test_json_round_trip():
    t = synthetic()
    back = xplane.Trace.from_json(json.loads(json.dumps(t.to_json())))
    a, b = xplane.reduce(t), xplane.reduce(back)
    assert a.busy_s == b.busy_s and a.idle_gaps() == b.idle_gaps()


@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(DATA, "trace_*.json"))))
def test_recorded_chip_trace(path):
    """A piece of a trace recorded on the chip: the reduction's numbers
    agree with a direct computation on its events."""
    with open(path) as f:
        rec = json.load(f)
    t = xplane.Trace.from_json(rec["trace"])
    s = xplane.reduce(t)
    lo, hi = s.window_ns
    for d, (iv, _) in t.device_ops.items():
        grid = np.zeros(int((hi - lo) // 1000) + 1, bool)   # 1 us cells
        for a, b in np.clip(iv, lo, hi):
            grid[int((a - lo) // 1000):int((b - lo) // 1000)] = True
        assert s.busy_s_per_device()[d] == pytest.approx(
            grid.sum() * 1e-6, abs=len(iv) * 2e-6 + 2e-6)
    assert 0.0 <= s.idle_share <= 1.0
    assert sum(v for _, v in s.idle_gaps(100)) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9, abs=1e-12)
    for key, want in rec["expect"].items():
        assert getattr(s, key) == pytest.approx(want, rel=1e-9)

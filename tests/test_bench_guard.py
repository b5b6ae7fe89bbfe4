"""Bench manifest + regression guard tests (benchmarks/{manifest,regress}).

The guard must be trustworthy in both directions: committed-vs-committed
always passes (the --dry CI lane), and a tampered fresh value outside its
tolerance band is flagged.  These tests run against a synthetic bench
root so they are immune to the real BENCH files drifting.
"""
import json
import os

import pytest

from benchmarks import manifest, regress

STREAMING_PAYLOAD = {
    "benchmark": "streaming_throughput", "unix_time": 1,
    "rows": [
        {"mode": "drain", "ips": 20.0, "lat_mean_s": 0.5},
        {"mode": "streaming", "ips": 30.0, "lat_mean_s": 0.2},
    ],
    "summary": {"ips_ratio": 1.5, "lat_mean_ratio": 0.4,
                "tau_ratio_bf16": 2.0, "tau_ratio_int8": 3.5},
    "residency": [
        {"tau_dtype": "fp32", "state_bytes_per_slot": 4240,
         "slots_per_gb": 235849},
        {"tau_dtype": "bf16", "state_bytes_per_slot": 2192,
         "slots_per_gb": 456204},
        {"tau_dtype": "int8", "state_bytes_per_slot": 1296,
         "slots_per_gb": 771604},
    ],
}

OBS_PAYLOAD = {
    "benchmark": "obs_overhead", "unix_time": 2,
    "rows": [
        {"level": "off", "ips": 10.0, "lat_mean_s": 0.1,
         "occupancy_mean": 0.5},
        {"level": "events", "ips": 9.9, "lat_mean_s": 0.1},
        {"level": "full", "ips": 9.8, "lat_mean_s": 0.11},
        {"level": "serving", "ips": 9.7, "lat_mean_s": 0.12},
    ],
    "summary": {"full_vs_off_ips": 0.98, "overhead_pct": 2.0,
                "within_5pct": True, "serving_vs_off_ips": 0.97,
                "serving_overhead_pct": 3.0, "within_5pct_serving": True},
}


def _bench_root(tmp_path):
    root = str(tmp_path)
    with open(os.path.join(root, "BENCH_streaming.json"), "w") as f:
        json.dump(STREAMING_PAYLOAD, f)
    with open(os.path.join(root, "BENCH_obs.json"), "w") as f:
        json.dump(OBS_PAYLOAD, f)
    return root


def test_manifest_build_and_headlines(tmp_path):
    root = _bench_root(tmp_path)
    path = manifest.write_manifest(root=root)
    man = manifest.load_manifest(root=root)
    assert os.path.basename(path) == manifest.MANIFEST_NAME
    assert man["schema"] == manifest.SCHEMA
    st = man["benches"]["streaming"]
    assert st["present"] and st["unix_time"] == 1
    assert st["headline"]["ips_ratio"] == 1.5
    assert st["headline"]["streaming_ips"] == 30.0
    assert st["headline"]["drain_ips"] == 20.0
    ob = man["benches"]["obs"]["headline"]
    assert ob["serving_overhead_pct"] == 3.0
    assert ob["serving_ips"] == 9.7 and ob["off_occupancy_mean"] == 0.5
    # benches without files are listed as absent, not errors
    assert man["benches"]["solver"] == {"file": "BENCH_solver.json",
                                        "present": False}
    # corrupt payloads degrade to an extraction error, not a crash
    assert "_extract_error" in manifest.headline("streaming", {"rows": 7})
    assert manifest.headline("unknown-bench", {}) == {}


def test_regress_dry_passes_and_detects_drift(tmp_path, capsys):
    root = _bench_root(tmp_path)
    manifest.write_manifest(root=root)
    assert regress.run_checks(["streaming", "obs"], dry=True,
                              tol_scale=1.0, root=root) == 0
    assert regress.run_checks(["solver"], dry=True,
                              tol_scale=1.0, root=root) == 0  # absent→skip
    # a manifest whose stored headline disagrees with the committed file
    # is a plumbing error (stale index), not a silent pass
    man = manifest.load_manifest(root=root)
    man["benches"]["streaming"]["headline"]["ips_ratio"] = 9.9
    with open(os.path.join(root, manifest.MANIFEST_NAME), "w") as f:
        json.dump(man, f)
    assert regress.run_checks(["streaming"], dry=True,
                              tol_scale=1.0, root=root) == 3
    capsys.readouterr()


def test_regress_missing_manifest_is_plumbing_error(tmp_path):
    assert regress.run_checks(["streaming"], dry=True, tol_scale=1.0,
                              root=str(tmp_path)) == 3


@pytest.mark.parametrize("direction,committed,fresh,ok", [
    ("higher", 10.0, 7.0, True),     # within 35% band
    ("higher", 10.0, 6.0, False),    # below the floor
    ("lower", 1.0, 1.3, True),
    ("lower", 1.0, 1.5, False),
    ("match", 100.0, 101.0, True),
    ("match", 100.0, 140.0, False),
    ("match", 100.0, 60.0, False),   # match flags improvements too
])
def test_evaluate_tolerance_bands(direction, committed, fresh, ok):
    chk = regress.Check("x", "m", direction, rel=0.35, abs_slack=0.0)
    got, _ = regress.evaluate(chk, committed, fresh)
    assert got is ok


def test_evaluate_tol_scale_widens_band():
    chk = regress.Check("x", "m", "higher", rel=0.2)
    assert not regress.evaluate(chk, 10.0, 7.0)[0]
    assert regress.evaluate(chk, 10.0, 7.0, tol_scale=2.0)[0]


def test_regress_flags_regression_in_fresh_payload(tmp_path, monkeypatch):
    """End to end: a fresh run whose ips_ratio collapsed must exit 1."""
    root = _bench_root(tmp_path)
    manifest.write_manifest(root=root)
    bad = json.loads(json.dumps(STREAMING_PAYLOAD))
    bad["summary"]["ips_ratio"] = 0.5          # streaming now LOSES

    def fake_runner(out):
        with open(out, "w") as f:
            json.dump(bad, f)

    monkeypatch.setitem(regress.RUNNERS, "streaming", fake_runner)
    assert regress.run_checks(["streaming"], dry=False,
                              tol_scale=1.0, root=root) == 1


def test_run_parent_never_imports_jax():
    """benchmarks/run.py runs each table in its own process, and must not
    hold a device itself: importing it (and its manifest helper) leaves
    JAX unimported, and every table maps to a module of this package."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys\n"
            "from benchmarks import run\n"
            "assert 'jax' not in sys.modules, 'run.py imported jax'\n"
            "import os\n"
            "for module, _, _ in run.TABLES.values():\n"
            "    assert os.path.isfile(os.path.join('benchmarks', "
            "module + '.py')), module\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=root, timeout=120)
    assert out.returncode == 0, out.stderr

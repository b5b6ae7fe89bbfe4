"""chip_smoke.py refuses to run anywhere but on a TPU, and alone.

The smoke test's contract is that a run without an accelerator, or without
the repository beside the script, exits non-zero and prints no result line.
Both checks run as subprocesses under ``JAX_PLATFORMS=cpu``.
"""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=300)


def test_chip_smoke_fails_without_tpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    last = out.stderr.strip().splitlines()[-1]
    assert "no TPU" in last, out.stderr


def test_chip_smoke_fails_alone(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run(str(alone), str(tmp_path))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "sources are not beside this file" in out.stderr

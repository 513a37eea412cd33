"""chip_smoke.py refuses to run without a TPU: non-zero exit, no result
line, no CPU fallback."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert '"ok"' not in out.stdout
    assert "needs 1 TPU chip(s)" in out.stderr

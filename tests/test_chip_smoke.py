"""``chip_smoke.py`` refuses to run anywhere but on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    """On the CPU it exits non-zero before building any model, names the
    platform it found, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
    assert "PASS" not in proc.stdout and "granite" not in proc.stdout

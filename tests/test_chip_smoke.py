"""``chip_smoke.py`` has no CPU path: without a TPU it must exit
non-zero at once and never print its success line."""
import os
import subprocess
import sys
from pathlib import Path


def test_chip_smoke_refuses_cpu():
    root = Path(__file__).resolve().parents[1]
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr
    assert "[cache]" not in out.stdout      # refused before any work

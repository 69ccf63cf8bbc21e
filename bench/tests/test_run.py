"""The command refuses to measure without the chips its cell asks for,
and without the program beside it: a non-zero exit and no result."""
import os
import shutil
import subprocess
import sys

import harness

CELL = harness.load_json(harness.ROOT / "BENCHMARK.json")["workloads"][0]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL["name"],
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert '"correct": true' not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "No module named 'repro'" in p.stderr

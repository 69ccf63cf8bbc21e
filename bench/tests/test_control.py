"""The control for ``correct``: the plain reference computed one precision
step below the configuration's bf16 (float8 e4m3 matmul operands), read
at each position of the served prompts and tokens. Its widest gap has to
fail the limit that the program's sound runs pass.

As a test, at a tiny size on the CPU. On the chip, at the cell's own
size, over many seeds in one process (the readings the limit is set
from)::

    python3 bench/tests/test_control.py --workload lm3b-batch-decode \\
        --seconds 30 --seeds 11 12 13
"""
import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    TESTS = Path(__file__).resolve().parent
    sys.path[:0] = [str(TESTS), str(TESTS.parent),
                    str(TESTS.parents[1] / "src")]

import harness  # noqa: E402

SERVE = harness.load_module("drivers", "serve")


def readings(cell, seeds, seconds, devices) -> list[dict]:
    """Per seed: the control's verdict and checks (the control's tokens
    in the served tokens' place), the program's own checks over the
    same sample of served requests, and the run's own numbers."""
    out = []
    for seed in seeds:
        result, checks = SERVE.run(cell, seed, seconds, False, devices,
                                   harness.CompileCounter(), control="fp8")
        row = {"seed": seed, "correct": result["correct"],
               "attempted": result["attempted"],
               "failed": result["failed"],
               "metrics": {k: v["value"]
                           for k, v in result["metrics"].items()},
               "memory_peak_bytes": result["device"]["memory_peak_bytes"],
               "control_checks": {n: v for n, v, _ in checks},
               "program_checks": result["program_checks"]}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def test_control_fails_where_the_program_passes():
    import jax
    import tiny
    cell = tiny.tiny_cell()
    limit = cell.config["correct"]["max_gap_logits"]
    (row,) = readings(cell, [2**31 + 77], 3.0, jax.devices()[:1])
    assert row["program_checks"]["max_served_logit_gap"] <= limit
    assert row["control_checks"]["max_served_logit_gap"] > limit
    assert row["correct"] is False


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.keep_logs_in_checkout()
    harness.enable_compile_cache()
    readings(cell, args.seeds, args.seconds,
             harness.require_tpu(cell.chips))

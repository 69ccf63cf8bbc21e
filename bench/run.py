"""One run of one benchmark cell:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for. Without them it exits non-zero and prints no result. The last
line of stdout is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared for ``correct`` beside its limit.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse(argv)
    harness.keep_logs_in_checkout()
    import repro  # noqa: F401  the program under test, beside bench/
    cell = harness.load_cell(args.workload)
    harness.enable_compile_cache()
    devices = harness.require_tpu(cell.chips)
    driver = harness.load_module("drivers", cell.config["kind"])
    result, checks = driver.run(cell, args.seed, args.seconds,
                                bool(args.trace), devices,
                                harness.CompileCounter())
    harness.emit(result, checks)


if __name__ == "__main__":
    main()

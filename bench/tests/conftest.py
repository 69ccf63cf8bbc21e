"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
repository root, on the CPU."""
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
sys.path[:0] = [str(TESTS), str(TESTS.parent), str(TESTS.parents[1] / "src")]

"""The serving driver's closed loop: the steady state it pre-rolls into,
and the window's count of the step that straddles its close."""
import time

import numpy as np
import pytest

import harness
import trafficgen

SERVE = harness.load_module("drivers", "serve")
BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_staggered_start_is_what_admission_leaves(w):
    cell = harness.load_cell(w["name"], BENCH)
    e = cell.config["engine"]
    pairs = [tuple(map(int, p)) for p in
             trafficgen.length_pairs(cell.traffic)]
    flight, nxt = SERVE.staggered_start(pairs, e, 2 * e["max_batch"])

    def pages(i):
        p, o = pairs[i]
        return -(-min(p + o - 1, e["max_len"]) // e["page_size"])
    assert 0 < len(flight) <= e["max_batch"]
    assert [i for i, _ in flight] == sorted(i for i, _ in flight)
    assert all(0 <= k < pairs[i][1] for i, k in flight)
    # lanes are staggered: progress spreads across the outputs
    assert len({k for _, k in flight}) >= len(flight) - 2
    used = sum(pages(i) for i, _ in flight)
    assert used <= e["n_pages"]
    # the next request waits for a lane or for pages
    assert len(flight) == e["max_batch"] or \
        used + pages(nxt) > e["n_pages"]
    # every request admitted before ``nxt`` and not in flight finished
    assert nxt - len(flight) >= 2 * e["max_batch"]


def test_staggered_start_by_hand():
    e = {"page_size": 4, "max_batch": 2, "n_pages": 100, "max_len": 64}
    # lane 0 takes 3 tokens, lane 1 5; after 2 finish (steps 3 and 5),
    # request 2 (started at step 3) has emitted 2, request 3 none
    pairs = [(1, 3), (1, 5), (1, 9), (1, 9)]
    assert SERVE.staggered_start(pairs, e, 2) == ([(2, 2), (3, 0)], 4)
    # a pool of 5 pages holds one request of 4 pages at a time
    e["n_pages"] = 5
    pairs = [(1, 13), (1, 9), (1, 9)]
    assert SERVE.staggered_start(pairs, e, 1) == ([(1, 0)], 2)


class _FakeEngine:
    """Steps of fixed length, each emitting ``per_step`` tokens."""

    def __init__(self, step_s, per_step):
        self.step_s, self.per_step = step_s, per_step
        self.scheduler, self.stats = [], {"generated_tokens": 0}

    def submit(self, prompt, new):
        self.scheduler.append(1)
        return len(self.stats) + len(self.scheduler)

    def step(self):
        time.sleep(self.step_s)
        self.stats["generated_tokens"] += self.per_step
        return []


def test_window_counts_the_straddling_step_by_its_share():
    class Off:
        on = False
    eng = _FakeEngine(0.3, 30)
    reqs = [(np.zeros(4, np.int32), 4)]
    win = SERVE.run_window(eng, reqs, 0, {"loop": "closed",
                                          "queue_per_lane": 0}, 1, 1.0,
                           False, Off(), {})
    # four steps began in the window; the fourth is a third inside it
    assert win.counters["generated_tokens"] == 120
    assert win.seconds == 1.0
    assert win.tokens == pytest.approx(100, abs=6)

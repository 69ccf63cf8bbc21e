"""The one traffic generator: turns a mix file (``bench/traffic/*.json``)
and a seed into requests.

Every seed gets the same list of (prompt length, output length) pairs
in the same order; the seed draws the token ids (uniform over the
vocabulary). Request i takes its prompt length at the mix's quantile
``halton(i, 2)`` and its output length at ``halton(i, 3)``: a
two-dimensional low-discrepancy sequence, so every stretch of the list
spreads over both distributions and their pairings.

Why one order for every seed: a window finishes a few tens of requests,
and the engine's decode step costs what its longest lane's read-page
bucket costs, so the window's work turns on when the few long requests
run. Orders drawn from the seed, even of the same set of sizes, change
that work from seed to seed (PERF.md, section 6).

A closed loop (``"loop": "closed"``) keeps ``queue_per_lane`` requests
per engine lane waiting; the driver cycles through the list.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def halton(i: int, base: int) -> float:
    """The radical inverse of ``i`` in ``base``: in (0, 1) for i >= 1."""
    f, r = 1.0, 0.0
    while i > 0:
        f /= base
        r += f * (i % base)
        i //= base
    return r


def lengths(spec: dict, quantiles) -> np.ndarray:
    """Lengths at the given quantiles of ``spec``'s distribution,
    clipped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = NormalDist()
    vals = [spec["median"] * math.exp(spec["sigma"] * z.inv_cdf(u))
            for u in quantiles]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def length_pairs(mix: dict) -> np.ndarray:
    """(requests, 2) prompt and output lengths, in order: the same for
    every seed."""
    idx = range(1, mix["requests"] + 1)
    prompt = lengths(mix["prompt_tokens"], [halton(i, 2) for i in idx])
    output = lengths(mix["output_tokens"], [halton(i, 3) for i in idx])
    return np.stack([prompt, output], axis=1)


def requests(mix: dict, seed: int, vocab: int
             ) -> list[tuple[np.ndarray, int]]:
    """[(prompt token ids int32, max new tokens)], in the list's order."""
    if mix.get("shared_prefix_tokens", 0) or mix.get("eos", False):
        raise ValueError("this generator makes unshared prompts without eos")
    rng = np.random.default_rng([seed, 2])
    return [(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in length_pairs(mix)]

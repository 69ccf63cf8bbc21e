"""The generator: the same requests for a seed, the same lengths in the
same order for another seed, at the mix's quantiles, with every prefix
of the list spread over them."""
import numpy as np

import harness
import trafficgen

MIX = harness.load_json(harness.BENCH / "traffic" / "offline-batch.json")
BIG = 2**31 + 987_654_321


def test_deterministic_per_seed():
    a = trafficgen.requests(MIX, BIG, 50304)
    b = trafficgen.requests(MIX, BIG, 50304)
    assert len(a) == MIX["requests"]
    assert all(np.array_equal(p, q) and m == n
               for (p, m), (q, n) in zip(a, b))
    c = trafficgen.requests(MIX, BIG + 1, 50304)
    assert not all(np.array_equal(p, q) for (p, _), (q, _) in zip(a, c))


def test_same_work_for_every_seed():
    def pairs(seed):
        return [(p.size, n) for p, n in trafficgen.requests(MIX, seed, 50304)]
    assert pairs(3) == pairs(BIG)


def test_prefixes_spread():
    # the first 32 requests, what a window finishes, span both ranges
    lp = trafficgen.length_pairs(MIX)[:32]
    for col, spec in ((0, MIX["prompt_tokens"]), (1, MIX["output_tokens"])):
        v = np.sort(lp[:, col])
        assert abs(np.median(v) / spec["median"] - 1) < 0.1
        assert v[0] < spec["median"] * 0.6 and v[-1] > spec["median"] * 1.6
    # prompt and output lengths are not tied to each other
    assert abs(np.corrcoef(lp[:, 0], lp[:, 1])[0, 1]) < 0.3


def test_percentiles():
    lp = trafficgen.length_pairs(MIX)
    for col, spec in ((0, MIX["prompt_tokens"]), (1, MIX["output_tokens"])):
        v = lp[:, col]
        assert v.min() >= spec["min"] and v.max() <= spec["max"]
        assert abs(np.median(v) - spec["median"]) <= 1
        # the lognormal's 84th percentile: median * exp(sigma), unclipped
        q84 = np.quantile(v, 0.8413)
        assert abs(q84 / (spec["median"] * np.exp(spec["sigma"])) - 1) < 0.02
    assert lp[:, 0].min() == 32 and lp[:, 0].max() == 512
    assert lp[:, 1].min() == 128


def test_token_ids_in_vocab():
    for p, _ in trafficgen.requests(MIX, 7, 50304):
        assert p.dtype == np.int32 and p.min() >= 0 and p.max() < 50304

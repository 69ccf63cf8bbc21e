"""Continuous-batching serving engine (serving/engine.py):

  * greedy parity — equal-length batches are BITWISE-identical to the
    token-by-token ``serve_loop.generate`` oracle for every slab size
    K ∈ {1, 4, 16};
  * ragged prompt lengths — right-aligned group prefill + per-lane
    position offsets reproduce each sequence's solo generation exactly;
  * per-lane frontiers — a freed lane resets its OWN frontier to 0 and
    admits the next request immediately (no waiting for batch drain);
  * mid-slab stops — eos, budget exhaustion, and cache-end truncation
    inside a slab are masked on-device and discarded on the host;
  * admission under queue pressure — more requests than lanes drain
    FIFO and all complete, identically across slab sizes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_cfg
from repro.models import registry
from repro.serving import engine, serve_loop
from repro.serving.scheduler import FIFOScheduler, Request


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(int(p),))
            .astype(np.int32) for p in lens]


@pytest.mark.parametrize("slab_k", [1, 4, 16])
def test_equal_length_bitwise_parity_with_oracle(model, slab_k):
    cfg, params = model
    B, P, NEW = 3, 8, 6
    prompts = jnp.asarray(np.stack(_prompts(cfg, [P] * B)))
    want, _ = serve_loop.generate(cfg, params, prompts,
                                  max_new_tokens=NEW)
    got, stats = engine.generate(cfg, params, np.asarray(prompts),
                                 max_new_tokens=NEW, prefill_chunk=4,
                                 slab_k=slab_k)
    np.testing.assert_array_equal(np.stack(got), np.asarray(want))
    # chunked batched prefill, not a per-token Python loop:
    assert stats["prefill_chunks"] == -(-P // 4)
    # the host syncs once per SLAB: O(tokens/K) dispatches, not O(tokens)
    assert stats["decode_slabs"] == -(-(NEW - 1) // slab_k)
    assert stats["decode_tokens"] == B * (NEW - 1)


@pytest.mark.parametrize("slab_k", [1, 4])
def test_ragged_prompts_match_solo_generation(model, slab_k):
    cfg, params = model
    NEW, MAXLEN = 5, 20
    prompts = _prompts(cfg, [5, 8, 3, 7])
    got, _ = engine.generate(cfg, params, prompts, max_new_tokens=NEW,
                             max_len=MAXLEN, prefill_chunk=4,
                             slab_k=slab_k)
    for p, g in zip(prompts, got):
        want, _ = serve_loop.generate(cfg, params, jnp.asarray(p)[None],
                                      max_new_tokens=NEW, max_len=MAXLEN)
        np.testing.assert_array_equal(g, np.asarray(want)[0])


def test_prefill_logits_hook_reports_each_request(model):
    """The hook sees, per request, the prefill logits whose argmax is
    that request's first served token, and matches the dense forward
    at the prompt's last position."""
    cfg, params = model
    prompts = _prompts(cfg, [5, 8, 3])
    eng = engine.Engine(cfg, params, max_batch=2, max_len=16,
                        prefill_chunk=4)
    seen = {}
    eng.prefill_logits_hook = seen.update
    uids = [eng.submit(p, 3) for p in prompts]
    res = eng.run()
    assert sorted(seen) == sorted(uids)
    for u, p in zip(uids, prompts):
        got = np.asarray(seen[u], np.float32)
        assert int(got.argmax()) == int(res[u].generated[0])
        want, _ = registry.forward(cfg, params, jnp.asarray(p)[None],
                                   masks=None)
        np.testing.assert_allclose(got, np.asarray(want[0, -1], np.float32),
                                   atol=2e-2 * float(np.abs(got).max()))


def test_slab_sizes_bitwise_identical_under_continuous_admission(model):
    """Ragged continuous-admission workload: 6 requests over 2 lanes
    with different budgets — the slab engine (K=4, 16) must emit exactly
    the per-token engine's (K=1) tokens for every request."""
    cfg, params = model
    prompts = _prompts(cfg, [6, 3, 5, 7, 4, 6], seed=7)
    budgets = (3, 9, 5, 2, 7, 4)

    def run(k):
        eng = engine.Engine(cfg, params, max_batch=2, max_len=32,
                            prefill_chunk=4, slab_k=k)
        uids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        res = eng.run()
        return uids, res

    uids1, base = run(1)
    for k in (4, 16):
        uids, res = run(k)
        assert uids == uids1
        for u in uids:
            np.testing.assert_array_equal(res[u].tokens, base[u].tokens)
            assert res[u].truncated == base[u].truncated


def test_mid_slab_budget_exhaustion_and_lane_masking(model):
    """Budgets that end mid-slab (K=16 ≫ budgets): finished lanes are
    masked on-device, their trailing slab tokens discarded, and each
    request still matches its solo oracle generation."""
    cfg, params = model
    prompts = _prompts(cfg, [6, 6, 4], seed=2)
    budgets = (3, 7, 5)
    eng = engine.Engine(cfg, params, max_batch=3, max_len=32,
                        prefill_chunk=4, slab_k=16)
    uids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    res = eng.run()
    for uid, p, n in zip(uids, prompts, budgets):
        assert res[uid].generated.size == n
        want, _ = serve_loop.generate(cfg, params, jnp.asarray(p)[None],
                                      max_new_tokens=n, max_len=32)
        np.testing.assert_array_equal(res[uid].tokens,
                                      np.asarray(want)[0])
    # all budgets fit in one slab: exactly one host sync for decode
    assert eng.stats["decode_slabs"] == 1


def test_mid_slab_eos(model):
    """A lane emitting eos inside a slab stops there — identical cut to
    the per-token engine, and the eos token itself is kept."""
    cfg, params = model
    prompts = _prompts(cfg, [5, 7], seed=4)
    # pick as eos a token the second request actually emits mid-stream
    free, _ = engine.generate(cfg, params, prompts, max_new_tokens=10,
                              max_len=32, slab_k=1)
    plen = prompts[1].size
    eos = int(free[1][plen + 4])

    def run(k):
        eng = engine.Engine(cfg, params, max_batch=2, max_len=32,
                            prefill_chunk=4, slab_k=k, eos_id=eos)
        uids = [eng.submit(p, 10) for p in prompts]
        return uids, eng.run()

    uids1, base = run(1)
    uidsk, slab = run(8)
    assert uids1 == uidsk
    for u in uids1:
        np.testing.assert_array_equal(slab[u].tokens, base[u].tokens)
    stopped = slab[uids1[1]]
    assert stopped.generated[-1] == eos
    assert stopped.generated.size <= 5 + 1   # cut at the eos emission


def test_per_lane_frontier_reuse_after_eviction(model):
    """With per-lane frontiers, a freed lane restarts at slot 0 and
    takes the next queued request IMMEDIATELY — while the other lane
    keeps decoding (the old shared frontier only reset on batch drain)."""
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=32,
                        prefill_chunk=4, slab_k=1)
    prompts = _prompts(cfg, [6, 6, 4], seed=1)
    uids = [eng.submit(p, n) for p, n in zip(prompts, (2, 12, 4))]
    res = {}
    # run until the short request finishes and the queued one is admitted
    while len(eng.scheduler):
        for r in eng.step():
            res[r.uid] = r
    assert eng.stats["admitted"] == 3
    # the reused lane restarted its own frontier behind the running lane
    fr = eng.frontiers
    busy = [i for i in eng.active_lanes
            if eng.lanes[i].req.uid == uids[1]]
    fresh = [i for i in eng.active_lanes
             if eng.lanes[i].req.uid == uids[2]]
    assert busy and fresh
    assert fr[fresh[0]] < fr[busy[0]]
    res.update(eng.run())
    for uid, p, n in zip(uids, prompts, (2, 12, 4)):
        want, _ = serve_loop.generate(cfg, params, jnp.asarray(p)[None],
                                      max_new_tokens=n, max_len=32)
        np.testing.assert_array_equal(res[uid].tokens,
                                      np.asarray(want)[0])


def test_slot_eviction_and_reuse(model):
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=32,
                        prefill_chunk=4, slab_k=4)
    # different budgets -> lanes free at different steps; 4 requests
    # over 2 lanes forces reuse of evicted slots
    prompts = _prompts(cfg, [6, 6, 4, 5])
    uids = [eng.submit(p, n) for p, n in zip(prompts, (3, 7, 4, 6))]
    res = eng.run()
    assert sorted(res) == sorted(uids)
    assert eng.stats["evicted"] == 4 and eng.stats["admitted"] == 4
    assert eng.active_lanes == [] and len(eng.scheduler) == 0
    for uid, p, n in zip(uids, prompts, (3, 7, 4, 6)):
        assert res[uid].generated.size == n
        want, _ = serve_loop.generate(cfg, params, jnp.asarray(p)[None],
                                      max_new_tokens=n, max_len=32)
        np.testing.assert_array_equal(res[uid].tokens,
                                      np.asarray(want)[0])


def test_truncation_at_cache_end_mid_slab(model):
    """A lane that runs out of cache slots mid-slab is truncated at
    exactly the same token as with per-token decode."""
    cfg, params = model
    prompts = _prompts(cfg, [6, 3], seed=5)

    def run(k):
        eng = engine.Engine(cfg, params, max_batch=2, max_len=10,
                            prefill_chunk=4, slab_k=k)
        uids = [eng.submit(p, 16) for p in prompts]
        return uids, eng.run(), eng.stats["truncated"]

    uids1, base, tr1 = run(1)
    uidsk, slab, trk = run(8)
    assert tr1 == trk == 2        # both lanes hit max_len before budget
    for u in uids1:
        assert slab[u].truncated and base[u].truncated
        np.testing.assert_array_equal(slab[u].tokens, base[u].tokens)


def test_admission_under_queue_pressure(model):
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=24,
                        prefill_chunk=4, slab_k=2)
    prompts = _prompts(cfg, [4, 4, 4, 4, 4])
    uids = [eng.submit(p, 4) for p in prompts]
    assert len(eng.scheduler) == 5
    eng.step()
    # only max_batch lanes admitted; the rest wait in the FIFO queue
    assert eng.stats["admitted"] == 2 and len(eng.scheduler) == 3
    res = eng.run()
    assert sorted(res) == sorted(uids)
    assert eng.stats["admitted"] == 5
    for uid, p in zip(uids, prompts):
        want, _ = serve_loop.generate(cfg, params, jnp.asarray(p)[None],
                                      max_new_tokens=4, max_len=24)
        np.testing.assert_array_equal(res[uid].tokens,
                                      np.asarray(want)[0])


def test_local_global_pattern_parity():
    """Paired local/global stacks (gemma2-style) through the engine:
    chunked prefill + per-lane slab decode must match the oracle too."""
    cfg = tiny_cfg(layer_pattern="local_global", sliding_window=4,
                   attn_logit_softcap=50.0, final_logit_softcap=30.0,
                   scale_embeddings=True, tie_embeddings=True)
    params = registry.init_params(cfg, jax.random.PRNGKey(1))
    prompts = _prompts(cfg, [8, 8], seed=3)
    want, _ = serve_loop.generate(cfg, params,
                                  jnp.asarray(np.stack(prompts)),
                                  max_new_tokens=5)
    got, _ = engine.generate(cfg, params, prompts, max_new_tokens=5,
                             prefill_chunk=4, slab_k=4)
    np.testing.assert_array_equal(np.stack(got), np.asarray(want))


def test_scheduler_rules():
    s = FIFOScheduler(max_batch=4, max_len=16)
    with pytest.raises(ValueError):      # prompt can never fit
        s.submit(Request(0, np.zeros(16, np.int32), 4))
    s.submit(Request(1, np.zeros(8, np.int32), 4))
    s.submit(Request(2, np.zeros(2, np.int32), 4))
    s.submit(Request(3, np.zeros(2, np.int32), 4))
    # per-lane frontiers: free lanes admit the FIFO prefix immediately
    got = s.admit(n_free=2)
    assert [r.uid for r in got] == [1, 2]
    assert len(s) == 1
    assert [r.uid for r in s.admit(n_free=2)] == [3]


def test_engine_rejects_non_kv_families(model):
    cfg, _ = model
    bad = dataclasses.replace(cfg, family="ssm")
    with pytest.raises(NotImplementedError):
        engine.Engine(bad, {}, max_batch=1, max_len=8)


@pytest.mark.parametrize("mixed", [False, True])
def test_reset_stats_and_observability_counters(model, mixed):
    """Scheduler observability (queue depth high-water, page-gate
    rejections, queued time) and the mixed-batching counters (fused
    steps, stall counter, TTFT/ITL percentiles) are tracked under BOTH
    scheduling modes and all cleared by reset_stats."""
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=3, max_len=32,
                        prefill_chunk=4, slab_k=2, page_size=4,
                        n_pages=4, mixed=mixed)   # pool fits one at a time
    for p in _prompts(cfg, [8, 8, 8], seed=9):
        eng.submit(p, 5)
    assert eng.stats["queue_depth_peak"] == 3
    eng.step()                      # one admits; the page gate blocks two
    assert eng.stats["admitted"] == 1
    assert eng.scheduler.rejections >= 1
    eng.run()
    st = eng.stats
    assert st["admission_rejections"] >= 1
    assert st["queued_s_total"] >= st["queued_s_max"] >= 0.0
    assert st["ttft_p95_s"] >= st["ttft_p50_s"] > 0.0
    if mixed:
        # serialized admissions never overlap running decode: the
        # fused step fires per admission, decode is never stalled
        assert st["mixed_steps"] >= 3
        assert st["stalled_decode_steps"] == 0
    else:
        assert st["mixed_steps"] == 0
    eng.reset_stats()
    for key in ("queue_depth_peak", "admission_rejections",
                "queued_s_total", "queued_s_max", "mixed_steps",
                "mixed_s", "stalled_decode_steps", "prefill_chunks",
                "decode_tokens"):
        assert not eng.stats[key], key
    assert eng.scheduler.rejections == 0
    assert eng._ttft == [] and eng._itl == []


@pytest.mark.parametrize("kind", ["paged", "contiguous", "mixed"])
def test_step_donates_the_cache(model, kind):
    """Every jitted call that takes the KV cache donates it: after an
    engine step the arrays the engine held before it are deleted (the
    device updated them in place) and the engine holds live ones."""
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=32,
                        prefill_chunk=4, slab_k=2, page_size=4,
                        paged=kind != "contiguous", mixed=kind == "mixed")
    for p in _prompts(cfg, [5, 7], seed=4):
        eng.submit(p, 6)
    for _ in range(3):
        before = jax.tree_util.tree_leaves(eng.cache)
        eng.step()
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted()
                       for a in jax.tree_util.tree_leaves(eng.cache))

"""Paged KV cache: shared page pool + block-table attention
(serving/engine.py paged=True, models/attention.py paged_*_attention,
serving/pages.py, kernels/paged_attention.py):

  * greedy decode through the paged path is BITWISE-identical to the
    contiguous engine and the ``serve_loop`` oracle for every slab size
    K ∈ {1, 4, 16}, including ragged admission and mid-slab eviction /
    readmission whose frontiers cross page boundaries;
  * a prompt longer than any contiguous per-lane extent (up to pool
    capacity) is admitted and completes — the ``max_batch × max_len``
    memory cap is gone, total context is bounded by pool pages;
  * admission is gated on FREE PAGES (a group that would overdraw the
    pool waits in FIFO order) and ``Engine.submit`` rejects requests
    that could never fit, with a page-units error;
  * the block-table gather reads strictly fewer pages than a dense
    ``max_len`` read at short live lengths;
  * the Pallas blocked-gather decode kernel (interpret mode) matches
    the XLA gather oracle, standalone and through the engine.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_cfg
from repro.models import attention as attn
from repro.models import registry
from repro.serving import engine, serve_loop
from repro.serving.pages import PagePool


@pytest.fixture(scope="module")
def model():
    cfg = tiny_cfg()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(int(p),))
            .astype(np.int32) for p in lens]


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("slab_k", [1, 4, 16])
def test_paged_bitwise_parity_with_oracle_and_contiguous(model, slab_k):
    cfg, params = model
    B, P, NEW = 3, 8, 6
    prompts = _prompts(cfg, [P] * B)
    want, _ = serve_loop.generate(cfg, params,
                                  jnp.asarray(np.stack(prompts)),
                                  max_new_tokens=NEW)
    dense, _ = engine.generate(cfg, params, prompts, max_new_tokens=NEW,
                               prefill_chunk=4, slab_k=slab_k,
                               paged=False)
    paged, _ = engine.generate(cfg, params, prompts, max_new_tokens=NEW,
                               prefill_chunk=4, slab_k=slab_k,
                               paged=True, page_size=4)
    np.testing.assert_array_equal(np.stack(paged), np.asarray(want))
    np.testing.assert_array_equal(np.stack(paged), np.stack(dense))


@pytest.mark.parametrize("slab_k", [1, 4, 16])
def test_paged_ragged_eviction_readmission_across_page_boundary(
        model, slab_k):
    """6 ragged requests over 2 lanes, page_size=4: frontiers cross page
    boundaries mid-slab, lanes are evicted and readmitted onto recycled
    pages — every request must match the per-token contiguous engine."""
    cfg, params = model
    prompts = _prompts(cfg, [6, 3, 5, 7, 4, 6], seed=7)
    budgets = (3, 9, 5, 2, 7, 4)

    def run(paged, k, **kw):
        eng = engine.Engine(cfg, params, max_batch=2, max_len=32,
                            prefill_chunk=4, slab_k=k, paged=paged, **kw)
        uids = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
        return uids, eng.run()

    uids1, base = run(False, 1)
    uidsp, res = run(True, slab_k, page_size=4, n_pages=16)
    assert uids1 == uidsp
    for u in uids1:
        np.testing.assert_array_equal(res[u].tokens, base[u].tokens)
        assert res[u].truncated == base[u].truncated


def test_paged_truncation_parity_with_contiguous(model):
    """Lanes that hit the slot cap mid-slab truncate at exactly the
    contiguous engine's token, even when the cap is page-interior."""
    cfg, params = model
    prompts = _prompts(cfg, [6, 3], seed=5)

    def run(paged):
        eng = engine.Engine(cfg, params, max_batch=2, max_len=10,
                            prefill_chunk=4, slab_k=8, paged=paged,
                            **({"page_size": 4} if paged else {}))
        uids = [eng.submit(p, 16) for p in prompts]
        return uids, eng.run(), eng.stats["truncated"]

    uids, base, tr_d = run(False)
    uidsp, res, tr_p = run(True)
    assert tr_d == tr_p == 2
    for u in uids:
        assert res[u].truncated
        np.testing.assert_array_equal(res[u].tokens, base[u].tokens)


# ----------------------------------------------------- capacity semantics
def test_long_prompt_beyond_contiguous_lane_extent(model):
    """Pool of 64 slots over 2 lanes: a contiguous cache with the same
    memory would cap every lane at 32 slots. The paged engine admits a
    40-token prompt (+8 decode) in ONE lane and completes it exactly —
    total context is bounded by pool pages, not max_batch × max_len."""
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=60,
                        prefill_chunk=8, slab_k=4, paged=True,
                        page_size=4, n_pages=16)
    long_p = _prompts(cfg, [40], seed=3)[0]
    uid = eng.submit(long_p, 8)
    res = eng.run()
    assert res[uid].generated.size == 8 and not res[uid].truncated
    want, _ = serve_loop.generate(cfg, params, jnp.asarray(long_p)[None],
                                  max_new_tokens=8, max_len=60)
    np.testing.assert_array_equal(res[uid].tokens, np.asarray(want)[0])


def test_submit_rejects_oversized_request_in_page_units(model):
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=60,
                        prefill_chunk=8, slab_k=4, paged=True,
                        page_size=4, n_pages=8)
    with pytest.raises(ValueError, match=r"10 pages .* only 8 pages"):
        eng.submit(np.ones(20, np.int32), 20)
    with pytest.raises(ValueError, match="cannot fit"):
        eng.submit(np.ones(60, np.int32), 4)
    # a feasible request still goes through
    eng.submit(np.ones(8, np.int32), 4)
    assert len(eng.scheduler) == 1


def test_zero_budget_request_rejected(model):
    """max_new_tokens=0 must be rejected at submit: prefill writes the
    full group width, so a zero budget would under-pin pages (cost is
    width + budget - 1 slots) and scatter into pool page 0 — which may
    belong to a LIVE lane (cross-lane KV corruption)."""
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=32,
                        prefill_chunk=4, slab_k=4, paged=True,
                        page_size=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.ones(5, np.int32), 0)


def test_admission_gated_on_free_pages(model):
    """3 requests over 3 free lanes but a pool that only fits one at a
    time: admission serialises on pages (strict FIFO), all complete."""
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=3, max_len=32,
                        prefill_chunk=4, slab_k=2, paged=True,
                        page_size=4, n_pages=4)  # 16 slots total
    prompts = _prompts(cfg, [8, 8, 8], seed=9)
    uids = [eng.submit(p, 5) for p in prompts]
    eng.step()
    assert eng.stats["admitted"] == 1 and len(eng.scheduler) == 2
    res = eng.run()
    assert sorted(res) == sorted(uids)
    for uid, p in zip(uids, prompts):
        want, _ = serve_loop.generate(cfg, params, jnp.asarray(p)[None],
                                      max_new_tokens=5, max_len=32)
        np.testing.assert_array_equal(res[uid].tokens,
                                      np.asarray(want)[0])


def test_page_reads_scale_with_frontier_not_max_len(model):
    """Short live contexts under a huge max_len: the block-table gather
    must touch strictly fewer pages than a dense max_len read — and the
    paged peak cache bytes must undercut the contiguous slab."""
    cfg, params = model
    prompts = _prompts(cfg, [8, 8], seed=1)
    _, st = engine.generate(cfg, params, prompts, max_new_tokens=8,
                            max_len=256, prefill_chunk=4, slab_k=4,
                            paged=True, page_size=4, n_pages=16)
    assert st["pages_read"] > 0
    assert st["pages_read"] < st["pages_read_dense_equiv"]
    assert st["peak_kv_bytes"] < st["kv_bytes_contiguous_equiv"]


# ----------------------------------------------------------- pool plumbing
def test_page_pool_free_list():
    pool = PagePool(6, 4)
    a = pool.alloc(3)
    assert a == [0, 1, 2] and pool.free_pages == 3 and pool.in_use == 3
    pool.release(a)
    assert pool.free_pages == 6
    b = pool.alloc(2)
    assert b == [0, 1]              # freed pages recycled, low-first
    assert pool.peak_in_use == 3
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(5)
    assert pool.slots_for(9) == 3


def test_paged_write_drops_parked_and_masked_lanes():
    """A parked lane (slot >= max_pages*ps) and a lane_mask'ed lane must
    NOT write — a clamped index would corrupt pool page 0, which may
    belong to another lane."""
    pool = jnp.zeros((1, 3, 4, 1, 2), jnp.float32)   # one layer
    bt = jnp.asarray([[1, 2], [0, 0]], jnp.int32)
    vals = jnp.ones((2, 1, 2), jnp.float32)
    out = attn.paged_write(pool, bt, jnp.asarray([8, 8]), vals,
                           layer=0)                          # parked
    assert float(jnp.abs(out).sum()) == 0.0
    out = attn.paged_write(pool, bt, jnp.asarray([0, 0]), vals,
                           lane_mask=jnp.asarray([True, False]),
                           layer=0)[0]
    assert float(jnp.abs(out[1]).sum()) == 1.0 * 2   # lane 0 -> page 1
    assert float(jnp.abs(out[0]).sum()) == 0.0       # lane 1 dropped


def test_block_table_state_roundtrips_through_slab(model):
    cfg, params = model
    eng = engine.Engine(cfg, params, max_batch=2, max_len=16,
                        prefill_chunk=4, slab_k=2, paged=True,
                        page_size=4)
    eng.submit(_prompts(cfg, [5], seed=2)[0], 6)
    eng.step()
    bt = eng.block_tables
    assert bt.shape == (2, 4)
    # lane 0 owns ceil(min(5+6-1, 16)/4) = 3 distinct pool pages
    owned = bt[0][:3]
    assert len(set(owned.tolist())) == 3
    eng.run()
    assert eng.pool.free_pages == eng.pool.n_pages   # all released


# ------------------------------------------------------------ pallas kernel
def test_paged_flash_decode_kernel_matches_xla_gather():
    """The blocked-gather Pallas kernel (interpret mode) against the
    gather + dense-core oracle, with ragged offsets, garbage in
    unallocated pages, and a sliding window crossing page boundaries."""
    from repro.kernels import paged_attention as pk
    cfg = tiny_cfg()
    rng = np.random.default_rng(0)
    b, kvh, g, hd, ps, n_pages, r = 2, 2, 1, 16, 4, 6, 2
    q4 = jnp.asarray(rng.normal(size=(b, kvh, g, hd)), jnp.float32)
    pool_k = jnp.asarray(rng.normal(size=(1, n_pages, ps, kvh, hd)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(1, n_pages, ps, kvh, hd)),
                         jnp.float32)
    bt = jnp.asarray([[3, 1], [0, 5]], jnp.int32)
    offsets = jnp.asarray([0, 2], jnp.int32)
    posv = jnp.asarray([6, 7], jnp.int32)
    posb = (posv - offsets)[:, None]
    kpos = attn._cache_positions(r * ps, offsets)
    for window in (0, 3):
        bias = pk.mask_bias(posb, kpos, window)
        got = pk.paged_flash_decode(q4, pool_k, pool_v, bt, bias,
                                    scale=1.0 / np.sqrt(hd), layer=0,
                                    interpret=True)
        # oracle: gather + masked softmax (attention.py dense core)
        gk = attn.gather_pages(pool_k, bt, r, 0)
        gv = attn.gather_pages(pool_v, bt, r, 0)
        q = q4.reshape(b, 1, kvh * g, hd)
        want = attn._scores_to_out(cfg, q, gk, gv, posb, kpos,
                                   causal=True, window=window)
        np.testing.assert_allclose(
            np.asarray(got).reshape(b, 1, kvh * g, hd),
            np.asarray(want), rtol=1e-5, atol=1e-5)


def test_kernel_tolerates_mixed_read_buckets():
    """Mixed batching admits lanes whose live contexts differ wildly,
    all read under ONE shared ``read_pages`` bucket. A short lane's
    block-table entries past its allocation point at pool page 0 —
    which here BELONGS to the long lane — so the kernel must let the
    bias masking zero those pages out entirely: the short lane's output
    under the wide shared bucket must equal its own narrow-bucket
    (R=1) result, and both lanes must match the XLA gather oracle."""
    from repro.kernels import paged_attention as pk
    cfg = tiny_cfg()
    rng = np.random.default_rng(5)
    kvh, g, hd, ps, n_pages, r = 2, 1, 16, 4, 8, 4
    q4 = jnp.asarray(rng.normal(size=(2, kvh, g, hd)), jnp.float32)
    pool_k = jnp.asarray(rng.normal(size=(1, n_pages, ps, kvh, hd)),
                         jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(1, n_pages, ps, kvh, hd)),
                         jnp.float32)
    # lane 0: ONE live page (page 2); its table rows 1.. default to 0,
    # aliasing the long lane's first page. lane 1: four live pages.
    bt = jnp.asarray([[2, 0, 0, 0], [0, 1, 5, 7]], jnp.int32)
    offsets = jnp.asarray([0, 0], jnp.int32)
    posv = jnp.asarray([2, 14], jnp.int32)        # frontiers 3 vs 15
    posb = posv[:, None]
    kpos = attn._cache_positions(r * ps, offsets)
    bias = pk.mask_bias(posb, kpos, 0)
    got = pk.paged_flash_decode(q4, pool_k, pool_v, bt, bias,
                                scale=1.0 / np.sqrt(hd), layer=0,
                                interpret=True)
    gk = attn.gather_pages(pool_k, bt, r, 0)
    gv = attn.gather_pages(pool_v, bt, r, 0)
    want = attn._scores_to_out(cfg, q4.reshape(2, 1, kvh * g, hd),
                               gk, gv, posb, kpos, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(got).reshape(2, 1, kvh * g, hd),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    # short lane alone under its OWN narrow bucket: identical output —
    # the aliased page-0 reads contributed nothing
    bias1 = pk.mask_bias(posb[:1], attn._cache_positions(ps, offsets[:1]),
                         0)
    solo = pk.paged_flash_decode(q4[:1], pool_k, pool_v, bt[:1, :1],
                                 bias1, scale=1.0 / np.sqrt(hd), layer=0,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(solo)[0],
                               rtol=1e-6, atol=1e-6)


def test_pallas_interp_engine_token_parity(model):
    """attn_backend='pallas_interp' through the whole engine: greedy
    tokens match the XLA gather path exactly."""
    cfg, params = model
    prompts = _prompts(cfg, [6, 9, 4], seed=4)
    kw = dict(max_new_tokens=6, prefill_chunk=4, slab_k=4, paged=True,
              page_size=4)
    got_x, _ = engine.generate(cfg, params, prompts,
                               attn_backend="xla", **kw)
    got_p, _ = engine.generate(cfg, params, prompts,
                               attn_backend="pallas_interp", **kw)
    for a, b in zip(got_x, got_p):
        np.testing.assert_array_equal(a, b)

"""Attention masking edge cases (models/attention.py):

  * ``chunk_attention`` ``lane_mask`` shielding — running lanes' cache
    rows (dense) / pool pages (paged) survive a group prefill untouched;
  * sliding-window attention combined with ragged offsets — the window
    mask is AND-ed with the causal mask, so the ``_PAD_POS`` sentinel
    for left-pad slots must survive both, with and without page
    boundaries inside the window;
  * the layer-stacked cache — each function touches only layer
    ``layer`` of the whole stack, bitwise as on that layer alone.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_cfg
from repro.models import attention as attn
from repro.models import registry
from repro.serving import engine, serve_loop


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    layer = jax.tree_util.tree_map(lambda p: p[0], params["layers"])
    return cfg, layer["attn"]


def _rand(rng, shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def test_chunk_attention_lane_mask_shields_cache_rows(setup):
    cfg, p = setup
    rng = np.random.default_rng(0)
    b, c, smax, kv, hd = 3, 4, 16, 2, cfg.head_dim
    x = _rand(rng, (b, c, cfg.d_model))
    ck = _rand(rng, (b, smax, kv, hd))
    cv = _rand(rng, (b, smax, kv, hd))
    offsets = jnp.asarray([0, 1, 2], jnp.int32)
    mask = jnp.asarray([True, False, True])
    _, nk, nv = attn.chunk_attention(cfg, p, x, ck[None], cv[None], 4,
                                     offsets, layer=0, lane_mask=mask)
    nk, nv = nk[0], nv[0]
    # masked lane 1: every cache row bitwise-preserved
    np.testing.assert_array_equal(np.asarray(nk[1]), np.asarray(ck[1]))
    np.testing.assert_array_equal(np.asarray(nv[1]), np.asarray(cv[1]))
    # unmasked lanes: the chunk's rows changed, the rest preserved
    assert not np.array_equal(np.asarray(nk[0, 4:8]),
                              np.asarray(ck[0, 4:8]))
    np.testing.assert_array_equal(np.asarray(nk[0, :4]),
                                  np.asarray(ck[0, :4]))
    np.testing.assert_array_equal(np.asarray(nk[0, 8:]),
                                  np.asarray(ck[0, 8:]))


def test_paged_chunk_lane_mask_shields_pool_pages(setup):
    """Paged twin: a shielded lane's POOL pages survive bitwise — and
    no other page is touched either (the write is a drop, not a
    read-modify-write of someone else's page)."""
    cfg, p = setup
    rng = np.random.default_rng(1)
    b, c, ps, n_pages, kv, hd = 2, 4, 4, 6, 2, cfg.head_dim
    x = _rand(rng, (b, c, cfg.d_model))
    pk = _rand(rng, (n_pages, ps, kv, hd))
    pv = _rand(rng, (n_pages, ps, kv, hd))
    bt = jnp.asarray([[2, 4], [1, 3]], jnp.int32)
    offsets = jnp.asarray([0, 1], jnp.int32)
    mask = jnp.asarray([True, False])
    _, nk, _ = attn.paged_chunk_attention(
        cfg, p, x, pk[None], pv[None], bt, 2, offsets, layer=0,
        read_pages=2, lane_mask=mask)
    nk = nk[0]
    # lane 1 owns pages 1 and 3: untouched
    np.testing.assert_array_equal(np.asarray(nk[1]), np.asarray(pk[1]))
    np.testing.assert_array_equal(np.asarray(nk[3]), np.asarray(pk[3]))
    # unowned pages 0 and 5: untouched too
    np.testing.assert_array_equal(np.asarray(nk[0]), np.asarray(pk[0]))
    np.testing.assert_array_equal(np.asarray(nk[5]), np.asarray(pk[5]))
    # lane 0 wrote slots [2, 6): page 2 rows 2-3 and page 4 rows 0-1
    assert not np.array_equal(np.asarray(nk[2, 2:]),
                              np.asarray(pk[2, 2:]))
    assert not np.array_equal(np.asarray(nk[4, :2]),
                              np.asarray(pk[4, :2]))


def test_sliding_window_with_ragged_offsets_matches_solo():
    """Ragged batch + sliding window through the full engine: every
    request must reproduce its solo (offset-free) generation exactly —
    the window mask must act on LOGICAL positions, with left-pad slots
    excluded by the AND-ed causal/_PAD_POS mask."""
    cfg = tiny_cfg(sliding_window=3)
    params = registry.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=(n,))
               .astype(np.int32) for n in (5, 8, 3)]
    for paged in (False, True):
        got, _ = engine.generate(
            cfg, params, prompts, max_new_tokens=6, max_len=20,
            prefill_chunk=4, slab_k=4, paged=paged,
            **({"page_size": 4} if paged else {}))
        for p, g in zip(prompts, got):
            want, _ = serve_loop.generate(cfg, params,
                                          jnp.asarray(p)[None],
                                          max_new_tokens=6, max_len=20)
            np.testing.assert_array_equal(g, np.asarray(want)[0])


def test_window_mask_across_page_boundary(setup):
    """Direct check that a window smaller than a page AND one spanning a
    page boundary read identical context through the paged gather as
    through the dense cache (page_size=4, window ∈ {2, 5})."""
    cfg, p = setup
    rng = np.random.default_rng(4)
    b, smax, ps, kv, hd = 2, 16, 4, 2, cfg.head_dim
    x = _rand(rng, (b, 1, cfg.d_model))
    ck = _rand(rng, (b, smax, kv, hd))
    cv = _rand(rng, (b, smax, kv, hd))
    offsets = jnp.asarray([0, 2], jnp.int32)
    pos = jnp.asarray([6, 7], jnp.int32)
    # paged pool holding the same data: lane b's page j = rows of ck
    bt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
    pool_k = jnp.concatenate([ck[0].reshape(4, ps, kv, hd),
                              ck[1].reshape(4, ps, kv, hd)])
    pool_v = jnp.concatenate([cv[0].reshape(4, ps, kv, hd),
                              cv[1].reshape(4, ps, kv, hd)])
    for window in (2, 5):
        want, _, _ = attn.decode_attention(cfg, p, x, ck[None], cv[None],
                                           pos, layer=0, window=window,
                                           offsets=offsets)
        got, _, _ = attn.paged_decode_attention(
            cfg, p, x, pool_k[None], pool_v[None], bt, pos, layer=0,
            read_pages=2, window=window, offsets=offsets)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", ["decode", "decode_scalar_pos", "chunk",
                                  "paged_decode", "paged_decode_pallas",
                                  "paged_chunk"])
def test_stacked_cache_layer_matches_single_layer(setup, kind):
    """An attention function writes and reads layer ``layer`` of the
    whole layer-stacked cache the layer scan carries: its output and
    that layer's new rows are bitwise what the same call gives on that
    layer alone (a stack of one), and every other layer is left as it
    was."""
    cfg, p = setup
    rng = np.random.default_rng(5)
    n_layers, layer, b, kv, hd = 3, 1, 2, 2, cfg.head_dim
    offsets = jnp.asarray([0, 1], jnp.int32)
    bt = jnp.asarray([[2, 4], [1, 3]], jnp.int32)
    if kind.startswith("paged"):
        shape = (n_layers, 6, 4, kv, hd)             # 6 pages of 4 slots
    else:
        shape = (n_layers, b, 8, kv, hd)
    ck, cv = _rand(rng, shape), _rand(rng, shape)
    c = 3 if kind in ("chunk", "paged_chunk") else 1
    x = _rand(rng, (b, c, cfg.d_model))
    pos = jnp.asarray([3, 5], jnp.int32)

    def call(k, v, lyr):
        if kind == "decode":
            return attn.decode_attention(cfg, p, x, k, v, pos,
                                         offsets=offsets, layer=lyr)
        if kind == "decode_scalar_pos":
            return attn.decode_attention(cfg, p, x, k, v, jnp.int32(4),
                                         layer=lyr)
        if kind == "chunk":
            return attn.chunk_attention(
                cfg, p, x, k, v, 2, offsets,
                lane_mask=jnp.asarray([True, False]), layer=lyr)
        if kind == "paged_chunk":
            return attn.paged_chunk_attention(
                cfg, p, x, k, v, bt, pos, offsets, read_pages=2,
                q_lens=jnp.asarray([3, 1], jnp.int32), layer=lyr)
        return attn.paged_decode_attention(
            cfg, p, x, k, v, bt, pos, read_pages=2, offsets=offsets,
            backend="pallas_interp" if kind.endswith("pallas") else "xla",
            layer=lyr)

    alone = slice(layer, layer + 1)
    want, wk, wv = call(ck[alone], cv[alone], jnp.int32(0))
    got, gk, gv = call(ck, cv, jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for new, old, one in ((gk, ck, wk), (gv, cv, wv)):
        np.testing.assert_array_equal(np.asarray(new[layer]),
                                      np.asarray(one[0]))
        for other in (0, 2):
            np.testing.assert_array_equal(np.asarray(new[other]),
                                          np.asarray(old[other]))
        assert not np.array_equal(np.asarray(new[layer]),
                                  np.asarray(old[layer]))

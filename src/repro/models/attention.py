"""Attention: GQA/MHA with rope, sliding window, logit softcap, qk-norm,
query-chunked computation (bounds the score transient to
(chunk, S) — the memory behaviour a production TPU stack needs at 32k),
decode with sequence-sharded KV caches, and optional cross-attention
(whisper).

Head padding: archs whose head count does not divide TP=16 declare
``pad_heads_to``; extra heads are zero-initialised (wo rows zero ⇒ the
padding is numerically exact) — DESIGN.md §5.

Every attention function runs under the named scope ``attn``, and its
parts under ``attn.qkv``, ``attn.kv_write``, ``attn.kv_read``,
``attn.core`` and ``attn.out`` (``obs.trace.SCOPES``): a device trace
attributes each op to its part by the ``op_name`` these leave in the
HLO metadata. Scopes change no computation.

The cache-facing functions take the WHOLE layer-stacked cache that the
layer scan carries (models/transformer.py ``_run_stack``) and ``layer``,
the layer's index (traced): the new K/V is written at ``[layer, ...]``
and the read indexes ``[layer]``, so a serving step updates the cache in
place and never slices or restacks a layer's cache. A caller holding one
layer's cache passes it as a stack of one (``cache[None]``, layer 0).
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rmsnorm, softcap
from repro.models.params import ParamSpec

NEG_INF = -1e30

# Read ONCE at import: the pre-optimization dry-run variant. A per-call
# env read inside traced code was silently baked into whatever jit cache
# existed when the function was first traced — flipping the env var
# mid-process did nothing (or worse, half of it).
DRYRUN_BASELINE = bool(os.environ.get("DRYRUN_BASELINE"))


def eff_heads(cfg) -> tuple[int, int]:
    """(q_heads, kv_heads) after TP padding."""
    h = cfg.num_heads
    kv = cfg.num_kv_heads
    if cfg.pad_heads_to:
        h = max(h, cfg.pad_heads_to)
        if cfg.num_kv_heads == cfg.num_heads:     # MHA: pad kv too
            kv = h
    return h, kv


def attn_param_specs(cfg, cross: bool = False) -> dict:
    """ParamSpec dict for one attention block (stacked by caller)."""
    d, hd = cfg.d_model, cfg.head_dim
    h, kv = eff_heads(cfg)
    specs = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim"),
                        fan_in=d),
        "wk": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                        fan_in=d),
        "wv": ParamSpec((d, kv, hd), ("embed", "kv_heads", "head_dim"),
                        fan_in=d),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed"),
                        scale=1.0 / math.sqrt(2 * cfg.num_layers),
                        fan_in=h * hd),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), init="zeros")
        specs["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"),
                                init="zeros")
        specs["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"),
                                init="zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), init="zeros")
    if cross:
        # cross-attention re-uses wq/wo; K/V project from encoder states
        specs = {k: v for k, v in specs.items()}
    return specs


def _project_qkv(cfg, p, x, kv_src=None):
    """-> q (B,S,H,hd), k,v (B,Skv,KV,hd)."""
    kv_src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    return q, k, v


@jax.named_scope("attn.core")
def _scores_to_out(cfg, q, k, v, q_pos, k_pos, causal, window):
    """Grouped attention core. q: (B,Sq,H,hd); k/v: (B,Sk,KV,hd);
    q_pos: (B,Sq); k_pos: (B,Sk) (for masking). Returns (B,Sq,H,hd).

    Mixed precision WITHOUT materialising f32 copies of K/V: the dots
    accumulate in f32 via preferred_element_type (a wholesale
    cache->f32 convert was the #1 byte contributor of the decode
    roofline — EXPERIMENTS.md §Perf iteration 1)."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    scale = cfg.attn_scale or 1.0 / math.sqrt(hd)
    qg = q.reshape(b, sq, kv, g, hd)
    if DRYRUN_BASELINE:                     # pre-optimization variant
        logits = jnp.einsum("bqhgk,bshk->bhgqs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
    else:
        logits = jnp.einsum("bqhgk,bshk->bhgqs", qg, k,
                            preferred_element_type=jnp.float32) * scale
    logits = softcap(logits, cfg.attn_logit_softcap)
    mask = jnp.ones((b, sq, k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, :, None] >= k_pos[:, None, :]
    if window:
        mask &= q_pos[:, :, None] - k_pos[:, None, :] < window
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if DRYRUN_BASELINE:
        out = jnp.einsum("bhgqs,bshk->bqhgk", probs,
                         v.astype(jnp.float32))
    else:
        out = jnp.einsum("bhgqs,bshk->bqhgk", probs.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, hd).astype(q.dtype)


@jax.named_scope("attn")
def multihead_attention(cfg, p, x, positions, *, causal=True, window=0,
                        q_chunk=1024, kv_src=None, kv_positions=None):
    """Full (train/prefill/encoder) attention with query chunking.

    Returns (out (B,S,D), (k, v)) — k/v returned so prefill can seed the
    cache."""
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(cfg, p, x, kv_src)
        if cfg.rope_theta > 0 and kv_src is None:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions if kv_positions is None
                           else kv_positions, cfg.rope_theta)
    kpos = positions if kv_positions is None else kv_positions
    s = q.shape[1]
    if s <= q_chunk or s % q_chunk != 0:
        out = _scores_to_out(cfg, q, k, v, positions, kpos, causal, window)
    else:
        nch = s // q_chunk
        qs = q.reshape(q.shape[0], nch, q_chunk, *q.shape[2:])
        ps = positions.reshape(positions.shape[0], nch, q_chunk)
        def chunk(carry, inp):
            qc, pc = inp
            oc = _scores_to_out(cfg, qc, k, v, pc, kpos, causal, window)
            return carry, oc
        # scan over chunks: transient is (B, q_chunk, S) not (B, S, S)
        _, outs = jax.lax.scan(chunk, None,
                               (qs.swapaxes(0, 1), ps.swapaxes(0, 1)))
        out = outs.swapaxes(0, 1).reshape(q.shape)
    with jax.named_scope("attn.out"):
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, (k, v)


# Cache slots holding no real token (left-padding of ragged prompts) get
# this sentinel "logical position": larger than any query position, so the
# causal mask excludes them (and with it the AND-ed window mask).
_PAD_POS = 1 << 30


def _cache_positions(smax: int, offsets: jax.Array) -> jax.Array:
    """(B, Smax) logical position of each cache slot for right-aligned
    sequences: slot s holds logical token ``s - offset``; slots before
    ``offset`` are padding (sentinel ``_PAD_POS`` → always masked)."""
    slots = jnp.arange(smax, dtype=jnp.int32)[None, :]
    off = offsets.astype(jnp.int32)[:, None]
    return jnp.where(slots >= off, slots - off, jnp.int32(_PAD_POS))


@jax.named_scope("attn")
def decode_attention(cfg, p, x, cache_k, cache_v, pos, *, layer,
                     window=0, cross=False, offsets=None):
    """One-token decode. x: (B,1,D); cache_k/v: the (L,B,Smax,KV,hd)
    stack, of which only layer ``layer`` is written and read; ``pos``
    is the CACHE SLOT of the new token — a scalar int32 (synchronized
    batch: every lane writes the same slot) or a (B,) vector (per-lane
    frontiers: lane b writes its own slot ``pos[b]``, engine slab
    decode). Out-of-range per-lane slots (>= Smax) drop the write — the
    engine parks finished lanes there so they stop advancing.

    For self-attention the new K/V is written at ``pos`` (functional
    update); for cross-attention the cache is the (static) encoder memory.
    With ``offsets`` (B,) the batch is ragged: lane b's logical position
    is ``pos[b] - offsets[b]`` (rope + masking), while the cache slot
    stays ``pos``. ``offsets=None`` with scalar ``pos`` is
    bitwise-identical to the historical synchronized path.
    Returns (out, new_cache_k, new_cache_v)."""
    b = x.shape[0]
    per_lane = jnp.ndim(pos) > 0
    posv = (pos.astype(jnp.int32) if per_lane
            else jnp.full((b,), pos, jnp.int32))
    if offsets is None:
        posb = posv[:, None]
    else:
        posb = (posv - offsets.astype(jnp.int32))[:, None]
    if cross:
        # encoder memory is already projected K/V; only project Q
        with jax.named_scope("attn.qkv"):
            q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
            if cfg.qkv_bias:
                q = q + p["bq"].astype(x.dtype)
            if cfg.qk_norm:
                q = rmsnorm(q, p["q_norm"])
    else:
        with jax.named_scope("attn.qkv"):
            q, k, v = _project_qkv(cfg, p, x)
            if cfg.rope_theta > 0:
                q = apply_rope(q, posb, cfg.rope_theta)
                k = apply_rope(k, posb, cfg.rope_theta)
        with jax.named_scope("attn.kv_write"):
            if per_lane:
                # per-lane write slots: scatter row b at (b, pos[b]);
                # lanes whose slot is out of bounds are dropped
                lanes = jnp.arange(b)
                cache_k = cache_k.at[layer, lanes, posv].set(
                    k[:, 0].astype(cache_k.dtype), mode="drop")
                cache_v = cache_v.at[layer, lanes, posv].set(
                    v[:, 0].astype(cache_v.dtype), mode="drop")
            else:
                at = (layer, 0, pos, 0, 0)
                cache_k = jax.lax.dynamic_update_slice(
                    cache_k, k[None].astype(cache_k.dtype), at)
                cache_v = jax.lax.dynamic_update_slice(
                    cache_v, v[None].astype(cache_v.dtype), at)
    smax = cache_k.shape[2]
    if offsets is None:
        kpos = jnp.broadcast_to(jnp.arange(smax, dtype=jnp.int32),
                                (b, smax))
    else:
        kpos = _cache_positions(smax, offsets)
    with jax.named_scope("attn.kv_read"):
        rk = cache_k[layer].astype(q.dtype)
        rv = cache_v[layer].astype(q.dtype)
    # causal mask at qpos==pos also masks the garbage cache tail
    out = _scores_to_out(cfg, q, rk, rv, posb, kpos,
                         causal=not cross, window=window)
    with jax.named_scope("attn.out"):
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, cache_k, cache_v


@jax.named_scope("attn")
def chunk_attention(cfg, p, x, cache_k, cache_v, slot, offsets, *,
                    layer, window=0, lane_mask=None):
    """Batched chunked-prefill attention: C prompt tokens at once.

    x: (B,C,D); cache_k/v: the (L,B,Smax,KV,hd) stack, of which only
    layer ``layer`` is written and read. The chunk's K/V is written at
    cache slots [slot, slot+C); lane b's token at slot s has logical
    position ``s - offsets[b]`` (right-aligned ragged batch — left-pad
    slots are masked everywhere via the ``_PAD_POS`` sentinel).
    ``lane_mask`` (B,) bool, when given, preserves the existing cache
    rows of lanes not being prefilled (continuous batching admits new
    sequences behind the decode frontier of running ones).
    Returns (out (B,C,D), new_cache_k, new_cache_v)."""
    b, c, _ = x.shape
    slots = jnp.int32(slot) + jnp.arange(c, dtype=jnp.int32)
    qpos = slots[None, :] - offsets.astype(jnp.int32)[:, None]   # (B,C)
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(cfg, p, x)
        if cfg.rope_theta > 0:
            # pad queries have negative logical positions; clamp for
            # rope (their K/V and outputs are masked / discarded anyway)
            rp = jnp.maximum(qpos, 0)
            q = apply_rope(q, rp, cfg.rope_theta)
            k = apply_rope(k, rp, cfg.rope_theta)
    with jax.named_scope("attn.kv_write"):
        k = k[None].astype(cache_k.dtype)
        v = v[None].astype(cache_v.dtype)
        at = (layer, 0, slot, 0, 0)
        if lane_mask is not None:
            keep = lane_mask[None, :, None, None, None]
            k = jnp.where(keep, k, jax.lax.dynamic_slice(cache_k, at,
                                                         k.shape))
            v = jnp.where(keep, v, jax.lax.dynamic_slice(cache_v, at,
                                                         v.shape))
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, at)
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, at)
    kpos = _cache_positions(cache_k.shape[2], offsets)
    with jax.named_scope("attn.kv_read"):
        rk = cache_k[layer].astype(q.dtype)
        rv = cache_v[layer].astype(q.dtype)
    out = _scores_to_out(cfg, q, rk, rv, qpos, kpos,
                         causal=True, window=window)
    with jax.named_scope("attn.out"):
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, cache_k, cache_v


# ---------------------------------------------------------------- paged KV
# The contiguous cache above scores every query against the full
# (B, Smax, KV, hd) slab, so decode attention bytes scale with ``Smax``
# no matter how short a lane's live context is. The paged variant stores
# K/V in a SHARED page pool (n_pages, page_size, KV, hd); each lane maps
# logical cache slots to pool pages through a (max_pages,) block table
# and attention gathers ONLY the lane's first ``read_pages`` pages — the
# engine buckets ``read_pages`` to the next power of two of the live
# frontier, so per-token attention reads scale with
# ``ceil(frontier / page_size)`` instead of ``Smax`` (BLaST's
# move-only-the-blocks-that-matter thesis applied to the KV cache).
#
# Logical slot ``s`` of lane ``b`` lives at pool page
# ``block_tables[b, s // page_size]``, row ``s % page_size``; the slot
# numbering (and with it rope, offsets, causal/window masking via
# ``_cache_positions``) is IDENTICAL to the contiguous cache, so greedy
# decode through this path is bitwise-identical to the dense one — the
# gathered slots beyond a lane's frontier land on unallocated (or
# stale) pages and are killed by the same causal mask that hides the
# garbage cache tail in the dense path.


def gather_pages(pool: jax.Array, block_tables: jax.Array,
                 read_pages: int, layer) -> jax.Array:
    """(L, n_pages, ps, KV, hd) pool + (B, max_pages) tables ->
    (B, read_pages*ps, KV, hd): each lane's first ``read_pages`` logical
    pages of layer ``layer``, gathered straight out of the stack, in
    logical-slot order (the XLA fallback of the Pallas blocked-gather
    kernel — kernels/paged_attention.py)."""
    b = block_tables.shape[0]
    g = pool[layer, block_tables[:, :read_pages]]    # (B, R, ps, KV, hd)
    return g.reshape(b, read_pages * pool.shape[2], *pool.shape[3:])


@jax.named_scope("attn.kv_write")
def paged_write(pool: jax.Array, block_tables: jax.Array,
                slots: jax.Array, values: jax.Array,
                lane_mask: jax.Array | None = None, *,
                layer) -> jax.Array:
    """Scatter ``values`` at logical ``slots`` through the block tables.

    pool: (L, n_pages, ps, KV, hd), written at ``[layer, page, row]``
    in place; slots: (B,) or (B, C) int32; values:
    slots.shape + (KV, hd). Slots past the table end (>= max_pages*ps —
    the engine parks finished lanes there) and lanes masked out by
    ``lane_mask`` are DROPPED, never clamped: a clamp would alias the
    write onto pool page 0, which may belong to another lane.
    ``lane_mask`` is (B,) bool (whole lanes) or (B, C) bool (per-token:
    the mixed decode+prefill step pads every lane's query run to a
    common width — pad tokens must not scribble through the block
    table, whose rows beyond a lane's allocation point at page 0)."""
    n_pages, ps = pool.shape[1], pool.shape[2]
    max_pages = block_tables.shape[1]
    slots = slots.astype(jnp.int32)
    squeeze = slots.ndim == 1
    s2 = slots[:, None] if squeeze else slots            # (B, C)
    page = s2 // ps
    ok = page < max_pages
    if lane_mask is not None:
        ok &= (lane_mask[:, None] if lane_mask.ndim == 1 else lane_mask)
    phys = jnp.take_along_axis(block_tables,
                               jnp.minimum(page, max_pages - 1), axis=1)
    phys = jnp.where(ok, phys, jnp.int32(n_pages))       # OOB -> drop
    vals = (values[:, None] if squeeze else values).astype(pool.dtype)
    return pool.at[layer, phys, s2 % ps].set(vals, mode="drop")


@jax.named_scope("attn")
def paged_decode_attention(cfg, p, x, pool_k, pool_v, block_tables, pos,
                           *, layer, read_pages: int, window=0,
                           offsets=None, backend: str = "xla"):
    """One-token decode over the paged pool. x: (B,1,D); pool_k/v:
    (L, n_pages, ps, KV, hd) SHARED across lanes, of which layer
    ``layer`` is written and read; ``block_tables``
    (B, max_pages) int32; ``pos`` (B,) is each lane's logical cache
    slot (parked lanes carry ``max_pages*ps`` — the write drops).
    ``read_pages`` is STATIC: attention reads each lane's first
    ``read_pages`` pages (the engine guarantees they cover every live
    frontier and buckets the value to a power of two so the jit cache
    stays O(log max_pages)).

    ``backend``: 'xla' (gather + dense core — the oracle), 'pallas'
    (blocked-gather flash-decode kernel, kernels/paged_attention.py), or
    'pallas_interp' (same kernel, interpret mode); either reads layer
    ``layer``'s pages straight out of the stack.
    Returns (out, new_pool_k, new_pool_v)."""
    b = x.shape[0]
    ps = pool_k.shape[2]
    posv = pos.astype(jnp.int32)
    posb = (posv if offsets is None
            else posv - offsets.astype(jnp.int32))[:, None]
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(cfg, p, x)
        if cfg.rope_theta > 0:
            q = apply_rope(q, posb, cfg.rope_theta)
            k = apply_rope(k, posb, cfg.rope_theta)
    pool_k = paged_write(pool_k, block_tables, posv, k[:, 0], layer=layer)
    pool_v = paged_write(pool_v, block_tables, posv, v[:, 0], layer=layer)
    smax = read_pages * ps
    if offsets is None:
        kpos = jnp.broadcast_to(jnp.arange(smax, dtype=jnp.int32),
                                (b, smax))
    else:
        kpos = _cache_positions(smax, offsets)
    if backend in ("pallas", "pallas_interp"):
        from repro.kernels import paged_attention as pk
        with jax.named_scope("attn.core"):
            out = pk.paged_decode_attn(
                cfg, q, pool_k, pool_v, block_tables[:, :read_pages],
                posb, kpos, window=window, layer=layer,
                interpret=(backend == "pallas_interp"))
    else:
        with jax.named_scope("attn.kv_read"):
            gk = gather_pages(pool_k, block_tables, read_pages, layer)
            gv = gather_pages(pool_v, block_tables, read_pages, layer)
            gk, gv = gk.astype(q.dtype), gv.astype(q.dtype)
        out = _scores_to_out(cfg, q, gk, gv, posb, kpos,
                             causal=True, window=window)
    with jax.named_scope("attn.out"):
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, pool_k, pool_v


@jax.named_scope("attn")
def paged_chunk_attention(cfg, p, x, pool_k, pool_v, block_tables, slot,
                          offsets, *, read_pages: int, window=0,
                          layer, lane_mask=None, q_lens=None):
    """Batched chunked-prefill attention over the paged pool: C prompt
    tokens written at logical slots [slot, slot+C) of layer ``layer`` of
    the (L, n_pages, ps, KV, hd) pools through each lane's
    block table (the engine allocates the covering pages before the
    first chunk). ``lane_mask`` shields running lanes the natural paged
    way — their writes are dropped, their pages never touched (the
    dense path had to read-modify-write them back).

    ``slot`` may be a scalar (every lane writes the same slot range —
    group prefill) or a (B,) vector of PER-LANE start slots; with
    ``q_lens`` (B,) the query run is additionally RAGGED per lane: lane
    b's tokens [0, q_lens[b]) are real (written + attended from its own
    positions), the rest of the width-C row is padding whose writes are
    dropped and whose outputs the caller discards. This is the mixed
    decode+prefill core: decode lanes ride along at q_len == 1 (start =
    their frontier) while admitting lanes prefill a chunk, all in ONE
    call — per-query attention math is position-row independent, so
    each lane's rows come out bitwise-identical to the phased paths.
    Returns (out (B,C,D), new_pool_k, new_pool_v)."""
    b, c, _ = x.shape
    ps = pool_k.shape[2]
    slot = jnp.asarray(slot, jnp.int32)
    steps = jnp.arange(c, dtype=jnp.int32)
    if slot.ndim == 0:
        slots_b = jnp.broadcast_to((slot + steps)[None, :], (b, c))
    else:
        slots_b = slot[:, None] + steps[None, :]             # (B, C)
    qpos = slots_b - offsets.astype(jnp.int32)[:, None]      # (B, C)
    with jax.named_scope("attn.qkv"):
        q, k, v = _project_qkv(cfg, p, x)
        if cfg.rope_theta > 0:
            rp = jnp.maximum(qpos, 0)
            q = apply_rope(q, rp, cfg.rope_theta)
            k = apply_rope(k, rp, cfg.rope_theta)
    wmask = None if lane_mask is None else lane_mask
    if q_lens is not None:
        valid = steps[None, :] < q_lens.astype(jnp.int32)[:, None]
        wmask = valid if wmask is None else (wmask[:, None] & valid
                                             if wmask.ndim == 1
                                             else wmask & valid)
    pool_k = paged_write(pool_k, block_tables, slots_b, k, wmask,
                         layer=layer)
    pool_v = paged_write(pool_v, block_tables, slots_b, v, wmask,
                         layer=layer)
    kpos = _cache_positions(read_pages * ps, offsets)
    with jax.named_scope("attn.kv_read"):
        gk = gather_pages(pool_k, block_tables, read_pages, layer)
        gv = gather_pages(pool_v, block_tables, read_pages, layer)
        gk, gv = gk.astype(q.dtype), gv.astype(q.dtype)
    out = _scores_to_out(cfg, q, gk, gv, qpos, kpos, causal=True,
                         window=window)
    with jax.named_scope("attn.out"):
        y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, pool_k, pool_v

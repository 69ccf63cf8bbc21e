"""Serving steps: prefill (build cache + last-token logits) and decode
(one token with cache). Weights arrive already PRUNED (zeros in pruned
blocks) or PACKED (balanced BCSC — the paper's inference memory win;
``export.py``). Greedy sampling by default; temperature optional at the
loop level. A step's argmax, stop logic and lane-state update run under
the named scope ``sample`` (``obs.trace.SCOPES``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import registry


def make_prefill_step(cfg, dist=None):
    """prefill(params, tokens, **frontend) -> (last_logits, kv-seed).

    For the KV-cache families the prefill writes the cache via the
    training forward's returned K/V; here (dry-run + CPU serving) we
    lower the forward and re-run decode from scratch caches, which is
    the same compute cost — the cache-write variant is a serving-loop
    detail (serve_loop.py seeds caches token-by-token for exactness)."""
    def prefill_step(params, tokens, **kw):
        logits, _ = registry.forward(cfg, params, tokens, masks=None,
                                     dist=dist, **kw)
        return logits[:, -1]
    return prefill_step


def make_prefill_chunk_step(cfg, dist=None):
    """Jittable chunked batched prefill (engine.py): one call runs a
    whole (B, C) chunk of right-aligned prompt tokens through the model
    and seeds the KV cache — the per-token Python prefill loop collapses
    to ceil(plen / C) jitted calls.

    prefill(params, cache, tokens, slot, offsets, lane_mask)
        -> (last_logits (B, V) f32, new_cache)
    """
    def prefill_step(params, cache, tokens, slot, offsets, lane_mask):
        logits, cache = registry.prefill_chunk(
            cfg, params, cache, tokens, slot, offsets, masks=None,
            dist=dist, lane_mask=lane_mask)
        return logits[:, -1], cache
    return prefill_step


def _run_slab(k_steps, max_len, eos_id, cache, state, park, step_fn):
    """The decode-slab scan body shared by the contiguous and paged
    twins — they differ ONLY in where a dead lane parks (``park``: a
    slot the cache write drops) and how one step touches the cache
    (``step_fn(cache, tokens (B,1), write_pos (B,)) -> (logits,
    new_cache)``), so the stop logic can never drift between them (the
    paged-vs-contiguous bitwise-parity guarantee leans on that).

    A lane dies mid-slab when it emits ``eos_id``, exhausts its budget,
    or runs out of cache (``frontier`` reaching ``max_len``); a dead
    lane's frontier/remaining freeze and its emitted tokens after the
    stop point are garbage the host discards — so greedy decode stays
    bitwise-identical to the per-token path.

    Fault containment rides the same carry: each step's last-row logits
    pass a per-lane finite check, and a lane whose logits go NaN/Inf is
    marked ``faulted`` and dies WITHOUT advancing its frontier — its
    request fails structurally (engine quarantine) while every other
    lane's argmax stream is untouched. ``state["poison"]`` (f32 (B,),
    normally all zero) is the injection port: it is added to the first
    in-slab step's logits and then zeroed, so a seeded FaultPlan can
    corrupt exactly one lane at exactly one step — adding 0.0 to every
    healthy lane's logits is exact in f32, so the check costs no
    parity."""
    def body(carry, _):
        cache, pending, frontier, remaining, live, poison, faulted = carry
        with jax.named_scope("sample"):
            write_pos = jnp.where(live, frontier, park)
        logits, cache = step_fn(cache, pending[:, None], write_pos)
        with jax.named_scope("sample"):
            last = logits[:, -1] + poison[:, None]
            poison = jnp.zeros_like(poison)
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
            bad = live & ~jnp.isfinite(last).all(axis=-1)
            faulted = faulted | bad
            ok = live & ~bad
            frontier = jnp.where(ok, frontier + 1, frontier)
            remaining = jnp.where(ok, remaining - 1, remaining)
            died = (remaining <= 0) | (frontier >= max_len) | bad
            if eos_id is not None:
                died |= nxt == eos_id
            live = live & ~died
            pending = jnp.where(live, nxt, pending)
        return (cache, pending, frontier, remaining, live, poison,
                faulted), nxt

    carry = (cache, state["pending"], state["frontier"],
             state["remaining"], state["live"], state["poison"],
             state["faulted"])
    (cache, pending, frontier, remaining, live, poison,
     faulted), toks = jax.lax.scan(body, carry, None, length=k_steps)
    state = dict(state, pending=pending, frontier=frontier,
                 remaining=remaining, live=live, poison=poison,
                 faulted=faulted)
    return toks.T, state, cache


def make_decode_slab_step(cfg, k_steps: int, max_len: int,
                          eos_id: int | None = None, dist=None):
    """Jitted decode SLAB: one ``lax.scan`` over ``k_steps`` greedy
    decode steps, the whole token loop on-device — the host syncs once
    per slab instead of once per token (engine.py).

    The carried per-lane state (all (B,) vectors, persistent on-device
    between slabs) is a dict:

      ``pending``   int32  next token to feed each lane
      ``frontier``  int32  cache slot the lane writes next
      ``offsets``   int32  left-pad of the lane's prompt (rope/masking)
      ``remaining`` int32  decode tokens the lane may still emit
      ``live``      bool   lane still decoding

    Dead lanes park at write slot ``max_len`` (the scatter drops it) —
    see ``_run_slab`` for the shared stop logic.

    slab(params, cache, state) -> (tokens (B, k_steps) int32,
                                   new_state, new_cache)
    """
    def slab(params, cache, state):
        offsets = state["offsets"]

        def step_fn(cache, tokens, write_pos):
            return registry.decode_step(
                cfg, params, cache, tokens, write_pos, masks=None,
                dist=dist, offsets=offsets)

        return _run_slab(k_steps, max_len, eos_id, cache, state,
                         jnp.int32(max_len), step_fn)
    return slab


def make_paged_prefill_chunk_step(cfg, dist=None):
    """Paged twin of ``make_prefill_chunk_step``: the chunk's K/V routes
    through per-lane block tables into the shared page pool.
    ``read_pages`` must be jit-STATIC (the engine buckets it to a power
    of two, so the jit cache stays O(log max_pages)).

    prefill(params, cache, tokens, slot, offsets, lane_mask,
            block_tables, read_pages) -> (last_logits (B, V), new_cache)
    """
    def prefill_step(params, cache, tokens, slot, offsets, lane_mask,
                     block_tables, read_pages):
        logits, cache = registry.paged_prefill_chunk(
            cfg, params, cache, tokens, slot, offsets, block_tables,
            read_pages=read_pages, masks=None, dist=dist,
            lane_mask=lane_mask)
        return logits[:, -1], cache
    return prefill_step


def make_paged_decode_slab_step(cfg, k_steps: int, max_len: int,
                                page_size: int, eos_id: int | None = None,
                                dist=None, attn_backend: str = "xla"):
    """Paged twin of ``make_decode_slab_step``: the scan carries the same
    per-lane state dict plus ``bt`` — each lane's (max_pages,) block
    table, constant THROUGH a slab (the engine grows allocations only at
    slab boundaries, where the host syncs anyway). A dead lane parks at
    logical slot ``max_pages * page_size``: past the table end, so the
    paged write DROPS instead of clamping onto pool page 0 (which may
    belong to another lane). ``read_pages`` is jit-static; the engine
    guarantees ``read_pages * page_size >= min(max frontier + k_steps,
    max_len)`` so every in-slab query sees its whole live context.
    Stop logic is the shared ``_run_slab``.

    slab(params, cache, state, read_pages) -> (tokens (B, k_steps),
                                               new_state, new_cache)
    """
    def slab(params, cache, state, read_pages):
        offsets = state["offsets"]
        bt = state["bt"]

        def step_fn(cache, tokens, write_pos):
            return registry.paged_decode_step(
                cfg, params, cache, tokens, write_pos, bt,
                read_pages=read_pages, masks=None, dist=dist,
                offsets=offsets, attn_backend=attn_backend)

        return _run_slab(k_steps, max_len, eos_id, cache, state,
                         jnp.int32(bt.shape[1] * page_size), step_fn)
    return slab


def make_mixed_step(cfg, dist=None):
    """Jitted MIXED decode+prefill step (engine ``mixed=True``): one
    pass of the transformer stack over a (B, W) token batch with
    per-lane variable query lengths — running lanes contribute ONE
    decode token each (q_len 1 at start = their frontier), admitting
    lanes contribute a prefill chunk (q_len = chunk at start = their
    prefill position), idle lanes ride along masked out (q_len 0).
    Decode throughput is never zeroed by an arriving prompt, and the
    uncovered tails of several prefix-cached admissions coalesce into
    this one call instead of per-lane prefill loops.

    Each lane's next token is the argmax of its LAST valid row — for a
    decode lane that is its next decode token, for a lane finishing its
    prompt this step it is the request's first generated token, and for
    a mid-prompt or idle lane it is garbage the host ignores. Only the
    (B,) token vector crosses to the host.

    ``read_pages`` must be jit-STATIC and cover every lane's
    ``start + q_len`` (the engine buckets it to a power of two); W is
    baked into the trace, so the engine buckets the width too.

    ``poison`` (f32 (B,), normally zeros) is the same fault-injection
    port as the slab's: added to each lane's last valid row before the
    argmax, with a per-lane finite check returned as ``faulted`` so the
    engine can quarantine a corrupted lane without touching the others
    (idle lanes' garbage rows may be anything — the engine masks
    ``faulted`` by lane activity before acting on it).

    mixed(params, cache, tokens (B,W), starts (B,), q_lens (B,),
          offsets (B,), block_tables, read_pages, poison (B,))
        -> (next_tokens (B,) int32, faulted (B,) bool, new_cache)
    """
    def mixed_step(params, cache, tokens, starts, q_lens, offsets,
                   block_tables, read_pages, poison):
        logits, cache = registry.paged_prefill_chunk(
            cfg, params, cache, tokens, starts, offsets, block_tables,
            read_pages=read_pages, masks=None, dist=dist, q_lens=q_lens)
        with jax.named_scope("sample"):
            last = jnp.take_along_axis(
                logits, jnp.maximum(q_lens.astype(jnp.int32) - 1,
                                    0)[:, None, None], axis=1)[:, 0]
            last = last + poison[:, None]
            faulted = ~jnp.isfinite(last).all(axis=-1)
            nxt = jnp.argmax(last, -1).astype(jnp.int32)
        return nxt, faulted, cache
    return mixed_step


def make_copy_pages_step():
    """Jittable copy-on-write page copy over the paged pool
    (engine.py + serving/prefix_cache.py): duplicate pool pages ``src``
    into ``dst`` across every layer, K and V, in one fused scatter per
    array. The whole page is copied — the rows past the shared boundary
    are stale garbage the causal mask hides until the lane overwrites
    them, exactly like a recycled free page.

    copy(cache, src (n,) int32, dst (n,) int32) -> new_cache
    """
    def copy_pages(cache, src, dst):
        out = dict(cache)
        for name in ("k", "v"):
            out[name] = cache[name].at[:, dst].set(cache[name][:, src])
        return out
    return copy_pages


def make_gather_pages_step():
    """Jittable page DOWNLOAD gather for preemption (engine.py +
    serving/offload.py): pull pool pages ``pages`` out of the device
    cache across every layer, K and V — the (layers, n, page_size, KV,
    hd) results are what the host offload store keeps while the pages
    themselves are released for reuse.

    gather(cache, pages (n,) int32) -> (k, v)
    """
    def gather_pages(cache, pages):
        return cache["k"][:, pages], cache["v"][:, pages]
    return gather_pages


def make_scatter_pages_step():
    """Jittable page UPLOAD scatter, the restore half of preemption:
    write host-held page data ``k``/``v`` (layers, n, page_size, KV, hd)
    into freshly allocated pool pages ``dst``. Duplicate indices in
    ``dst`` (the engine's power-of-two padding repeats the first page
    with its own data) write identical values, so the pad is a no-op.

    scatter(cache, dst (n,) int32, k, v) -> new_cache
    """
    def scatter_pages(cache, dst, k, v):
        out = dict(cache)
        out["k"] = cache["k"].at[:, dst].set(k)
        out["v"] = cache["v"].at[:, dst].set(v)
        return out
    return scatter_pages


def make_decode_step(cfg, dist=None, temperature: float = 0.0):
    def decode_step(params, cache, tokens, pos, rng):
        logits, cache = registry.decode_step(cfg, params, cache, tokens,
                                             pos, masks=None, dist=dist)
        last = logits[:, -1]
        if temperature > 0.0:
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(sub, last / temperature)
        else:
            nxt = jnp.argmax(last, axis=-1)
        return nxt[:, None].astype(jnp.int32), cache, last, rng
    return decode_step

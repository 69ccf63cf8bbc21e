"""Generic decoder-only Transformer LM covering the dense / MoE / VLM
families (stablelm-3b/12b, qwen2-7b, gemma2-27b, qwen3-moe, deepseek-moe,
internvl2-2b, and the paper's GPT-2 / Llama configs).

Layers are scanned (stacked params, single compiled body — compile time
independent of depth). gemma2's local/global alternating pattern scans
(local, global) PAIRS. BLaST masks ride along as stacked scan inputs.

Decode uses per-layer KV caches stacked on the layer axis, carried
whole through the layer scan and updated in place; caches shard
their sequence dim over the ``model`` axis so a 1.6 TB gemma2 32k-batch
cache fits (DESIGN.md §5).

The layers run under the named scopes ``embed``, ``norm``, ``attn``
(models/attention.py), ``mlp`` and ``lm_head`` (``obs.trace.SCOPES``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import sparse_mlp as sm
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.layers import norm, softcap
from repro.models.params import ParamSpec


# -------------------------------------------------------------- param spec
def _norm_specs(cfg, name):
    d = {name + "_scale": ParamSpec((cfg.d_model,), ("embed",),
                                    init="zeros" if cfg.norm_kind ==
                                    "rmsnorm" else "ones")}
    if cfg.norm_kind == "layernorm":
        d[name + "_bias"] = ParamSpec((cfg.d_model,), ("embed",),
                                      init="zeros")
    return d


def mlp_param_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    down_scale = 1.0 / math.sqrt(2 * cfg.num_layers)
    if cfg.is_moe:
        return moe_mod.moe_param_specs(cfg)
    if cfg.mlp_kind == "glu":
        return {
            "w_gate": ParamSpec((d, f), ("embed", "ff")),
            "w_up": ParamSpec((d, f), ("embed", "ff")),
            "w_down": ParamSpec((f, d), ("ff", "embed"), scale=down_scale),
        }
    return {
        "w_in": ParamSpec((d, f), ("embed", "ff")),
        "b_in": ParamSpec((f,), ("ff",), init="zeros"),
        "w_out": ParamSpec((f, d), ("ff", "embed"), scale=down_scale),
        "b_out": ParamSpec((d,), ("embed",), init="zeros"),
    }


def layer_param_specs(cfg) -> dict:
    specs = {}
    specs.update(_norm_specs(cfg, "ln_attn"))
    specs.update({"attn": attn.attn_param_specs(cfg)})
    specs.update(_norm_specs(cfg, "ln_mlp"))
    specs.update({"mlp": mlp_param_specs(cfg)})
    return specs


def _stack_specs(specs: dict, n: int) -> dict:
    """Prepend a stacked 'layers' dim to every leaf."""
    def f(s: ParamSpec) -> ParamSpec:
        return dataclasses.replace(s, shape=(n,) + s.shape,
                                   axes=("layers",) + s.axes)
    return jax.tree_util.tree_map(
        f, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def n_stacks(cfg) -> tuple[int, int]:
    """(stack length, layers per scan step)."""
    if cfg.layer_pattern == "local_global":
        assert cfg.num_layers % 2 == 0
        return cfg.num_layers // 2, 2
    return cfg.num_layers, 1


def param_specs(cfg) -> dict:
    ns, per = n_stacks(cfg)
    specs: dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model),
                           ("vocab", "embed"), init="embed"),
    }
    if cfg.layer_pattern == "local_global":
        specs["layers_local"] = _stack_specs(layer_param_specs(cfg), ns)
        specs["layers_global"] = _stack_specs(layer_param_specs(cfg), ns)
    else:
        specs["layers"] = _stack_specs(layer_param_specs(cfg), ns)
    specs.update(_norm_specs(cfg, "ln_f"))
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"), init="embed")
    del per
    return specs


def sparse_paths(cfg) -> list[str]:
    """Mask-tree paths of BLaST-sparsified weights (stacked)."""
    stacks = (["layers_local", "layers_global"]
              if cfg.layer_pattern == "local_global" else ["layers"])
    if cfg.is_moe:
        leaves = ["mlp/w_gate", "mlp/w_up", "mlp/w_down"]
        if cfg.num_shared_experts:
            leaves += ["mlp/ws_gate", "mlp/ws_up", "mlp/ws_down"]
    elif cfg.mlp_kind == "glu":
        leaves = ["mlp/w_gate", "mlp/w_up", "mlp/w_down"]
    else:
        leaves = ["mlp/w_in", "mlp/w_out"]
    return [f"{s}/{leaf}" for s in stacks for leaf in leaves]


def dense_layer_flags(cfg) -> jax.Array:
    """(stack,) bool — True where the MLP stays dense (last L layers,
    paper §5.4.4). For paired stacks the flag covers the pair."""
    ns, per = n_stacks(cfg)
    n_dense = math.ceil(cfg.blast.dense_last / per)
    idx = jnp.arange(ns)
    return idx >= (ns - n_dense)


# ----------------------------------------------------------------- forward
def _layer_masks(masks: dict | None, stack: str) -> dict | None:
    if not masks:
        return None
    prefix = stack + "/mlp/"
    out = {k[len(prefix):]: v for k, v in masks.items()
           if k.startswith(prefix)}
    return out or None


def _moe_shardmap(cfg, p, x, masks, dist):
    """EP over the model axis: tokens replicated across 'model', local
    experts per shard, psum combine (DESIGN.md §4)."""
    from jax.sharding import PartitionSpec as P
    ma = dist.model_axis
    bp = dist.batch_pspec(3)
    rep = P()
    p_specs = {k: (P(ma, None, None) if k in ("w_gate", "w_up", "w_down")
                   else rep) for k in p}
    if masks:
        m_specs = {k: (P(ma, None, None)
                       if k in ("w_gate", "w_up", "w_down") else rep)
                   for k in masks}
    else:
        m_specs = None

    def body(x_l, p_l, m_l):
        y, aux = moe_mod.moe_forward(cfg, p_l, x_l, masks=m_l,
                                     axis_name=ma)
        if dist.batch_axes:
            aux = jax.lax.pmean(aux, dist.batch_axes)
        return y, aux

    y, aux = jax.shard_map(body, mesh=dist.mesh,
                           in_specs=(bp, p_specs, m_specs),
                           out_specs=(bp, rep),
                           check_vma=False)(x, p, masks)
    return y, aux


@jax.named_scope("mlp")
def mlp_forward(cfg, p, x, masks, dist=None):
    if cfg.is_moe:
        if dist is not None and dist.mesh is not None \
                and not dist.inside_shard_map:
            return _moe_shardmap(cfg, p, x, masks, dist)
        axis = dist.model_axis if (dist and dist.inside_shard_map) else None
        y, aux = moe_mod.moe_forward(cfg, p, x, masks=masks,
                                     axis_name=axis)
        return y, aux
    if cfg.mlp_kind == "glu":
        y = sm.glu_mlp(x, p["w_gate"], p["w_up"], p["w_down"],
                       act=cfg.mlp_act, masks=masks, spec=cfg.blast)
    else:
        y = sm.mlp2(x, p["w_in"], p["w_out"], p.get("b_in"),
                    p.get("b_out"), act=cfg.mlp_act, masks=masks,
                    spec=cfg.blast)
    return y, 0.0


def _block(cfg, p, x, positions, masks, *, window, dist=None):
    """One pre-norm transformer block (full attention)."""
    with jax.named_scope("norm"):
        h = norm(cfg.norm_kind, x, p["ln_attn_scale"],
                 p.get("ln_attn_bias"))
    a, _ = attn.multihead_attention(cfg, p["attn"], h, positions,
                                    causal=True, window=window)
    with jax.named_scope("attn"):
        x = x + a
    with jax.named_scope("norm"):
        h = norm(cfg.norm_kind, x, p["ln_mlp_scale"], p.get("ln_mlp_bias"))
    m, aux = mlp_forward(cfg, p["mlp"], h, masks, dist)
    with jax.named_scope("mlp"):
        return x + m, aux


@jax.named_scope("embed")
def embed_inputs(cfg, params, tokens, patch_embeds=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = x.astype(jnp.dtype(cfg.compute_dtype))
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    if patch_embeds is not None and cfg.num_patches:
        p = patch_embeds.astype(x.dtype)
        x = jnp.concatenate([p, x[:, cfg.num_patches:]], axis=1)
    return x


@jax.named_scope("lm_head")
def logits_from_hidden(cfg, params, x, dist=None):
    xf = norm(cfg.norm_kind, x, params["ln_f_scale"],
              params.get("ln_f_bias"))
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", xf, head.astype(xf.dtype))
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    if dist is not None:
        logits = dist.constrain_logits(logits)
    return logits


def forward(cfg, params, tokens, *, masks=None, patch_embeds=None,
            dist=None):
    """Training/prefill forward -> (logits (B,S,V) f32, aux_loss)."""
    b, s = tokens.shape
    x = embed_inputs(cfg, params, tokens, patch_embeds)
    if dist is not None:
        x = dist.constrain_seq(x)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    def body(carry, xs):
        x, aux = carry
        if cfg.layer_pattern == "local_global":
            p_loc, m_loc, p_glb, m_glb = xs
            x, a1 = _block(cfg, p_loc, x, positions, m_loc,
                           window=cfg.sliding_window, dist=dist)
            x, a2 = _block(cfg, p_glb, x, positions, m_glb,
                           window=0, dist=dist)
            if dist is not None:
                x = dist.constrain_seq(x)
            return (x, aux + a1 + a2), None
        p_l, m_l = xs
        x, a = _block(cfg, p_l, x, positions, m_l,
                      window=cfg.sliding_window, dist=dist)
        if dist is not None:
            x = dist.constrain_seq(x)
        return (x, aux + a), None

    if cfg.remat:
        from repro.models.layers import remat_policy
        body = jax.checkpoint(body, policy=remat_policy(cfg))

    if cfg.layer_pattern == "local_global":
        xs = (params["layers_local"], _layer_masks(masks, "layers_local"),
              params["layers_global"], _layer_masks(masks, "layers_global"))
    else:
        xs = (params["layers"], _layer_masks(masks, "layers"))
    (x, aux), _ = jax.lax.scan(body, (x, 0.0), xs)
    return logits_from_hidden(cfg, params, x, dist), aux


# ------------------------------------------------------------------ decode
def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    ns, per = n_stacks(cfg)
    _, kv = attn.eff_heads(cfg)
    shape = (ns * per, batch, max_len, kv, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    ns, per = n_stacks(cfg)
    _, kv = attn.eff_heads(cfg)
    shape = (ns * per, batch, max_len, kv, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shape, dtype),
            "v": jax.ShapeDtypeStruct(shape, dtype)}


def init_paged_cache(cfg, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16):
    """Paged KV pool: (layers, n_pages, page_size, KV, hd), SHARED by
    every lane — lanes map logical slots to pool pages through per-lane
    block tables (engine.py), and total servable context is bounded by
    ``n_pages * page_size`` instead of ``max_batch * max_len``. A pool
    page is allocated for a lane across ALL layers at once, so the block
    table is layer-independent."""
    ns, per = n_stacks(cfg)
    _, kv = attn.eff_heads(cfg)
    shape = (ns * per, n_pages, page_size, kv, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _run_stack(cfg, params, cache, x, masks, dist, attn_fn):
    """Scan the layer stack with a pluggable attention core — the single
    implementation behind contiguous/paged decode and chunked prefill
    (they differ ONLY in how attention reads/writes the cache).

    The whole layer-stacked cache rides in the scan CARRY beside the
    hidden state: each layer writes its new K/V rows at ``[layer, ...]``
    and reads its own layer out of the stack, so the cache is updated in
    place. Scanned as ``xs``/``ys`` instead, every layer would slice its
    whole cache out and the scan would restack it, and a caller's outer
    loop (the decode slab) would copy the stacked result back into its
    own carry at every step.

    ``attn_fn(p_attn, h, cache_k, cache_v, layer, window) -> (attn_out,
    new_k, new_v)`` where cache_k/v are the whole stacked arrays and
    ``layer`` the traced layer index. Returns (hidden, new_cache)."""
    def one(window, p_l, m_l, x, aux, ck, cv, layer):
        with jax.named_scope("norm"):
            h = norm(cfg.norm_kind, x, p_l["ln_attn_scale"],
                     p_l.get("ln_attn_bias"))
        a, ck, cv = attn_fn(p_l["attn"], h, ck, cv, layer, window)
        with jax.named_scope("attn"):
            x = x + a
        with jax.named_scope("norm"):
            h = norm(cfg.norm_kind, x, p_l["ln_mlp_scale"],
                     p_l.get("ln_mlp_bias"))
        m, al = mlp_forward(cfg, p_l["mlp"], h, m_l, dist)
        with jax.named_scope("mlp"):
            return x + m, aux + al, ck, cv

    def body(carry, xs):
        # i: the first cache layer of this scan step, carried as a
        # counter (``per`` layers per step)
        x, aux, ck, cv, i = carry
        if cfg.layer_pattern == "local_global":
            p_loc, m_loc, p_glb, m_glb = xs
            x, aux, ck, cv = one(cfg.sliding_window, p_loc, m_loc, x, aux,
                                 ck, cv, i)
            x, aux, ck, cv = one(0, p_glb, m_glb, x, aux, ck, cv, i + 1)
        else:
            p_l, m_l = xs
            x, aux, ck, cv = one(cfg.sliding_window, p_l, m_l, x, aux,
                                 ck, cv, i)
        return (x, aux, ck, cv, i + per), None

    _, per = n_stacks(cfg)
    if cfg.layer_pattern == "local_global":
        xs = (params["layers_local"], _layer_masks(masks, "layers_local"),
              params["layers_global"], _layer_masks(masks, "layers_global"))
    else:
        xs = (params["layers"], _layer_masks(masks, "layers"))
    (x, _, ck, cv, _), _ = jax.lax.scan(
        body, (x, 0.0, cache["k"], cache["v"], jnp.int32(0)), xs)
    return x, dict(cache, k=ck, v=cv)


def decode_step(cfg, params, cache, tokens, pos, *, masks=None, dist=None,
                offsets=None):
    """One decode step. tokens: (B,1); pos: CACHE SLOT — scalar int32
    (synchronized batch) or (B,) int32 vector (per-lane frontiers: lane
    b writes slot ``pos[b]``; out-of-range slots drop the write —
    engine slab decode parks finished lanes at Smax).

    ``offsets`` (B,) makes the batch ragged: lane b's logical position
    is ``pos[b] - offsets[b]`` (engine.py). ``None`` with scalar ``pos``
    keeps the synchronized path bitwise-unchanged.
    Returns (logits (B,1,V), new_cache)."""
    x = embed_inputs(cfg, params, tokens)

    def attn_fn(p_a, h, ck, cv, layer, window):
        return attn.decode_attention(cfg, p_a, h, ck, cv, pos,
                                     window=window, offsets=offsets,
                                     layer=layer)

    x, new_cache = _run_stack(cfg, params, cache, x, masks, dist, attn_fn)
    return logits_from_hidden(cfg, params, x), new_cache


def paged_decode_step(cfg, params, cache, tokens, pos, block_tables, *,
                      read_pages: int, masks=None, dist=None,
                      offsets=None, attn_backend: str = "xla"):
    """One decode step over the PAGED pool cache (init_paged_cache).
    tokens: (B,1); pos: (B,) logical cache slots (parked lanes carry
    ``max_pages * page_size`` — the write drops); block_tables:
    (B, max_pages) int32; ``read_pages`` STATIC — attention reads only
    each lane's first ``read_pages`` pages, so per-token attention bytes
    scale with the live frontier, not the cache extent.
    Returns (logits (B,1,V), new_cache)."""
    x = embed_inputs(cfg, params, tokens)

    def attn_fn(p_a, h, ck, cv, layer, window):
        return attn.paged_decode_attention(
            cfg, p_a, h, ck, cv, block_tables, pos,
            read_pages=read_pages, window=window, offsets=offsets,
            backend=attn_backend, layer=layer)

    x, new_cache = _run_stack(cfg, params, cache, x, masks, dist, attn_fn)
    return logits_from_hidden(cfg, params, x), new_cache


def prefill_chunk(cfg, params, cache, tokens, slot, offsets, *,
                  masks=None, dist=None, lane_mask=None):
    """Batched chunked prefill: run a whole (B, C) chunk of right-aligned
    prompt tokens through every layer in one jitted call, writing K/V at
    cache slots [slot, slot+C) — replaces the token-by-token Python
    prefill loop (paper §5.2 serving setting, continuous batching).

    tokens: (B,C); slot: scalar int32 start slot; offsets: (B,) left-pad
    per lane (logical position of slot s is ``s - offsets[b]``);
    ``lane_mask`` (B,) bool — lanes with False keep their existing cache
    rows untouched (they are mid-decode while new lanes prefill behind
    their frontier). Returns (logits (B,C,V) f32, new_cache)."""
    x = embed_inputs(cfg, params, tokens)

    def attn_fn(p_a, h, ck, cv, layer, window):
        return attn.chunk_attention(cfg, p_a, h, ck, cv, slot, offsets,
                                    window=window, lane_mask=lane_mask,
                                    layer=layer)

    x, new_cache = _run_stack(cfg, params, cache, x, masks, dist, attn_fn)
    return logits_from_hidden(cfg, params, x), new_cache


def paged_prefill_chunk(cfg, params, cache, tokens, slot, offsets,
                        block_tables, *, read_pages: int, masks=None,
                        dist=None, lane_mask=None, q_lens=None):
    """Chunked prefill over the PAGED pool: the chunk's K/V lands at
    logical slots [slot, slot+C) through each lane's block table (pages
    pre-allocated by the engine); attention reads each lane's first
    ``read_pages`` pages (STATIC — must cover slot+C).

    ``slot`` may also be a (B,) vector of per-lane start slots and
    ``q_lens`` a (B,) per-lane query-run length — the MIXED batch shape
    (serving/step.py make_mixed_step): decode lanes contribute one
    token (q_len 1 at their frontier) while admitting lanes contribute
    a prefill chunk, through one pass of the same ``_run_stack`` core.
    Returns (logits (B,C,V) f32, new_cache)."""
    x = embed_inputs(cfg, params, tokens)

    def attn_fn(p_a, h, ck, cv, layer, window):
        return attn.paged_chunk_attention(
            cfg, p_a, h, ck, cv, block_tables, slot, offsets,
            read_pages=read_pages, window=window, lane_mask=lane_mask,
            q_lens=q_lens, layer=layer)

    x, new_cache = _run_stack(cfg, params, cache, x, masks, dist, attn_fn)
    return logits_from_hidden(cfg, params, x), new_cache

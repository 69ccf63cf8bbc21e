"""whisper-large-v3 [audio]: encoder-decoder transformer backbone.

The conv/mel frontend is a STUB per the task statement: ``input_specs()``
provides precomputed frame embeddings (B, S_enc, d_model). Learned
position embeddings (whisper style, sized to the assigned shapes);
decoder layers interleave causal self-attention and cross-attention into
the encoder memory. BLaST applies to both encoder and decoder MLPs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import sparse_mlp as sm
from repro.models import attention as attn_mod
from repro.models.layers import norm
from repro.models.params import ParamSpec
from repro.models.transformer import (_layer_masks, _norm_specs,
                                      _stack_specs, mlp_param_specs)

MAX_POS = 16_384   # backbone scaled to the assigned shapes (prefill 16k)


def enc_layer_specs(cfg) -> dict:
    specs = {}
    specs.update(_norm_specs(cfg, "ln_attn"))
    specs["attn"] = attn_mod.attn_param_specs(cfg)
    specs.update(_norm_specs(cfg, "ln_mlp"))
    specs["mlp"] = mlp_param_specs(cfg)
    return specs


def dec_layer_specs(cfg) -> dict:
    specs = enc_layer_specs(cfg)
    specs.update(_norm_specs(cfg, "ln_cross"))
    specs["cross"] = attn_mod.attn_param_specs(cfg, cross=True)
    return specs


def param_specs(cfg) -> dict:
    d = cfg.d_model
    specs = {
        "embed": ParamSpec((cfg.vocab_size, d), ("vocab", "embed"),
                           init="embed"),
        "pos_enc": ParamSpec((MAX_POS, d), (None, "embed"), init="embed"),
        "pos_dec": ParamSpec((MAX_POS, d), (None, "embed"), init="embed"),
        "encoder": _stack_specs(enc_layer_specs(cfg),
                                cfg.num_encoder_layers),
        "decoder": _stack_specs(dec_layer_specs(cfg), cfg.num_layers),
        "lm_head": ParamSpec((d, cfg.vocab_size), ("embed", "vocab"),
                             init="embed"),
    }
    specs.update(_norm_specs(cfg, "ln_f"))
    specs.update(_norm_specs(cfg, "ln_enc_f"))
    return specs


def sparse_paths(cfg) -> list[str]:
    return ["encoder/mlp/w_in", "encoder/mlp/w_out",
            "decoder/mlp/w_in", "decoder/mlp/w_out"]


def dense_layer_flags(cfg):
    """Per-stack flags (encoder/decoder depths differ in smoke configs);
    the last L layers of EACH stack stay dense (paper §5.4.4)."""
    def flags(n):
        return jnp.arange(n) >= (n - cfg.blast.dense_last)
    return {"encoder": flags(cfg.num_encoder_layers),
            "decoder": flags(cfg.num_layers)}


def encode(cfg, params, frames, *, masks=None, dist=None):
    """frames: (B, S_enc, D) precomputed embeddings (stub frontend)."""
    b, s, _ = frames.shape
    x = frames.astype(jnp.dtype(cfg.compute_dtype))
    x = x + params["pos_enc"][:s].astype(x.dtype)
    if dist is not None:
        x = dist.constrain_seq(x)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    emasks = _layer_masks(masks, "encoder")

    def body(carry, xs_):
        x, = carry
        p_l, m_l = xs_
        h = norm(cfg.norm_kind, x, p_l["ln_attn_scale"],
                 p_l.get("ln_attn_bias"))
        a, _ = attn_mod.multihead_attention(cfg, p_l["attn"], h,
                                            positions, causal=False)
        x = x + a
        h = norm(cfg.norm_kind, x, p_l["ln_mlp_scale"],
                 p_l.get("ln_mlp_bias"))
        m = sm.mlp2(h, p_l["mlp"]["w_in"], p_l["mlp"]["w_out"],
                    p_l["mlp"].get("b_in"), p_l["mlp"].get("b_out"),
                    act=cfg.mlp_act, masks=m_l, spec=cfg.blast)
        x = x + m
        if dist is not None:
            x = dist.constrain_seq(x)
        return (x,), None

    if cfg.remat:
        from repro.models.layers import remat_policy
        body = jax.checkpoint(body, policy=remat_policy(cfg))
    (x,), _ = jax.lax.scan(body, (x,), (params["encoder"], emasks))
    return norm(cfg.norm_kind, x, params["ln_enc_f_scale"],
                params.get("ln_enc_f_bias"))


def _dec_block(cfg, p_l, m_l, x, positions, memory, mem_positions):
    h = norm(cfg.norm_kind, x, p_l["ln_attn_scale"],
             p_l.get("ln_attn_bias"))
    a, kv = attn_mod.multihead_attention(cfg, p_l["attn"], h, positions,
                                         causal=True)
    x = x + a
    h = norm(cfg.norm_kind, x, p_l["ln_cross_scale"],
             p_l.get("ln_cross_bias"))
    c, cross_kv = attn_mod.multihead_attention(
        cfg, p_l["cross"], h, positions, causal=False, kv_src=memory,
        kv_positions=mem_positions)
    x = x + c
    h = norm(cfg.norm_kind, x, p_l["ln_mlp_scale"],
             p_l.get("ln_mlp_bias"))
    m = sm.mlp2(h, p_l["mlp"]["w_in"], p_l["mlp"]["w_out"],
                p_l["mlp"].get("b_in"), p_l["mlp"].get("b_out"),
                act=cfg.mlp_act, masks=m_l, spec=cfg.blast)
    return x + m, kv, cross_kv


def forward(cfg, params, tokens, *, frames=None, masks=None, dist=None,
            **_):
    """Training forward: frames (B,S_enc,D) + tokens (B,S_dec)."""
    memory = encode(cfg, params, frames, masks=masks, dist=dist)
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    x = x + params["pos_dec"][:s].astype(x.dtype)
    if dist is not None:
        x = dist.constrain_seq(x)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    mem_positions = jnp.broadcast_to(
        jnp.arange(memory.shape[1], dtype=jnp.int32),
        (b, memory.shape[1]))
    dmasks = _layer_masks(masks, "decoder")

    def body(carry, xs_):
        x, = carry
        p_l, m_l = xs_
        x, _, _ = _dec_block(cfg, p_l, m_l, x, positions, memory,
                             mem_positions)
        if dist is not None:
            x = dist.constrain_seq(x)
        return (x,), None

    if cfg.remat:
        from repro.models.layers import remat_policy
        body = jax.checkpoint(body, policy=remat_policy(cfg))
    (x,), _ = jax.lax.scan(body, (x,), (params["decoder"], dmasks))
    from repro.models.transformer import logits_from_hidden
    return logits_from_hidden(cfg, params, x, dist), 0.0


def prefill_cross(cfg, params, frames, *, masks=None, dist=None,
                  dtype=jnp.bfloat16):
    """Run the encoder and project per-decoder-layer cross K/V — fills
    the 'ck'/'cv' slots of the decode cache."""
    memory = encode(cfg, params, frames, masks=masks, dist=dist)

    def proj(p_l):
        k = jnp.einsum("bsd,dhk->bshk", memory,
                       p_l["cross"]["wk"].astype(memory.dtype))
        v = jnp.einsum("bsd,dhk->bshk", memory,
                       p_l["cross"]["wv"].astype(memory.dtype))
        if cfg.qkv_bias:
            k = k + p_l["cross"]["bk"].astype(k.dtype)
            v = v + p_l["cross"]["bv"].astype(v.dtype)
        return k.astype(dtype), v.astype(dtype)

    ck, cv = jax.lax.map(proj, params["decoder"])
    return ck, cv


# ------------------------------------------------------------------ decode
def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               enc_len: int | None = None):
    """Self-attn cache (decoder) + projected encoder memory K/V."""
    enc_len = enc_len or max_len
    _, kv = attn_mod.eff_heads(cfg)
    L = cfg.num_layers
    return {
        "k": jnp.zeros((L, batch, max_len, kv, cfg.head_dim), dtype),
        "v": jnp.zeros((L, batch, max_len, kv, cfg.head_dim), dtype),
        "ck": jnp.zeros((L, batch, enc_len, kv, cfg.head_dim), dtype),
        "cv": jnp.zeros((L, batch, enc_len, kv, cfg.head_dim), dtype),
    }


def abstract_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
                   enc_len: int | None = None):
    # eval_shape: NO allocation (decode_32k whisper cache is ~1 TB)
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, dtype, enc_len))


def decode_step(cfg, params, cache, tokens, pos, *, masks=None,
                dist=None):
    b = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    x = x + jax.lax.dynamic_slice_in_dim(
        params["pos_dec"], pos, 1).astype(x.dtype)
    dmasks = _layer_masks(masks, "decoder")

    def body(carry, xs_):
        x, = carry
        p_l, m_l, ck, cv, cck, ccv = xs_
        h = norm(cfg.norm_kind, x, p_l["ln_attn_scale"],
                 p_l.get("ln_attn_bias"))
        a, nk, nv = attn_mod.decode_attention(cfg, p_l["attn"], h,
                                              ck[None], cv[None], pos,
                                              layer=0)
        x = x + a
        h = norm(cfg.norm_kind, x, p_l["ln_cross_scale"],
                 p_l.get("ln_cross_bias"))
        c, _, _ = attn_mod.decode_attention(cfg, p_l["cross"], h,
                                            cck[None], ccv[None], pos,
                                            layer=0, cross=True)
        x = x + c
        h = norm(cfg.norm_kind, x, p_l["ln_mlp_scale"],
                 p_l.get("ln_mlp_bias"))
        m = sm.mlp2(h, p_l["mlp"]["w_in"], p_l["mlp"]["w_out"],
                    p_l["mlp"].get("b_in"), p_l["mlp"].get("b_out"),
                    act=cfg.mlp_act, masks=m_l, spec=cfg.blast)
        return (x + m,), (nk[0], nv[0])

    xs_ = (params["decoder"], dmasks, cache["k"], cache["v"],
           cache["ck"], cache["cv"])
    (x,), (nk, nv) = jax.lax.scan(body, (x,), xs_)
    new_cache = dict(cache, k=nk, v=nv)
    from repro.models.transformer import logits_from_hidden
    return logits_from_hidden(cfg, params, x), new_cache

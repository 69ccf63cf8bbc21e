"""zamba2 [hybrid]: a stack of Mamba2 (SSD) layers with ONE weight-shared
transformer block (attention + GLU MLP) applied every ``attn_every``
layers (arXiv:2411.15242; per-invocation LoRA omitted — DESIGN.md §8).

Mamba2 layer: in_proj -> [z | x | B | C | dt]; causal depthwise conv on
(x,B,C); scalar-per-head decay a_t = exp(-softplus(dt + bias)·exp(A_log));
SSD evaluated with the shared chunked linear scan ('full' diagonal mode);
gated RMSNorm; out_proj. BLaST applies to the shared block's MLP only
(the Mamba mixers are attention-analogue weights).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import sparse_mlp as sm
from repro.models import attention as attn_mod
from repro.models.layers import norm, rmsnorm
from repro.models.linear_scan import (chunked_linear_attention,
                                      chunked_ssd, recurrent_step,
                                      ssd_recurrent_step)
from repro.models.params import ParamSpec


def _dims(cfg):
    d_inner = cfg.ssm_expand * cfg.d_model
    nheads = cfg.ssm_heads
    headdim = d_inner // nheads
    return d_inner, nheads, headdim, cfg.ssm_state


def mamba_param_specs(cfg) -> dict:
    d = cfg.d_model
    d_inner, nheads, headdim, state = _dims(cfg)
    conv_dim = d_inner + 2 * state
    proj_out = 2 * d_inner + 2 * state + nheads
    return {
        "ln_scale": ParamSpec((d,), ("embed",), init="zeros"),
        "in_proj": ParamSpec((d, proj_out), ("embed", "ssm_proj")),
        "conv_w": ParamSpec((cfg.conv_kernel, conv_dim),
                            (None, "ssm_conv"), init="normal", scale=1.0),
        "conv_b": ParamSpec((conv_dim,), ("ssm_conv",), init="zeros"),
        "a_log": ParamSpec((nheads,), ("ssm_heads",), init="zeros"),
        "dt_bias": ParamSpec((nheads,), ("ssm_heads",), init="zeros"),
        "d_skip": ParamSpec((nheads,), ("ssm_heads",), init="ones"),
        "norm_scale": ParamSpec((d_inner,), ("ssm_inner",), init="zeros"),
        "out_proj": ParamSpec((d_inner, d), ("ssm_inner", "embed"),
                              scale=1.0 / math.sqrt(2 * cfg.num_layers)),
    }


def shared_block_specs(cfg) -> dict:
    from repro.models.transformer import _norm_specs, mlp_param_specs
    specs = {}
    specs.update(_norm_specs(cfg, "ln_attn"))
    specs["attn"] = attn_mod.attn_param_specs(cfg)
    specs.update(_norm_specs(cfg, "ln_mlp"))
    specs["mlp"] = mlp_param_specs(cfg)
    return specs


def param_specs(cfg) -> dict:
    from repro.models.transformer import _norm_specs, _stack_specs
    specs = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model),
                           ("vocab", "embed"), init="embed"),
        "layers": _stack_specs(mamba_param_specs(cfg), cfg.num_layers),
        "shared": shared_block_specs(cfg),
        "lm_head": ParamSpec((cfg.d_model, cfg.vocab_size),
                             ("embed", "vocab"), init="embed"),
    }
    specs.update(_norm_specs(cfg, "ln_f"))
    return specs


def sparse_paths(cfg) -> list[str]:
    return ["shared/mlp/w_gate", "shared/mlp/w_up", "shared/mlp/w_down"]


def dense_layer_flags(cfg):
    return None   # the single shared MLP is sparsified as a whole


def n_shared_applications(cfg) -> int:
    return len([i for i in range(cfg.num_layers)
                if i % cfg.attn_every == 0])


def _split_proj(cfg, zxbcdt):
    d_inner, nheads, headdim, state = _dims(cfg)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + d_inner + 2 * state]
    dt = zxbcdt[..., -nheads:]
    return z, xbc, dt


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x: (B,S,C); w: (K,C). Returns (y, tail)
    where tail = last K-1 inputs (decode state)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros_like(x[:, :k - 1])
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
            for i in range(k))
    y = jax.nn.silu(y + b.astype(x.dtype))
    return y, xp[:, -(k - 1):]


def mamba_mixer(cfg, p, x, *, ssm_state=None, conv_state=None,
                decode=False):
    """x: (B,S,D) -> (y, (new_ssm_state, new_conv_state))."""
    d_inner, nheads, headdim, state = _dims(cfg)
    b, s, _ = x.shape
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z, xbc, dt = _split_proj(cfg, zxbcdt)
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xs = xbc[..., :d_inner].reshape(b, s, nheads, headdim)
    bmat = xbc[..., d_inner:d_inner + state]           # (B,S,state)
    cmat = xbc[..., d_inner + state:]                  # (B,S,state)
    dt_s = jax.nn.softplus(dt.astype(jnp.float32)
                           + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))       # (H,)
    log_a = dt_s * a                                   # (B,S,H) scalar
    # Grouped SSD (n_groups=1): B/C shared across heads, per-head scalar
    # decay — never materialises (B,S,H,d_state) broadcasts
    # (EXPERIMENTS.md §Perf, zamba2 iteration)
    v = xs * dt_s[..., None].astype(xs.dtype)
    if attn_mod.DRYRUN_BASELINE:            # pre-optimization variant
        log_w = jnp.broadcast_to((dt_s * a)[..., None],
                                 (b, s, nheads, state))
        q = jnp.broadcast_to(cmat[:, :, None], (b, s, nheads, state))
        k = jnp.broadcast_to(bmat[:, :, None], (b, s, nheads, state))
        if decode:
            y, new_ssm = recurrent_step(q[:, 0], k[:, 0], v[:, 0],
                                        log_w[:, 0], ssm_state,
                                        chunk=cfg.chunk_size,
                                        include_diag="full")
            y = y[:, None]
        else:
            y, new_ssm = chunked_linear_attention(
                q, k, v, log_w, chunk=cfg.chunk_size,
                initial_state=ssm_state, include_diag="full")
    elif decode:
        y, new_ssm = ssd_recurrent_step(cmat[:, 0], bmat[:, 0], v[:, 0],
                                        log_a[:, 0], ssm_state)
        y = y[:, None]
    else:
        y, new_ssm = chunked_ssd(cmat, bmat, v, log_a,
                                 chunk=cfg.chunk_size,
                                 initial_state=ssm_state)
    y = y + xs * p["d_skip"].astype(x.dtype)[:, None]
    y = y.reshape(b, s, d_inner)
    # gated RMSNorm (mamba2)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_scale"])
    return y @ p["out_proj"].astype(x.dtype), (new_ssm, new_conv)


def _shared_block(cfg, p, x, positions, masks, cache=None, pos=None):
    """The weight-shared attention+MLP block. With ``cache`` (decode):
    cache = (ck, cv) for THIS application. Returns (x, new_cache)."""
    h = norm(cfg.norm_kind, x, p["ln_attn_scale"], p.get("ln_attn_bias"))
    if cache is None:
        a, _ = attn_mod.multihead_attention(cfg, p["attn"], h, positions,
                                            causal=True)
        new_cache = None
    else:
        a, nk, nv = attn_mod.decode_attention(
            cfg, p["attn"], h, cache[0][None], cache[1][None], pos, layer=0)
        new_cache = (nk[0], nv[0])
    x = x + a
    h = norm(cfg.norm_kind, x, p["ln_mlp_scale"], p.get("ln_mlp_bias"))
    from repro.models.transformer import _layer_masks
    m = sm.glu_mlp(h, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                   p["mlp"]["w_down"], act=cfg.mlp_act,
                   masks=masks, spec=cfg.blast)
    return x + m, new_cache


def _shared_masks(masks):
    if not masks:
        return None
    prefix = "shared/mlp/"
    out = {k[len(prefix):]: v for k, v in masks.items()
           if k.startswith(prefix)}
    return out or None


def forward(cfg, params, tokens, *, masks=None, dist=None, **_):
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if dist is not None:
        x = dist.constrain_seq(x)
    smasks = _shared_masks(masks)

    def body(carry, xs_):
        x, i = carry
        p_l = xs_

        def with_attn(x):
            y, _ = _shared_block(cfg, params["shared"], x, positions,
                                 smasks)
            return y

        x = jax.lax.cond(i % cfg.attn_every == 0, with_attn,
                         lambda x: x, x)
        h = norm(cfg.norm_kind, x, p_l["ln_scale"], None)
        y, _ = mamba_mixer(cfg, p_l, h)
        x = x + y
        if dist is not None:
            x = dist.constrain_seq(x)
        return (x, i + 1), None

    if cfg.remat:
        from repro.models.layers import remat_policy
        body = jax.checkpoint(body, policy=remat_policy(cfg))
    (x, _), _ = jax.lax.scan(body, (x, 0), params["layers"])
    from repro.models.transformer import logits_from_hidden
    return logits_from_hidden(cfg, params, x, dist), 0.0


# ------------------------------------------------------------------ decode
def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    d_inner, nheads, headdim, state = _dims(cfg)
    napp = n_shared_applications(cfg)
    _, kv = attn_mod.eff_heads(cfg)
    return {
        "ssm": jnp.zeros((cfg.num_layers, batch, nheads, state, headdim),
                         jnp.float32),
        "conv": jnp.zeros((cfg.num_layers, batch, cfg.conv_kernel - 1,
                           d_inner + 2 * state), dtype),
        "k": jnp.zeros((napp, batch, max_len, kv, cfg.head_dim), dtype),
        "v": jnp.zeros((napp, batch, max_len, kv, cfg.head_dim), dtype),
    }


def abstract_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype))


def decode_step(cfg, params, cache, tokens, pos, *, masks=None, dist=None):
    x = jnp.take(params["embed"], tokens, axis=0).astype(
        jnp.dtype(cfg.compute_dtype))
    smasks = _shared_masks(masks)
    napp = n_shared_applications(cfg)

    # shared-attn applications run OUTSIDE the mamba scan (python loop
    # over the napp cache slots, interleaved by layer index)
    new_k, new_v = [], []
    app_layers = [i for i in range(cfg.num_layers)
                  if i % cfg.attn_every == 0]

    def mamba_span(x, lo, hi, cache):
        """Scan mamba layers [lo, hi) functionally."""
        sl = lambda t: t[lo:hi]

        def body(carry, xs_):
            x, = carry
            p_l, st, cv = xs_
            h = norm(cfg.norm_kind, x, p_l["ln_scale"], None)
            y, (nst, ncv) = mamba_mixer(cfg, p_l, h, ssm_state=st,
                                        conv_state=cv, decode=True)
            return (x + y,), (nst, ncv)

        xs_ = (jax.tree_util.tree_map(sl, params["layers"]),
               sl(cache["ssm"]), sl(cache["conv"]))
        (x,), (nst, ncv) = jax.lax.scan(body, (x,), xs_)
        return x, nst, ncv

    ssm_parts, conv_parts = [], []
    spans = app_layers + [cfg.num_layers]
    for j, lo in enumerate(app_layers):
        x, nc = _shared_block(cfg, params["shared"], x, None, smasks,
                              cache=(cache["k"][j], cache["v"][j]),
                              pos=pos)
        new_k.append(nc[0])
        new_v.append(nc[1])
        hi = spans[j + 1]
        x, nst, ncv = mamba_span(x, lo, hi, cache)
        ssm_parts.append(nst)
        conv_parts.append(ncv)

    new_cache = {
        "ssm": jnp.concatenate(ssm_parts, 0),
        "conv": jnp.concatenate(conv_parts, 0),
        "k": jnp.stack(new_k),
        "v": jnp.stack(new_v),
    }
    del napp
    from repro.models.transformer import logits_from_hidden
    return logits_from_hidden(cfg, params, x), new_cache

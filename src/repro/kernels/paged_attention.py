"""Paged-KV decode attention as a Pallas TPU blocked-gather kernel.

The XLA paged path (models/attention.py ``gather_pages`` +
``_scores_to_out``) materialises a (B, R*ps, KV, hd) gathered copy of
each lane's live pages in HBM before the attention core reads it — the
bytes are right, but they move twice. This kernel reuses the BCSC-style
block-gather machinery of ``bspmm.py``: the scalar-prefetched block
table drives the ``BlockSpec.index_map`` of the K/V pool operands, so
Mosaic's pipeline DMAs each live page HBM->VMEM exactly once, straight
into a flash-decode online-softmax accumulation — no gathered
intermediate ever exists (the paper's "only necessary blocks are
loaded", applied to the KV cache instead of the weights).

grid = (lanes, pages); the page axis is ``arbitrary`` (it carries the
running max / sum / accumulator scratch), lanes are parallel. One grid
step DMAs one whole pool page across ALL kv heads, of the layer the
scalar-prefetched index names in the layer-stacked pool — the K/V block
``(1, ps, KV, hd)`` keeps the pool's last two dims whole, which is what
the TPU's (8, 128) tiling rule accepts for any KV; cutting one head out
(a ``(1, ps, 1, hd)`` block) is refused whenever KV > 1. Every head of
the page is then folded in-kernel on the vector unit: scores are a
broadcast multiply and a lane reduction over ``hd``, the value update a
reduction over the page's ``ps`` rows, so the pool layout (and with it
``transformer.init_paged_cache``, offload and recovery) stays as it is.
Masking (causal, window, ragged left-pad) arrives as an additive-bias
row per (lane, slot) — precomputed in XLA from the same
``_cache_positions`` logic as the dense path, so the two paths mask
identically.

Validated in interpret mode against the XLA gather path
(tests/test_paged_kv.py) and compiled for a TPU v5e at stablelm-3b
widths (tests/test_tpu_compile.py); the engine picks it via
``attn_backend='pallas'``.

Mixed read-page buckets per lane: the grid reads the SAME ``R`` pages
for every lane even when frontiers differ wildly (the engine buckets
``R`` to the batch max). A lane whose live context is shorter than
``R`` pages has block-table entries past its allocation pointing at
pool page 0 — a page that may belong to another lane — so tolerating
mixed buckets means those reads must contribute NOTHING: the bias row
marks every slot past the lane's frontier NEG_INF (causal mask), the
``valid`` guard zeroes their probabilities before the accumulator sees
them, and a fully-masked page leaves m/l/acc untouched. Verified by
tests/test_paged_kv.py::test_kernel_tolerates_mixed_read_buckets
(one-page lane next to a many-page lane under one shared bucket).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(scale, softcap, bt_ref, layer_ref, q_ref, k_ref,
                         v_ref, bias_ref, o_ref, acc_ref, m_ref, l_ref):
    """One (lane b, page j) grid step: fold pool page bt[b, j] into lane
    b's online softmax for every head. Per query group g the running
    state is kept per kv head: m/l (KV, 1), acc (KV, hd)."""
    j = pl.program_id(1)
    npg = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k = k_ref[0].astype(jnp.float32)                 # (ps, KV, hd)
    v = v_ref[0]
    valid = bias_ref[0, 0] > NEG_INF / 2             # (ps, 1, 1)
    for g in range(q_ref.shape[1]):
        q = q_ref[0, g].astype(jnp.float32)          # (KV, hd)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)             # (ps, KV, 1)

        m_prev = m_ref[g]                            # (KV, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])
        p = jnp.where(valid, p, 0.0)                 # fully-masked pages
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=0)
        pv = p.astype(v.dtype).astype(jnp.float32) * v.astype(jnp.float32)
        acc_ref[g] = acc_ref[g] * alpha + jnp.sum(pv, axis=0)
        m_ref[g] = m_new

    @pl.when(j == npg - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)   # all-masked lane: garbage,
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)   # discarded


def paged_flash_decode(q4, pool_k, pool_v, block_tables, bias, *,
                       scale: float, layer, softcap: float = 0.0,
                       interpret: bool = False) -> jax.Array:
    """q4: (B, KV, G, hd); pool_k/v: the (L, n_pages, ps, KV, hd) stack,
    whose pages of layer ``layer`` (a scalar int32, prefetched beside the
    block table) the DMAs read in place; block_tables: (B, R) int32 —
    the lanes' first R logical pages; bias: (B, R*ps) f32, 0 where the
    slot may be attended, NEG_INF where masked. Returns (B, KV, G, hd)
    f32."""
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    b, kvh, g, hd = q4.shape
    ps = pool_k.shape[2]
    r = block_tables.shape[1]
    assert bias.shape == (b, r * ps), (bias.shape, b, r, ps)
    # group-major q (a kv head's query rows are one (KV, hd) slab per
    # group) and one (ps, 1, 1) bias column per page: both blocks then
    # keep their array's last two dims whole
    qg = q4.transpose(0, 2, 1, 3)                    # (B, G, KV, hd)
    bias5 = bias.reshape(b, r, ps, 1, 1)

    page = pl.BlockSpec((None, 1, ps, kvh, hd),
                        lambda i, j, bt, lyr: (lyr[0], bt[i, j], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, r),
        in_specs=[
            pl.BlockSpec((1, g, kvh, hd),
                         lambda i, j, bt, lyr: (i, 0, 0, 0)),
            page, page,
            pl.BlockSpec((1, 1, ps, 1, 1),
                         lambda i, j, bt, lyr: (i, j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, kvh, hd),
                               lambda i, j, bt, lyr: (i, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g, kvh, hd), jnp.float32),
                        pltpu.VMEM((g, kvh, 1), jnp.float32),
                        pltpu.VMEM((g, kvh, 1), jnp.float32)],
    )
    kernel = functools.partial(_paged_decode_kernel, scale, softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_flash_decode",
        out_shape=jax.ShapeDtypeStruct((b, g, kvh, hd), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_tables, layer, qg, pool_k, pool_v, bias5)
    return out.transpose(0, 2, 1, 3)


def mask_bias(posb, kpos, window: int = 0) -> jax.Array:
    """(B,1) query positions + (B,S) slot positions -> (B,S) additive
    bias: 0 where the causal (AND optional window) mask admits the slot,
    NEG_INF elsewhere — the dense path's where-mask as a bias row."""
    mask = posb >= kpos
    if window:
        mask &= posb - kpos < window
    return jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32)


def paged_decode_attn(cfg, q, pool_k, pool_v, block_tables, posb, kpos,
                      *, layer, window: int = 0,
                      interpret: bool = False) -> jax.Array:
    """models/attention.py adapter: q (B,1,H,hd) -> out (B,1,H,hd),
    matching ``_scores_to_out``'s grouped layout and mixed precision."""
    b, _, h, hd = q.shape
    kvh = pool_k.shape[3]
    g = h // kvh
    scale = cfg.attn_scale or 1.0 / math.sqrt(hd)
    q4 = q.reshape(b, kvh, g, hd)
    bias = mask_bias(posb, kpos, window)
    out = paged_flash_decode(
        q4, pool_k, pool_v, block_tables, bias, scale=scale,
        softcap=float(cfg.attn_logit_softcap or 0.0), layer=layer,
        interpret=interpret)
    return out.reshape(b, 1, h, hd).astype(q.dtype)

"""Model registry: family -> implementation module, plus generic
init / abstract-params / forward / decode entry points used by the
training loop, serving loop, and dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import params as pmod
from repro.models import rwkv6, transformer, whisper, zamba


def module_for(cfg):
    return {
        "dense": transformer, "moe": transformer, "vlm": transformer,
        "ssm": rwkv6, "hybrid": zamba, "audio": whisper,
    }[cfg.family]


def param_specs(cfg):
    return module_for(cfg).param_specs(cfg)


def init_params(cfg, rng, dtype=None):
    return pmod.init_params(param_specs(cfg), rng, dtype)


def abstract_params(cfg):
    return pmod.abstract_params(param_specs(cfg))


def axes_tree(cfg):
    return pmod.axes_tree(param_specs(cfg))


def sparse_paths(cfg):
    return module_for(cfg).sparse_paths(cfg)


def dense_layer_flags(cfg):
    return module_for(cfg).dense_layer_flags(cfg)


def forward(cfg, params, tokens, **kw):
    return module_for(cfg).forward(cfg, params, tokens, **kw)


def init_cache(cfg, batch, max_len, **kw):
    return module_for(cfg).init_cache(cfg, batch, max_len, **kw)


def abstract_cache(cfg, batch, max_len, **kw):
    return module_for(cfg).abstract_cache(cfg, batch, max_len, **kw)


def decode_step(cfg, params, cache, tokens, pos, **kw):
    """One decode step. ``pos`` is the cache write slot — scalar for a
    synchronized batch, (B,) vector for per-lane frontiers (transformer
    families only; see transformer.decode_step)."""
    return module_for(cfg).decode_step(cfg, params, cache, tokens, pos,
                                       **kw)


def supports_prefill_chunk(cfg) -> bool:
    return hasattr(module_for(cfg), "prefill_chunk")


def supports_paged(cfg) -> bool:
    """Paged KV pool + block-table attention (transformer families)."""
    return hasattr(module_for(cfg), "paged_decode_step")


def supports_mixed(cfg) -> bool:
    """Mixed decode+prefill batches: ``paged_prefill_chunk`` accepting
    per-lane start slots + ``q_lens`` (transformer families — the mixed
    step rides on the paged chunk path, so paged support implies it)."""
    return supports_paged(cfg)


def init_paged_cache(cfg, n_pages, page_size, **kw):
    """Shared paged KV pool (layers, n_pages, page_size, KV, hd); see
    transformer.init_paged_cache."""
    return module_for(cfg).init_paged_cache(cfg, n_pages, page_size, **kw)


def paged_decode_step(cfg, params, cache, tokens, pos, block_tables, *,
                      read_pages, **kw):
    """One decode step over the paged pool: ``pos`` (B,) logical slots,
    ``block_tables`` (B, max_pages), ``read_pages`` static — attention
    reads only each lane's first ``read_pages`` pages."""
    return module_for(cfg).paged_decode_step(
        cfg, params, cache, tokens, pos, block_tables,
        read_pages=read_pages, **kw)


def paged_prefill_chunk(cfg, params, cache, tokens, slot, offsets,
                        block_tables, *, read_pages, **kw):
    """Chunked prefill through the block tables (paged pool)."""
    return module_for(cfg).paged_prefill_chunk(
        cfg, params, cache, tokens, slot, offsets, block_tables,
        read_pages=read_pages, **kw)


def prefill_chunk(cfg, params, cache, tokens, slot, offsets, **kw):
    """Batched chunked prefill (KV-cache families). Writes the chunk's
    K/V at cache slots [slot, slot+C); see transformer.prefill_chunk."""
    mod = module_for(cfg)
    if not hasattr(mod, "prefill_chunk"):
        raise NotImplementedError(
            f"family {cfg.family!r} has no chunked prefill; use the "
            "token-by-token serve_loop.prefill_with_decode path")
    return mod.prefill_chunk(cfg, params, cache, tokens, slot, offsets,
                             **kw)


def count_params(cfg, active_only: bool = False) -> int:
    """Parameter count from the spec tree (no allocation). With
    ``active_only`` MoE expert stacks count only top_k (+shared) experts
    — the N in MODEL_FLOPS = 6·N_active·D."""
    specs = param_specs(cfg)
    leaves, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, pmod.ParamSpec))
    total = 0
    for s in leaves:
        n = 1
        for d in s.shape:
            n *= d
        if active_only and "experts" in s.axes:
            n = n // cfg.num_experts * cfg.top_k
        total += n
    return total


def init_masks(cfg, params):
    """BLaST mask tree for this model (all-kept at init)."""
    from repro.core import sparse_mlp as sm
    if not cfg.blast.enabled:
        return {}
    return sm.init_masks(cfg.blast, params, sparse_paths(cfg),
                         dense_layer_flags(cfg))

"""Export a trained BLaST model for serving (paper §5.2 / Fig. 7):

  * ``prune_params``  — bake masks into weights (zeros in pruned blocks),
    cast to bf16: the baseline serving layout;
  * ``pack_params``   — replace every sparse weight with its balanced-
    BCSC ``PackedBCSC`` (blocks + int32 index table): the 1/(1-s) memory
    reduction and the input the BSpMM kernels consume.

``memory_report`` quantifies the Fig. 7 claim (bytes & #accelerators).
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing, sparse_mlp as sm, topk
from repro.models import registry


class UnbalancedMaskWarning(UserWarning):
    """A mask handed to ``pack_params`` is not balanced: some block-
    columns keep fewer blocks than the max, so the pack zero-pads them
    up to the static ``nnz`` — numerically exact, but the advertised
    1/(1-s) memory reduction silently degrades by the pad fraction."""


def _cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if x.dtype == jnp.float32 else x, tree)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _prune_leaf(w, m, b_in, b_out, dtype):
    # one fused pass: the pruned leaf is the only new buffer
    return _cast(topk.apply_block_mask(w, m, b_in, b_out), dtype)


def prune_params(cfg, params, masks, dtype=jnp.bfloat16):
    out = params
    for path, m in masks.items():
        bi, bo = sm.block_dims_for(cfg.blast, path)
        out = sm.set_path(out, path, _prune_leaf(sm.get_path(params, path),
                                                 m, bi, bo, dtype))
    return _cast(out, dtype)


def pack_params(cfg, params, masks, dtype=jnp.bfloat16,
                unbalanced: str = "warn",
                pad_report: dict | None = None):
    """Sparse leaves -> PackedBCSC (static nnz = max kept per column,
    uniform under balanced selection).

    Gate/up pairs whose masks coincide (joint pruning) are marked
    ``joint`` so the fused GLU kernels stream each X tile once
    (``packing.mark_joint``).

    An UNBALANCED mask no longer packs silently: ``unbalanced`` is
    ``"warn"`` (``UnbalancedMaskWarning`` with the pad fraction),
    ``"raise"`` (``ValueError``), or ``"ignore"``. A caller-supplied
    ``pad_report`` dict is filled ``path -> pad fraction`` for every
    padded path — ``artifact.seal`` records it in the manifest."""
    if unbalanced not in ("warn", "raise", "ignore"):
        raise ValueError(f"unbalanced={unbalanced!r}: expected "
                         "'warn', 'raise' or 'ignore'")
    # packs the unpruned leaves: the pack keeps only the mask's blocks,
    # so no pruned dense copy of the model is ever made
    out = params
    for path, m in masks.items():
        w = sm.get_path(params, path)
        bi, bo = sm.block_dims_for(cfg.blast, path)
        counts = np.asarray(jax.device_get(m)).sum(axis=-2)
        nnz = int(counts.max())
        frac = packing.pad_fraction(m, nnz)
        if frac > 0.0:
            if pad_report is not None:
                pad_report[path] = frac
            msg = (f"mask for {path!r} is unbalanced: {frac:.1%} of "
                   f"packed block slots are zero padding (nnz={nnz}, "
                   f"min per-column count {int(counts.min())})")
            if unbalanced == "raise":
                raise ValueError(msg)
            if unbalanced == "warn":
                warnings.warn(msg, UnbalancedMaskWarning, stacklevel=2)
        p = packing.pack_stacked(w, m, bi, bo, nnz)
        out = sm.set_path(out, path, p)
    for gpath in masks:
        leaf = gpath.split("/")[-1]
        if leaf not in ("w_gate", "ws_gate"):
            continue
        upath = gpath[:-len(leaf)] + leaf.replace("gate", "up")
        if upath not in masks:
            continue
        pg, pu = packing.mark_joint(sm.get_path(out, gpath),
                                    sm.get_path(out, upath))
        out = sm.set_path(out, gpath, pg)
        out = sm.set_path(out, upath, pu)
    return _cast(out, dtype)


def abstract_packed_params(cfg, sparsity: float, mesh=None):
    """ShapeDtypeStruct serving params with sparse leaves replaced by
    abstract PackedBCSC at ``sparsity`` (dry-run: the compiled serve
    step carries the true sparse FLOPs and packed memory footprint).

    Returns (abstract_params, shardings | None)."""
    import math

    from repro.core.packing import PackedBCSC
    from repro.distributed import sharding as shd

    abs_p = registry.abstract_params(cfg)
    abs_p = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        abs_p)
    shards = shd.param_sharding_tree(registry.param_specs(cfg), mesh) \
        if mesh is not None else None
    axes = registry.axes_tree(cfg)
    tp = 1
    if mesh is not None:
        tp = dict(zip(mesh.axis_names,
                      mesh.devices.shape)).get("model", 1)
    for path in registry.sparse_paths(cfg):
        w = sm.get_path(abs_p, path)
        bi, bo = sm.block_dims_for(cfg.blast, path)
        kb, nb = w.shape[-2] // bi, w.shape[-1] // bo
        nnz = max(1, math.ceil((1.0 - sparsity) * kb))
        swapped = path.split("/")[-1] in sm._SWAPPED_LEAVES
        if swapped and nnz >= tp:
            # down-projections: column-blocks = d_model (often not
            # tp-divisible) — shard the nnz CONTRACTION dim instead
            # (zero-block padded; partial sums psum exactly)
            nnz = math.ceil(nnz / tp) * tp
        lead = w.shape[:-2]
        packed = PackedBCSC(
            blocks=jax.ShapeDtypeStruct(lead + (nb, nnz, bi, bo),
                                        jnp.bfloat16),
            idx=jax.ShapeDtypeStruct(lead + (nb, nnz), jnp.int32),
            kb=kb)
        abs_p = sm.set_path(abs_p, path, packed)
        if shards is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            waxes = sm.get_path(axes, path)
            nlead = len(lead)
            lead_parts = [shd.spec_for((w.shape[i],), (waxes[i],),
                                       mesh)[0] for i in range(nlead)]
            if swapped and nnz % tp == 0:
                bspec = P(*lead_parts, None, "model", None, None)
                ispec = P(*lead_parts, None, "model")
            elif not swapped and nb % tp == 0:
                bspec = P(*lead_parts, "model", None, None, None)
                ispec = P(*lead_parts, "model", None)
            else:
                bspec = P(*lead_parts, None, None, None, None)
                ispec = P(*lead_parts, None, None)
            shards = sm.set_path(
                shards, path,
                PackedBCSC(blocks=NamedSharding(mesh, bspec),
                           idx=NamedSharding(mesh, ispec), kb=kb))
    return abs_p, shards


def memory_report(cfg, params_or_packed) -> dict:
    """Bytes of the serving weights + #accelerators at a given HBM size
    (paper Fig. 7 uses 96 GB GH200; TPU v5e is 16 GB)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            params_or_packed,
            is_leaf=lambda x: isinstance(x, packing.PackedBCSC)):
        if isinstance(leaf, packing.PackedBCSC):
            total += packing.storage_bytes(leaf)
        else:
            total += leaf.size * leaf.dtype.itemsize
    return {
        "bytes": int(total),
        "GiB": total / 2**30,
        "chips_v5e_16GB": int(np.ceil(total / (16 * 2**30))),
        "gpus_96GB": int(np.ceil(total / (96 * 2**30))),
    }

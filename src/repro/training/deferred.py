"""Deferred (and optionally int8-EF-compressed) data-parallel gradient
reduction — the production fix identified by the gemma2-27b hillclimb
(EXPERIMENTS.md §Perf cell 2):

GSPMD's implicit gradient psum fires once PER MICROBATCH (measured: 8
microbatches doubled the collective term). Here the train step runs
under a PARTIAL-MANUAL shard_map — manual over the data axes, Auto over
the model axis (TP/SP/GSPMD untouched inside) — so per-shard gradients
accumulate UNREDUCED across microbatches and cross the DP fabric exactly
once, optionally as int8 (4x fewer bytes; error feedback keeps it
unbiased: optim/compress.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import sparse_mlp as sm
from repro.distributed.context import DistContext
from repro.models import registry
from repro.optim import adamw, compress
from repro.training.step import TrainState, loss_fn


def make_train_step_deferred(cfg, opt_cfg: adamw.AdamWConfig, mesh,
                             microbatches: int = 1,
                             compress_grads: bool = True):
    """train_step(state, batch) with ONE (compressed) DP reduction.

    opt_state grows an 'ef' tree (error-feedback residuals) when
    compression is on — init via ``init_opt_state``."""
    spec = cfg.blast
    dense_flags = registry.dense_layer_flags(cfg) if spec.enabled else None
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # inside the manual-data region, sharding constraints may reference
    # only the Auto axes -> batch dim unconstrained, model-axis SP kept
    dist = DistContext(mesh=mesh, manual_data=True)

    def body(state: TrainState, batch):
        def grads_of(b):
            return jax.value_and_grad(
                lambda p: loss_fn(cfg, p, state.masks, b, None,
                                  1.0, 0.0, dist),
                has_aux=True)(state.params)

        n = microbatches
        if n <= 1:
            (loss, (_, aux)), g = grads_of(batch)
        else:
            mb = jax.tree_util.tree_map(
                lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]),
                batch)

            def acc(carry, b_i):
                g_acc, l_acc, a_acc = carry
                (l_i, (_, a_i)), g_i = grads_of(b_i)
                return (jax.tree_util.tree_map(jnp.add, g_acc, g_i),
                        l_acc + l_i, a_acc + a_i), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (g, loss, aux), _ = jax.lax.scan(acc, (zeros, 0.0, 0.0), mb)
            g = jax.tree_util.tree_map(lambda x: x / n, g)
            loss, aux = loss / n, aux / n

        # THE deferred reduction: one pass over the DP fabric
        if compress_grads:
            flat_g, tdef = jax.tree_util.tree_flatten(g)
            flat_e = tdef.flatten_up_to(state.opt_state["ef"])
            red = [compress.reduce_leaf_int8(gi, ei, data_axes)
                   for gi, ei in zip(flat_g, flat_e)]
            dense_grads = tdef.unflatten([r[0] for r in red])
            new_ef = tdef.unflatten([r[1] for r in red])
        else:
            dense_grads = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, data_axes), g)
            new_ef = state.opt_state.get("ef", {})
        loss = jax.lax.pmean(loss, data_axes)

        if spec.enabled:
            masks, params, _ = sm.maybe_refresh(
                spec, state.params, dense_grads, state.masks,
                state.step, dense_flags)
            grads = sm.mask_grads(masks, dense_grads, spec)
            opt_state = adamw.mask_moments(state.opt_state, masks, spec)
        else:
            masks, params, grads = state.masks, state.params, dense_grads
            opt_state = state.opt_state

        params, mv, om = adamw.update(
            opt_cfg, grads, {"m": opt_state["m"], "v": opt_state["v"]},
            params, state.step)
        opt_state = {"m": mv["m"], "v": mv["v"], "ef": new_ef}
        metrics = {"loss": loss, "aux": aux,
                   "sparsity": (sm.tree_sparsity(masks)
                                if spec.enabled else 0.0), **om}
        return (TrainState(step=state.step + 1, params=params,
                           opt_state=opt_state, masks=masks,
                           rng=state.rng), metrics)

    # manual over data; params/opt/masks ride along on the Auto model
    # axis (specs must not mention Auto axes)
    rep = P()

    def train_step(state: TrainState, batch):
        st_spec = jax.tree_util.tree_map(lambda _: rep, state)
        b_first = tuple(data_axes) if len(data_axes) > 1 else \
            (data_axes[0] if data_axes else None)
        b_spec = jax.tree_util.tree_map(
            lambda x: P(*([b_first] + [None] * (x.ndim - 1))), batch)
        out_spec = (jax.tree_util.tree_map(lambda _: rep, state),
                    {"loss": rep, "aux": rep, "sparsity": rep,
                     "grad_norm": rep, "lr": rep})
        f = jax.shard_map(body, mesh=mesh, in_specs=(st_spec, b_spec),
                          out_specs=out_spec, check_vma=False,
                          axis_names=set(data_axes))
        return f(state, batch)

    return train_step


def init_opt_state(cfg, params, compress_grads: bool = True):
    st = adamw.init(params)
    st["ef"] = (compress.init_error_feedback(params)
                if compress_grads else {})
    return st

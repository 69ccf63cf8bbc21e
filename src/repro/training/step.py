"""The jitted train step: forward/backward (STE dense grads), in-step
blocked prune-and-grow (paper Listing 1 — the mask refresh happens INSIDE
the compiled step under lax.cond, so the whole sparsity schedule runs
with zero recompiles), masked AdamW update with regrown-moment reset.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import distill, sparse_mlp as sm
from repro.models import registry
from repro.optim import adamw


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    masks: Any
    rng: jax.Array


def init_state(cfg, rng) -> TrainState:
    params = registry.init_params(cfg, rng)
    masks = registry.init_masks(cfg, params)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=adamw.init(params), masks=masks, rng=rng)


def abstract_state(cfg) -> TrainState:
    """ShapeDtypeStruct TrainState (dry-run: no allocation)."""
    params = registry.abstract_params(cfg)
    sds = lambda t: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    # masks shapes derived from weight shapes
    masks = {}
    if cfg.blast.enabled:
        for path in registry.sparse_paths(cfg):
            w = sm.get_path(params, path)
            bi, bo = sm.block_dims_for(cfg.blast, path)
            masks[path] = jax.ShapeDtypeStruct(
                w.shape[:-2] + (w.shape[-2] // bi, w.shape[-1] // bo),
                jnp.bool_)
    opt = {"m": sds(params), "v": sds(params)}
    return TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params,
        opt_state=opt, masks=masks,
        rng=jax.ShapeDtypeStruct((2,), jnp.uint32))


def state_sharding(cfg, mesh) -> TrainState:
    """NamedSharding tree of a TrainState on ``mesh``: params and both
    AdamW moments by the logical-axis rules, masks like their weights,
    step and rng replicated. ``jax.jit(init_state, out_shardings=...)``
    with it creates the state sharded, never whole on one device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import sharding as shd
    p_shd = shd.param_sharding_tree(registry.param_specs(cfg), mesh)
    m_shd = shd.mask_sharding_tree(
        abstract_state(cfg).masks, registry.axes_tree(cfg),
        registry.sparse_paths(cfg), mesh) if cfg.blast.enabled else {}
    rep = NamedSharding(mesh, P())
    return TrainState(step=rep, params=p_shd,
                      opt_state={"m": p_shd, "v": p_shd}, masks=m_shd,
                      rng=rep)


def loss_fn(cfg, params, masks, batch, teacher_logits=None,
            kd_alpha=1.0, kd_beta=0.0, dist=None):
    kw = {}
    if cfg.family == "audio":
        kw["frames"] = batch["frames"]
    if cfg.family == "vlm":
        kw["patch_embeds"] = batch["patch_embeds"]
    logits, aux = registry.forward(cfg, params, batch["tokens"],
                                   masks=masks, dist=dist, **kw)
    loss = distill.distill_loss(logits, batch["labels"],
                                teacher_logits, alpha=kd_alpha,
                                beta=kd_beta)
    if cfg.is_moe:
        loss = loss + 0.01 * aux
    return loss, (logits, aux)


def make_train_step(cfg, opt_cfg: adamw.AdamWConfig, dist=None,
                    kd_alpha=1.0, kd_beta=0.0, teacher_cfg=None,
                    teacher_params_static=None, microbatches: int = 1,
                    guard: bool = True,
                    grad_norm_limit: float | None = None):
    """Build the jittable train_step(state, batch) -> (state, metrics).

    ``microbatches`` > 1: gradient accumulation via lax.scan over batch
    slices — bounds the activation working set to 1/N (gemma2-27B
    train_4k needs N>=4 to fit v5e HBM — EXPERIMENTS.md §Perf).

    Knowledge distillation (paper §5.2): when ``teacher_cfg`` is given,
    the batch must carry 'teacher_logits' (precomputed) OR
    ``teacher_params_static`` is closed over for an in-step dense
    teacher forward.

    Anomaly guard (``guard=True``): the step computes an ``anomaly``
    flag — non-finite loss, non-finite gradient norm, or gradient norm
    over ``grad_norm_limit`` — and applies SKIP-UPDATE semantics under
    ``lax.cond``: an anomalous step is an identity update on
    params/opt-state/masks (only ``step`` advances), so a run that
    hits NaN grads at step k is bitwise-identical to a run that never
    applies step k's update. The flag rides the metrics dict: zero
    extra host syncs.

    Fault-injection scalars (training/faults.py) may ride the batch:
    ``grad_poison`` multiplies the loss by ``(1 + poison)`` BEFORE the
    backward (NaN/Inf poisons every gradient; the 0.0 no-fault value is
    a bitwise-exact identity), ``loss_poison`` is added to the REPORTED
    loss only (host-visible spike, gradients untouched), and
    ``force_skip`` forces the skip path with healthy gradients (the
    parity oracle's control arm)."""
    spec = cfg.blast
    dense_flags = registry.dense_layer_flags(cfg) if spec.enabled else None

    def train_step(state: TrainState, batch):
        batch = dict(batch)
        grad_poison = batch.pop("grad_poison", None)
        loss_poison = batch.pop("loss_poison", None)
        force_skip = batch.pop("force_skip", None)
        teacher_logits = batch.get("teacher_logits")
        if teacher_params_static is not None:
            teacher_logits, _ = registry.forward(
                teacher_cfg or cfg, teacher_params_static,
                batch["tokens"])
            teacher_logits = jax.lax.stop_gradient(teacher_logits)

        def grads_of(b, tl):
            def poisoned_loss(p):
                loss, aux2 = loss_fn(cfg, p, state.masks, b, tl,
                                     kd_alpha, kd_beta, dist)
                if grad_poison is not None:
                    loss = loss * (1.0 + grad_poison)
                return loss, aux2
            return jax.value_and_grad(
                poisoned_loss, has_aux=True)(state.params)

        if microbatches <= 1:
            (loss, (_, aux)), dense_grads = grads_of(batch,
                                                     teacher_logits)
        else:
            n = microbatches
            mb = jax.tree_util.tree_map(
                lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:]),
                batch)
            tlm = None if teacher_logits is None else \
                teacher_logits.reshape(n, -1, *teacher_logits.shape[1:])

            def acc(carry, xs):
                g_acc, loss_acc, aux_acc = carry
                b_i = xs if tlm is None else xs[0]
                tl_i = None if tlm is None else xs[1]
                (loss_i, (_, aux_i)), g_i = grads_of(b_i, tl_i)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g_i)
                return (g_acc, loss_acc + loss_i, aux_acc + aux_i), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            xs = mb if tlm is None else (mb, tlm)
            (dense_grads, loss, aux), _ = jax.lax.scan(
                acc, (zeros, 0.0, 0.0), xs)
            dense_grads = jax.tree_util.tree_map(
                lambda g: g / n, dense_grads)
            loss, aux = loss / n, aux / n

        gnorm = adamw.global_norm(dense_grads)
        if guard:
            ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            if grad_norm_limit is not None:
                ok &= gnorm <= grad_norm_limit
            anomaly = ~ok
        else:
            anomaly = jnp.zeros((), bool)
        if force_skip is not None:
            anomaly = anomaly | (force_skip > 0)

        def apply_update(_):
            if spec.enabled:
                masks, params, _grown = sm.maybe_refresh(
                    spec, state.params, dense_grads, state.masks,
                    state.step, dense_flags)
                grads = sm.mask_grads(masks, dense_grads, spec)
                opt_state = adamw.mask_moments(state.opt_state, masks,
                                               spec)
            else:
                masks, params = state.masks, state.params
                grads, opt_state = dense_grads, state.opt_state
            params, opt_state, _om = adamw.update(
                opt_cfg, grads, opt_state, params, state.step)
            return params, opt_state, masks

        def skip_update(_):
            return state.params, state.opt_state, state.masks

        params, opt_state, masks = jax.lax.cond(
            anomaly, skip_update, apply_update, None)

        loss_out = loss if loss_poison is None else loss + loss_poison
        metrics = {"loss": loss_out, "aux": aux,
                   "sparsity": (sm.tree_sparsity(masks)
                                if spec.enabled else 0.0),
                   "grad_norm": gnorm,
                   "lr": adamw.lr_at(opt_cfg, state.step),
                   "anomaly": anomaly.astype(jnp.int32)}
        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state, masks=masks,
                               rng=state.rng)
        return new_state, metrics

    return train_step

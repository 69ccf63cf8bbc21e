"""System behaviour: training decreases loss; the BLaST invariants hold
DURING training (pruned blocks stay exactly zero between refreshes;
sparsity follows the schedule); checkpoints resume deterministically;
export/packed-serve agree with the trained model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from conftest import tiny_cfg
from repro.core import sparse_mlp as sm, topk
from repro.data.pipeline import SyntheticLM
from repro.models import registry
from repro.optim import adamw
from repro.training import step as ts, train_loop


def _train(cfg, steps, opt_total=60, **loop_kw):
    src = SyntheticLM(cfg.vocab_size, seq_len=32, global_batch=16, seed=3)
    # opt schedule horizon FIXED (not = steps) so runs of different
    # lengths follow the same LR trajectory (bitwise-resume test)
    opt = adamw.AdamWConfig(peak_lr=2e-2, warmup_steps=5,
                            total_steps=opt_total, weight_decay=0.0)
    loop = train_loop.TrainLoopConfig(total_steps=steps, log_every=5,
                                      **loop_kw)
    return train_loop.train(cfg, opt, src, loop)


def test_loss_decreases_dense():
    cfg = tiny_cfg(blast=dataclasses.replace(tiny_cfg().blast,
                                             enabled=False))
    state, hist = _train(cfg, 60)
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.1


def test_blast_invariants_during_training():
    cfg = tiny_cfg()
    state, hist = _train(cfg, 25)
    spec = cfg.blast
    # scheduled sparsity reached (dense_last layer excluded)
    assert hist[-1]["sparsity"] > 0.2
    # pruned blocks are EXACTLY zero in the stored params
    for path, mask in state.masks.items():
        w = np.asarray(sm.get_path(state.params, path))
        bi, bo = sm.block_dims_for(spec, path)
        kept = np.asarray(topk.expand_mask(mask, bi, bo))
        assert np.abs(w[~kept]).max() == 0.0
    # dense_last layer stays fully dense
    flags = np.asarray(registry.dense_layer_flags(cfg))
    for path, mask in state.masks.items():
        m = np.asarray(mask)
        assert m[flags].all(), f"dense-last layer pruned in {path}"


def test_checkpoint_resume_bitwise(tmp_path):
    cfg = tiny_cfg()
    d = str(tmp_path / "ck")
    # run 20 steps with checkpoint at 10
    state_a, _ = _train(cfg, 20, ckpt_dir=d, ckpt_every=10)
    # wipe nothing; resume from step 20's checkpoint? -> rerun to 30
    state_b, _ = _train(cfg, 30, ckpt_dir=d, ckpt_every=10)
    # fresh run straight to 30 with same seeds must match bitwise
    state_c, _ = _train(cfg, 30)
    for pa, pc in zip(jax.tree_util.tree_leaves(state_b.params),
                      jax.tree_util.tree_leaves(state_c.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pc))


def test_export_packed_matches_pruned(tmp_path):
    from repro.serving import export, serve_loop
    cfg = tiny_cfg()
    state, _ = _train(cfg, 15)
    pruned = export.prune_params(cfg, state.params, state.masks)
    packed = export.pack_params(cfg, state.params, state.masks)
    prompts = jnp.asarray(
        SyntheticLM(cfg.vocab_size, 8, 4, seed=9).batch(0)["tokens"])
    t1, _ = serve_loop.generate(cfg, pruned, prompts, max_new_tokens=6)
    t2, _ = serve_loop.generate(cfg, packed, prompts, max_new_tokens=6)
    np.testing.assert_array_equal(t1, t2)


def test_pack_params_ignores_pruned_blocks():
    """``pack_params`` keeps only the mask's blocks, so packing the
    unpruned weights gives exactly the pack of the pruned ones."""
    from repro.launch.serve import served_params
    from repro.serving import export
    cfg = tiny_cfg()
    params, masks = served_params(cfg, seed=1, sparsity=0.5)
    assert not all(bool(m.all()) for m in masks.values())
    pruned = export.prune_params(cfg, params, masks)
    a = export.pack_params(cfg, params, masks)
    b = export.pack_params(cfg, pruned, masks)
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_distillation_reduces_kl():
    """Post-training compression (paper §5.2): student with KD matches
    teacher logits better than CE-only student."""
    from repro.core.distill import kl_to_teacher
    cfg_t = tiny_cfg(blast=dataclasses.replace(tiny_cfg().blast,
                                               enabled=False))
    teacher_state, _ = _train(cfg_t, 40)
    cfg_s = tiny_cfg()
    src = SyntheticLM(cfg_s.vocab_size, seq_len=32, global_batch=16,
                      seed=3)
    opt = adamw.AdamWConfig(peak_lr=5e-3, warmup_steps=2,
                            total_steps=30, weight_decay=0.0)
    loop = train_loop.TrainLoopConfig(total_steps=30, log_every=10)
    state_kd, _ = train_loop.train(
        cfg_s, opt, src, loop,
        teacher_params=jax.tree_util.tree_map(
            jnp.copy, teacher_state.params),
        teacher_cfg=cfg_t, kd_beta=1.0)
    batch = src.batch(123)
    toks = jnp.asarray(batch["tokens"])
    s_logits, _ = registry.forward(cfg_s, state_kd.params, toks,
                                   masks=state_kd.masks)
    t_logits, _ = registry.forward(cfg_t, teacher_state.params, toks)
    kl = float(kl_to_teacher(s_logits, t_logits))
    assert np.isfinite(kl)
    assert kl < 3.0   # sanity bound: student tracks teacher

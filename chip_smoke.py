"""Chip smoke test: the main serving path at full width on one TPU.

    python3 chip_smoke.py             # one chip: serve stablelm-3b
    python3 chip_smoke.py --chips 4   # four chips: sharded train step

One process, no children. Without an option it serves packed
stablelm-3b (published widths, random weights from ``--seed``, one-shot
magnitude prune at 0.8 MLP block sparsity) through ``engine.Engine``,
checks the engine's prefill logits and every served token against a
plain dense reference run teacher-forced over the served sequences,
and runs the three Pallas kernels compiled for the chip against their
references. ``--chips 4`` runs only the sharded pretraining path: three
steps of full-depth stablelm-3b through the training launcher
(``repro.launch.train --mesh single``: a (data=1, model=4) mesh), plus
the single-device-versus-mesh comparison of ``train_loop.train`` on a
2-layer model.

Every number is a smoke number, not a benchmark metric. Any failed
check exits non-zero; the last line of stdout is printed only on
success: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
There is no CPU path: without a TPU the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.metadata
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# HBM per chip by ``device_kind`` (Google Cloud, "TPU v5e": 16 GB). A kind
# not listed here is an error: the smoke test knows no default device.
HBM_BYTES = {"TPU v5 lite": 16 * 10**9}

ARCH = "stablelm-3b"
PUBLISHED = dict(num_layers=32, d_model=2560, num_heads=32, num_kv_heads=32,
                 head_dim=80, d_ff=6912, vocab_size=50_304)
SPARSITY = 0.8
PROMPT_LENS = (128, 256, 384, 512)
NEW_TOKENS = 32
MAX_BATCH, MAX_LEN, PAGE_SIZE = 4, 1024, 16
# served-vs-reference bound on max|logit diff| / max|reference logit|.
# Both paths run bf16 weights and activations with f32 accumulation; they
# differ only in summation order (packed gather+einsum vs dense masked
# matmul, chunked paged attention vs one causal pass). The first chip run
# measured 8.2e-3 for the prefill logits of these prompts; the bound is
# three times that. A wrong block, page or position gives differences of
# the order of the logits themselves. A served token must equal the
# reference's argmax wherever the reference's top-2 gap exceeds the bound:
# flipping such a gap takes two logits each off by half the bound, 1.5x
# the measured error.
LOGIT_BOUND = 0.025
# kernel-vs-reference bound, relative to the reference's max |value|. The
# kernels round once to bf16 (2^-8) after f32 accumulation; the fused-GLU
# reference also rounds gate and up to bf16 before their product, so up to
# three roundings (~1.2e-2) separate the two, plus summation order.
KERNEL_BOUND = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAILED: {what}")


def device_phase(chips: int):
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: jax.devices()[0].platform is {dev.platform!r}")
    check(dev.device_kind in HBM_BYTES,
          f"unknown device kind {dev.device_kind!r}")
    check(len(jax.devices()) == chips,
          f"this phase runs on {chips} chip(s); {len(jax.devices())} "
          "are visible")
    log(f"[device] kind={dev.device_kind} count={len(jax.devices())} "
        f"jax={jax.__version__} "
        f"libtpu={importlib.metadata.version('libtpu')}")
    return dev


def device_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def check_peak(dev) -> int:
    """The device's peak bytes in use, checked against both the chip's
    HBM and the allocator's own limit."""
    stats = dev.memory_stats()
    peak = int(stats["peak_bytes_in_use"])
    limit = min(HBM_BYTES[dev.device_kind], int(stats["bytes_limit"]))
    check(peak < limit, f"peak {peak:,} B over the limit {limit:,} B")
    return peak


# ------------------------------------------------------------ one chip
def make_prompts(cfg, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def reference_logits(cfg, params, prompts, served) -> np.ndarray:
    """Plain dense forward, teacher-forced over each prompt followed by
    its served tokens (right-padded to one width; causal, so the pad
    never reaches a kept position): (n_prompts, NEW_TOKENS, V) float32
    logits at the positions that chose the served tokens, the prompt's
    last and each served token but the last."""
    from repro.models import registry
    seqs = [np.concatenate([p, t[:-1]]) for p, t in zip(prompts, served)]
    toks = np.zeros((len(seqs), max(s.size for s in seqs)), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :s.size] = s
    first = jnp.asarray([p.size - 1 for p in prompts], jnp.int32)

    @jax.jit
    def fwd(params, toks, first):
        logits, _ = registry.forward(cfg, params, toks, masks=None)
        at = first[:, None] + jnp.arange(NEW_TOKENS)
        return jnp.take_along_axis(logits, at[:, :, None],
                                   axis=1).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        return np.asarray(fwd(params, jnp.asarray(toks), first))


def serve_phase(cfg, packed, prompts, dev):
    """Serve the prompts through ``Engine`` twice: the first pass
    compiles, the second runs on compiled code. Returns each request's
    served tokens and the last-position logits of the engine's own
    prefill, read through its ``prefill_logits_hook``."""
    from repro.serving.engine import Engine
    eng = Engine(cfg, packed, max_batch=MAX_BATCH, max_len=MAX_LEN,
                 page_size=PAGE_SIZE)
    prefill_logits = {}
    eng.prefill_logits_hook = lambda got: prefill_logits.update(
        {u: np.asarray(x.astype(jnp.float32)) for u, x in got.items()})
    runs = []
    for _ in range(2):
        t0 = time.monotonic()
        uids = [eng.submit(p, NEW_TOKENS) for p in prompts]
        res = eng.run()
        runs.append((time.monotonic() - t0, uids,
                     [res[u].generated for u in uids]))
    (first_s, uids, toks), (steady_s, _, toks2) = runs
    n = sum(t.size for t in toks)
    check(n == len(prompts) * NEW_TOKENS,
          f"served {n} tokens, expected {len(prompts) * NEW_TOKENS}")
    check(all(((t >= 0) & (t < cfg.vocab_size)).all() for t in toks),
          "served token out of vocab range")
    check(all(np.array_equal(a, b) for a, b in zip(toks, toks2)),
          "greedy tokens differ between two identical passes")
    log(f"[serve] {len(prompts)} requests, prompts {list(PROMPT_LENS)}, "
        f"{n} tokens served; first pass {first_s:.2f} s (compiles), "
        f"second pass {steady_s:.2f} s -> compile ~{first_s - steady_s:.2f}"
        f" s, steady {n / steady_s:.1f} tok/s (smoke, not a benchmark "
        f"metric); pool {eng.n_pages} pages x {PAGE_SIZE}")
    log(f"[serve] peak_bytes_in_use={peak_bytes(dev):,}")
    del eng
    return toks, np.stack([prefill_logits[u] for u in uids])


def compare_phase(ref, prefill, served):
    """The engine's prefill logits against the reference's at each
    prompt's last position, then every served token against the
    reference's argmax wherever its top-2 gap clears the bound."""
    scale = float(np.abs(ref[:, 0]).max())
    diff = float(np.abs(prefill - ref[:, 0]).max()) / scale
    log(f"[compare] engine prefill logits vs dense reference: "
        f"max|diff|/scale={diff:.3e} (scale {scale:.3f}, bound "
        f"{LOGIT_BOUND})")
    check(diff < LOGIT_BOUND, f"logit diff {diff:.3e} >= {LOGIT_BOUND}")
    got = np.stack(served)                               # (n, NEW_TOKENS)
    want = ref.argmax(-1)
    top2 = np.sort(ref, axis=-1)[..., -2:]
    margin = (top2[..., 1] - top2[..., 0]) / np.abs(ref).max(-1)
    enforced = margin > LOGIT_BOUND
    agree = got == want
    for i, n in enumerate(PROMPT_LENS):
        log(f"[compare] prompt {n}: {int(agree[i].sum())}/{NEW_TOKENS} "
            f"served tokens are the reference's argmax; "
            f"{int(enforced[i].sum())} enforced (margin > {LOGIT_BOUND})")
    worst = margin[~agree].max() if (~agree).any() else 0.0
    log(f"[compare] {int(enforced.sum())} of {got.size} positions "
        f"enforced, {int((agree & enforced).sum())} agree; largest margin "
        f"among disagreements {worst:.3e}")
    check(bool(agree[enforced].all()),
          "a served token differs from the reference past the margin")


def rel_diff(got, want) -> float:
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    return float(np.abs(got - want).max() / np.abs(want).max())


def kernel_phase(cfg, packed, seed: int):
    """The Pallas kernels compiled for the chip at stablelm-3b widths,
    on layer 0's served weights, against ``kernels/ref.py`` and the XLA
    twins in ``kernels/ops.py``."""
    from repro.core.packing import PackedBCSC, mark_joint
    from repro.kernels import bspmm as bk, ops, paged_attention as pk, ref
    from repro.models import attention as attn

    def layer0(p):
        return PackedBCSC(blocks=p.blocks[0], idx=p.idx[0], kb=p.kb,
                          joint=p.joint)

    mlp = packed["layers"]["mlp"]
    pg, pu, pd = (layer0(mlp[k]) for k in ("w_gate", "w_up", "w_down"))
    pj, _ = mark_joint(pg, pg)
    check(pj.joint, "joint fast path not taken")
    key = jax.random.PRNGKey(seed)
    act = cfg.mlp_act
    results = []

    def precise(fn, *args, **kw):
        # references only: the kernels take the chip's native bf16 MXU
        # precision, and Mosaic refuses an fp32-precision bf16 matmul
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)

    for m in (4, 128):
        x = jax.random.normal(key, (m, cfg.d_model), jnp.bfloat16)
        for name, a, b in (("two-index", pg, pu), ("joint", pj, pj)):
            h = bk.fused_glu(x, a, b, act=act)
            results += [
                (f"fused_glu {name} m={m} vs ref",
                 rel_diff(h, precise(ref.fused_glu_ref, x, a, b, act))),
                (f"fused_glu {name} m={m} vs xla",
                 rel_diff(h, ops.fused_glu(x, a, b, act=act)))]
        y = bk.bspmm(h, pd)
        results += [(f"bspmm down m={m} vs ref",
                     rel_diff(y, precise(ref.bspmm_ref, h, pd))),
                    (f"bspmm down m={m} vs xla", rel_diff(y, ops.bspmm(h, pd)))]
        results.append((f"bspmm gate m={m} vs ref",
                        rel_diff(bk.bspmm(x, pg), precise(ref.bspmm_ref, x, pg))))

    # paged flash-decode: a served-size pool, ragged frontiers
    rng = np.random.default_rng(seed)
    b, r = MAX_BATCH, 8
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // kvh
    n_pages = MAX_BATCH * MAX_LEN // PAGE_SIZE
    ks = jax.random.split(key, 3)
    q4 = jax.random.normal(ks[0], (b, kvh, g, hd), jnp.bfloat16)
    # one layer's pool, as a stack of one
    pool_k = jax.random.normal(ks[1], (1, n_pages, PAGE_SIZE, kvh, hd),
                               jnp.bfloat16)
    pool_v = jax.random.normal(ks[2], (1, n_pages, PAGE_SIZE, kvh, hd),
                               jnp.bfloat16)
    bt = jnp.asarray(rng.permutation(n_pages)[:b * r].reshape(b, r),
                     jnp.int32)
    offsets = jnp.asarray(rng.integers(0, 8, b), jnp.int32)
    posv = jnp.asarray(rng.integers(8, r * PAGE_SIZE, b), jnp.int32)
    posb = (posv - offsets)[:, None]
    kpos = attn._cache_positions(r * PAGE_SIZE, offsets)
    out = pk.paged_flash_decode(q4, pool_k, pool_v, bt,
                                pk.mask_bias(posb, kpos),
                                scale=1.0 / math.sqrt(hd), layer=0)
    want = precise(attn._scores_to_out, cfg, q4.reshape(b, 1, kvh * g, hd),
                   attn.gather_pages(pool_k, bt, r, 0),
                   attn.gather_pages(pool_v, bt, r, 0), posb, kpos,
                   causal=True, window=0)
    results.append(("paged_flash_decode vs xla gather",
                    rel_diff(out.reshape(b, 1, kvh * g, hd), want)))
    for name, d in results:
        log(f"[kernels] {name}: max|diff|/scale={d:.3e}")
    check(all(d < KERNEL_BOUND for _, d in results),
          f"a kernel is off its reference by >= {KERNEL_BOUND}")
    log(f"[kernels] blocks {(pg.b_in, pg.b_out)}, nnz gate {pg.nnz} "
        f"down {pd.nnz}: all compiled for the chip (interpret=False)")


def one_chip(seed: int, dev) -> None:
    from repro.configs import get_config
    from repro.launch.serve import served_params
    from repro.serving import export

    cfg = get_config(ARCH)
    check(all(getattr(cfg, k) == v for k, v in PUBLISHED.items()),
          f"{ARCH} is not at its published widths")
    log(f"[model] {ARCH} {PUBLISHED}, blocks "
        f"({cfg.blast.b_in}, {cfg.blast.b_out}), sparsity {SPARSITY}")
    t0 = time.monotonic()
    params, masks = served_params(cfg, seed=seed, sparsity=SPARSITY)
    log(f"[model] dense bf16 params on device: {device_bytes(params):,} B "
        f"({time.monotonic() - t0:.1f} s), peak {peak_bytes(dev):,}")
    # the pruned dense weights are the reference's; the pack keeps only
    # the mask's blocks, so it packs them as well as the unpruned ones,
    # which can go at once
    params = export.prune_params(cfg, params, masks)
    t0 = time.monotonic()
    packed = export.pack_params(cfg, params, masks, unbalanced="raise")
    del masks
    log(f"[model] packed params on device: {device_bytes(packed):,} B "
        f"({time.monotonic() - t0:.1f} s), peak {peak_bytes(dev):,}")
    # the reference's weights wait on the host while the engine serves,
    # so the device holds what serving holds
    params = jax.device_get(params)

    prompts = make_prompts(cfg, seed)
    served, prefill = serve_phase(cfg, packed, prompts, dev)
    t0 = time.monotonic()
    ref = reference_logits(cfg, jax.device_put(params), prompts, served)
    del params
    check(bool(np.isfinite(ref).all()), "reference logits not finite")
    log(f"[reference] dense forward over {len(prompts)} prompts and their "
        f"served tokens ({time.monotonic() - t0:.1f} s), "
        f"peak {peak_bytes(dev):,}")
    compare_phase(ref, prefill, served)
    kernel_phase(cfg, packed, seed)
    peak = check_peak(dev)
    log(f"[memory] peak_bytes_in_use={peak:,} of "
        f"{HBM_BYTES[dev.device_kind]:,}")


# ----------------------------------------------------------- four chips
def four_chips() -> None:
    """The sharded pretraining path as a user runs it: the training
    launcher on a mesh of every chip, then ``train_loop.train`` on one
    chip and on the mesh for the same first step. The launcher makes
    its weights and data from its own fixed seed."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import make_source
    from repro.distributed.context import DistContext
    from repro.launch import train as train_launcher
    from repro.launch.mesh import make_host_mesh, shard_blocks
    from repro.optim import adamw
    from repro.training import train_loop

    mesh = make_host_mesh()
    cfg = shard_blocks(get_config(ARCH), mesh)
    check(cfg.remat, "remat must be on")
    log(f"[train] {ARCH} full depth via repro.launch.train --mesh single: "
        f"mesh {dict(mesh.shape)}, blocks ({cfg.blast.b_in}, "
        f"{cfg.blast.b_out}), batch 4 x 1024, remat")
    t0 = time.monotonic()
    state, history = train_launcher.main([
        "--arch", ARCH, "--steps", "3", "--batch", "4", "--seq", "1024",
        "--mesh", "single"])
    wall = time.monotonic() - t0
    # the loop logs its first and its last step
    steps = [h for h in history if "loss" in h]
    check([h["step"] for h in steps] == [0, 2], "first and last step logged")
    for h in steps:
        log(f"[train] step {h['step']}: loss {h['loss']:.4f} grad_norm "
            f"{h['grad_norm']:.4f} {h['sec_per_step']:.2f} s"
            f"{' (compiles)' if h['step'] == 0 else ''} "
            f"({4 * 1024 / h['sec_per_step']:.0f} tok/s, smoke)")
        check(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"]),
              "non-finite loss or grad norm")
        check(not h["anomaly"], "step flagged anomalous")
    # the launcher trained the mesh's blocks: 6912 / b_out block-columns
    mask = state.masks["layers/mlp/w_gate"]
    check(mask.shape[-1] == cfg.d_ff // cfg.blast.b_out,
          f"launcher's mask grid {mask.shape} is not the mesh's blocks")
    check(len(mask.sharding.device_set) == 4, "state not on the mesh")
    peaks = [check_peak(d) for d in jax.devices()]
    log(f"[train] launcher wall {wall:.1f} s; state "
        f"{device_bytes(state):,} B over 4 chips; peak_bytes_in_use per "
        f"chip {peaks}")
    del state

    # the same first step of train_loop.train on device 0 and on the mesh
    cfg2 = dataclasses.replace(cfg, num_layers=2)
    source = make_source(cfg2, ShapeConfig("cmp", 1024, 4, "train"))
    opt = adamw.AdamWConfig(total_steps=100, warmup_steps=0)
    loop = train_loop.TrainLoopConfig(total_steps=1)
    firsts = []
    for dist in (None, DistContext(mesh=mesh)):
        _, hist = train_loop.train(cfg2, opt, source, loop, dist=dist,
                                   log_fn=lambda m: None)
        firsts.append(hist[-1])
    (l1, g1), (l4, g4) = ((h["loss"], h["grad_norm"]) for h in firsts)
    log(f"[compare] 2-layer first step: loss {l1:.6f} (1 chip) vs "
        f"{l4:.6f} (4 chips); grad_norm {g1:.6f} vs {g4:.6f}")
    check(abs(l1 - l4) < 1e-3, "loss differs past 1e-3")
    check(abs(g1 - g4) / max(g1, 1.0) < 2e-2, "grad norm differs past 2%")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and prompts of the one-chip phase")
    args = ap.parse_args()
    dev = device_phase(args.chips)

    from repro.launch.compile_cache import enable_compile_cache
    log(f"[cache] {enable_compile_cache()}")
    if args.chips == 4:
        four_chips()
    else:
        one_chip(args.seed, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before any jax import (task spec).

"""Multi-pod dry-run: lower + compile train_step / serve_step for every
(architecture x input shape) on the 16x16 single-pod mesh and the
2x16x16 multi-pod mesh; record memory_analysis, cost_analysis and the
roofline terms (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch internvl2-2b \
        --shape train_4k [--multipod] [--out results/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, cells, get_config, skip_shapes
from repro.distributed import sharding as shd
from repro.distributed.context import DistContext
from repro.launch import specs as specs_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, shard_blocks
from repro.models import registry
from repro.optim import adamw
from repro.roofline import analysis
from repro.training import step as train_step_mod


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               packed: bool = False):
    """Returns (lowered, compiled, meta)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    cfg, shape, inputs = specs_mod.input_specs(arch, shape_name)
    cfg = shard_blocks(cfg, mesh)
    # §Perf experiment knobs (baseline = all unset)
    import dataclasses as _dc
    overrides = {}
    if os.environ.get("DRYRUN_REMAT"):
        overrides["remat_policy"] = os.environ["DRYRUN_REMAT"]
    if os.environ.get("DRYRUN_CHUNK"):
        overrides["chunk_size"] = int(os.environ["DRYRUN_CHUNK"])
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    dist = DistContext(mesh=mesh,
                       sp=not os.environ.get("DRYRUN_NO_SP"))
    rep = NamedSharding(mesh, P())
    t0 = time.time()

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig(total_steps=cfg.blast.total_steps)
        mb = int(os.environ.get("DRYRUN_MICROBATCH", "1"))
        if os.environ.get("DRYRUN_DEFERRED"):
            from repro.training import deferred
            mb = int(os.environ["DRYRUN_DEFERRED"])
            ts = deferred.make_train_step_deferred(
                cfg, opt_cfg, mesh, microbatches=mb,
                compress_grads=not os.environ.get("DRYRUN_NOCOMPRESS"))
        else:
            ts = train_step_mod.make_train_step(cfg, opt_cfg, dist=dist,
                                                microbatches=mb)
        state_abs = train_step_mod.abstract_state(cfg)
        if os.environ.get("DRYRUN_DEFERRED"):
            state_abs = train_step_mod.TrainState(
                step=state_abs.step, params=state_abs.params,
                opt_state={**state_abs.opt_state,
                           "ef": state_abs.params
                           if not os.environ.get("DRYRUN_NOCOMPRESS")
                           else {}},
                masks=state_abs.masks, rng=state_abs.rng)
        state_shd = train_step_mod.state_sharding(cfg, mesh)
        if os.environ.get("DRYRUN_DEFERRED") \
                and not os.environ.get("DRYRUN_NOCOMPRESS"):
            state_shd = train_step_mod.TrainState(
                step=state_shd.step, params=state_shd.params,
                opt_state={**state_shd.opt_state,
                           "ef": state_shd.params},
                masks=state_shd.masks, rng=state_shd.rng)
        batch_shd = specs_mod.batch_shardings(inputs, mesh)
        with mesh:
            lowered = jax.jit(
                ts, in_shardings=(state_shd, batch_shd),
                out_shardings=(state_shd, None),
                donate_argnums=(0,)).lower(state_abs, inputs)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6 * cfg.n_active_params() * tokens
    elif shape.kind == "prefill":
        def prefill(params, batch):
            kw = {}
            if cfg.family == "audio":
                kw["frames"] = batch["frames"]
            if cfg.family == "vlm":
                kw["patch_embeds"] = batch["patch_embeds"]
            logits, _ = registry.forward(cfg, params, batch["tokens"],
                                         masks=None, dist=dist, **kw)
            return logits[:, -1]
        params_abs = _serve_params(cfg)
        p_shd = shd.param_sharding_tree(registry.param_specs(cfg), mesh)
        batch = dict(inputs)
        batch.pop("labels", None)
        batch_shd = specs_mod.batch_shardings(batch, mesh)
        with mesh:
            lowered = jax.jit(
                prefill, in_shardings=(p_shd, batch_shd)).lower(
                params_abs, batch)
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2 * cfg.n_active_params() * tokens
    else:  # decode
        def serve_step(params, cache, tokens, pos):
            logits, new_cache = registry.decode_step(
                cfg, params, cache, tokens, pos, masks=None, dist=dist)
            return jnp.argmax(logits[:, -1], -1), new_cache
        if packed or os.environ.get("DRYRUN_PACKED"):
            from repro.serving import export
            sparsity = float(os.environ.get("DRYRUN_SPARSITY", "0.8"))
            params_abs, p_shd = export.abstract_packed_params(
                cfg, sparsity, mesh)
        else:
            params_abs = _serve_params(cfg)
            p_shd = shd.param_sharding_tree(registry.param_specs(cfg),
                                            mesh)
        cache_shd = specs_mod.cache_shardings(inputs["cache"], mesh)
        tok_shd = shd.batch_sharding(mesh, 2, inputs['tokens'].shape[0])
        with mesh:
            lowered = jax.jit(
                serve_step,
                in_shardings=(p_shd, cache_shd, tok_shd, rep),
                donate_argnums=(1,)).lower(
                params_abs, inputs["cache"], inputs["tokens"],
                inputs["pos"])
        model_flops = 2 * cfg.n_active_params() * shape.global_batch
    lower_s = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    meta = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "kind": shape.kind,
        "lower_s": round(lower_s, 1), "compile_s": round(compile_s, 1),
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        "blast_block": (cfg.blast.b_in, cfg.blast.b_out),
        "model_flops": model_flops,
    }
    return lowered, compiled, meta


def _serve_params(cfg):
    """bf16 serving weights (pruned dense layout) — abstract."""
    abs_p = registry.abstract_params(cfg)
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        abs_p)


def run_cell(arch, shape_name, multi_pod, out_dir, verbose=True):
    lowered, compiled, meta = lower_cell(arch, shape_name, multi_pod)
    report = analysis.analyze_compiled(compiled, meta["chips"],
                                       meta["model_flops"])
    result = {**meta, **report}
    if verbose:
        ma = compiled.memory_analysis()
        print(f"[{arch} x {shape_name} x {meta['mesh']}] "
              f"compile={meta['compile_s']}s")
        print("  memory_analysis:", ma)
        r = report["roofline"]
        print(f"  roofline: compute={r['compute_s']:.4f}s "
              f"memory={r['memory_s']:.4f}s "
              f"collective={r['collective_s']:.4f}s "
              f"dominant={r['dominant']}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{meta['mesh'].replace('x', '-')}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1, default=str)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--list-cells", action="store_true",
                    help="print 'arch shape mesh' rows and exit (used by "
                         "the per-cell-subprocess sweep driver)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.list_cells:
        for arch, shape in cells():
            print(arch, shape, "single")
            print(arch, shape, "multi")
        return

    todo = []
    if args.all:
        for arch, shape in cells():
            todo.append((arch, shape, False))
            todo.append((arch, shape, True))
    else:
        meshes = [args.multipod] if not args.both_meshes else [False, True]
        for mp in meshes:
            todo.append((args.arch, args.shape, mp))

    failures = []
    for arch, shape, mp in todo:
        tag = f"{arch}_{shape}_{'2-16-16' if mp else '16-16'}"
        if args.skip_existing and os.path.exists(
                os.path.join(args.out, tag + ".json")):
            continue
        try:
            run_cell(arch, shape, mp, args.out)
        except Exception as e:  # noqa: BLE001 — report and continue
            failures.append(tag)
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run OK")


if __name__ == "__main__":
    main()

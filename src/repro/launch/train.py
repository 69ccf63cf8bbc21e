"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \
        --smoke --steps 100 --batch 8 --seq 128 [--ckpt-dir ckpts/]

``--smoke`` selects the reduced config (CPU-runnable). ``--mesh single``
shards the state GSPMD-style over one (data, model) mesh of the devices
there are (``launch/mesh.py make_host_mesh``: every device on the model
axis), with BLaST blocks derived for one device's shard
(``shard_blocks``); jax.distributed.initialize is called when
JAX_COORDINATOR is set. ``main(argv)`` returns the final state and the
history, so a caller in the same process drives the same path.
"""
from __future__ import annotations

import argparse
import dataclasses
import os


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--s-max", type=float, default=None)
    ap.add_argument("--step-size", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--no-guard", action="store_true",
                    help="disable the anomaly guard (device-side skip "
                         "+ host-side spike/rewind policy)")
    ap.add_argument("--data", default=None, help="memmap token file")
    ap.add_argument("--mesh", choices=["none", "single"], default="none")
    ap.add_argument("--devices", type=int, default=0,
                    help="force host platform device count")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")
    if os.environ.get("JAX_COORDINATOR"):
        import jax
        jax.distributed.initialize()   # multi-host fleet entry

    import jax
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import make_source
    from repro.distributed.context import DistContext
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh, shard_blocks
    from repro.optim import adamw
    from repro.training import train_loop

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh() if args.mesh == "single" else None
    if mesh is not None:
        cfg = shard_blocks(cfg, mesh)
    overrides = {}
    if args.s_max is not None:
        overrides["s_max"] = args.s_max
    if args.step_size is not None:
        overrides["step_size"] = args.step_size
    if overrides or cfg.blast.enabled:
        cfg = dataclasses.replace(cfg, blast=dataclasses.replace(
            cfg.blast, total_steps=args.steps, **overrides))

    dist = DistContext(mesh=mesh) if mesh is not None else None

    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    source = make_source(cfg, shape, path=args.data)
    opt = adamw.AdamWConfig(peak_lr=args.lr, total_steps=args.steps,
                            warmup_steps=max(args.steps // 20, 5))
    loop = train_loop.TrainLoopConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=max(args.steps // 5, 10),
        guard=None if args.no_guard else train_loop.GuardConfig())
    state, history = train_loop.train(cfg, opt, source, loop, dist=dist)
    print(f"done: final loss {history[-1]['loss']:.4f}, "
          f"sparsity {history[-1]['sparsity']:.3f}")
    return state, history


if __name__ == "__main__":
    main()

"""Training loop: pjit'd step + BLaST pruning (inside the step) +
anomaly guard + checkpoint/restart + automatic rewind + preemption
handling + straggler watchdog.

Fault tolerance model (DESIGN.md §4, hardened per ISSUE 8):
  * auto-resume from the latest INTACT checkpoint in ``ckpt_dir`` at
    startup (torn/corrupt checkpoints are skipped via the crc32
    manifest);
  * periodic async checkpoints (keep-k, atomic, non-destructive swap);
    a failed background write surfaces on ``wait()``/the next save;
  * every jitted step carries an all-finite + grad-norm check and
    SKIPS anomalous updates on device (``training/step.py``); the host
    runs EMA/z-score loss-spike detection (``training/guard.py``),
    schedule-aware around prune-grow refreshes;
  * K consecutive anomalies trigger an automatic REWIND: restore the
    newest intact checkpoint and replay — bitwise-exact because the
    data pipeline is stateless (batch = f(seed, step)) and the RNG
    lives in the TrainState. A spent rewind budget raises
    ``TrainingDivergedError``;
  * SIGTERM/SIGINT triggers one final blocking checkpoint, then a clean
    exit — a preempted worker loses at most the in-flight step;
  * a wall-time watchdog emits structured straggler events (step,
    duration, running median) through the same log_fn/history channel
    as metrics, plus a ``straggler_steps`` counter (on real multi-pod
    deployments this feeds the controller that re-shards around slow
    hosts).
"""
from __future__ import annotations

import dataclasses
import functools
import signal
import sys
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpointing.checkpoint import Checkpointer
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.optim import adamw
from repro.training import step as step_mod
from repro.training.faults import TrainingDivergedError
from repro.training.guard import AnomalyGuard, GuardConfig


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_dir: str | None = None
    ckpt_every: int = 50
    log_every: int = 10
    keep: int = 3
    straggler_factor: float = 3.0
    guard: GuardConfig | None = dataclasses.field(
        default_factory=GuardConfig)


def train(cfg, opt_cfg: adamw.AdamWConfig, source, loop: TrainLoopConfig,
          dist=None, state=None, jit_kwargs: dict | None = None,
          log_fn: Callable[[dict], None] | None = None,
          teacher_params=None, teacher_cfg=None, kd_beta: float = 0.0,
          faults=None, tracer=None, metrics=None):
    """Returns (final_state, history list of metric dicts).

    ``faults`` is an optional ``training/faults.py`` TrainFaultPlan —
    the chaos-test injection port. History entries are either step
    metrics (every ``log_every`` steps and the final step — the LAST
    entry is always the final step's metrics) or structured events
    (``{"event": "straggler" | "rewind" | ...}``).

    ``tracer`` (obs/trace.py) records ``train.step`` spans at the
    step's EXISTING host sync and routes every structured event
    through the same schema serving uses; the checkpoint/rewind paths
    dump flight-recorder postmortems through it. ``metrics`` injects a
    ``MetricsRegistry`` so a caller can scrape the loop's counters
    (Prometheus/snapshot); by default a private one backs ``counters``
    — either way reset/snapshot derive from the registry, never from a
    hand-kept list."""
    tr = NULL_TRACER if tracer is None else tracer
    reg = metrics if metrics is not None else MetricsRegistry(
        namespace="blast_train")
    gcfg = loop.guard if (loop.guard and loop.guard.enabled) else None
    train_step = step_mod.make_train_step(
        cfg, opt_cfg, dist=dist, kd_beta=kd_beta,
        teacher_cfg=teacher_cfg, teacher_params_static=teacher_params,
        guard=gcfg is not None,
        grad_norm_limit=gcfg.grad_norm_limit if gcfg else None)
    # on a mesh the state is created, stepped and restored sharded: a
    # model whose state only fits split across the devices never lands
    # whole on one of them
    shd = (step_mod.state_sharding(cfg, dist.mesh)
           if dist is not None and dist.mesh is not None else None)
    if shd is not None and jit_kwargs is None:
        jit_kwargs = dict(in_shardings=(shd, None),
                          out_shardings=(shd, None))
    step_fn = jax.jit(train_step, donate_argnums=(0,),
                      **(jit_kwargs or {}))

    if state is None:
        init = functools.partial(step_mod.init_state, cfg)
        if shd is not None:
            init = jax.jit(init, out_shardings=shd)
        state = init(jax.random.PRNGKey(0))

    ckpt = Checkpointer(loop.ckpt_dir, keep=loop.keep) \
        if loop.ckpt_dir else None
    if ckpt is not None:
        ckpt.tracer = tr
        if faults is not None:
            ckpt.fault_hook = faults.on_ckpt_saved
    start = 0
    if ckpt and ckpt.latest_intact_step() is not None:
        state = ckpt.restore_state(state, shardings=shd)
        start = int(np.asarray(state.step))
        print(f"[resume] restored step {start} from {loop.ckpt_dir}")

    guard = AnomalyGuard(
        gcfg, step_size=(cfg.blast.step_size if cfg.blast.enabled
                         else 0)) if gcfg else None
    if guard is not None:
        guard.tracer = tr
    for name, help_ in (
            ("straggler_steps", "steps slower than factor x median"),
            ("ckpt_fallbacks", "corrupt/torn checkpoints skipped"),
            ("anomaly_steps", "steps with any anomaly verdict"),
            ("skipped_steps", "device-skipped (non-finite/grad) steps"),
            ("spike_steps", "host loss-spike verdicts"),
            ("rewinds", "automatic checkpoint rewinds"),
            ("steps_replayed", "steps re-run after rewinds")):
        reg.counter(name, help_)
    counters = reg.view()

    stop = {"flag": False}

    def handler(signum, frame):  # noqa: ARG001
        stop["flag"] = True

    old_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            old_handlers[sig] = signal.signal(sig, handler)
        except ValueError:   # not main thread (tests)
            pass

    history: list[dict] = []
    durations: list[float] = []

    def emit(event: dict):
        history.append(event)
        if tr.enabled:
            # one schema for log_fn/history AND the tracer: the span
            # stream carries the same straggler/rewind/anomaly events
            # the structured log does, namespaced under train.*
            tr.event("train." + event["event"],
                     **{k: v for k, v in event.items() if k != "event"})
        if log_fn:
            log_fn(event)
        else:
            print(f"[{event['event']}] {event}")

    try:
        i = start
        while i < loop.total_steps:
            if faults is not None:
                faults.on_host_step(i)
            batch = {k: jax.numpy.asarray(v)
                     for k, v in source.batch(i).items()}
            if faults is not None:
                batch.update({k: jax.numpy.asarray(v) for k, v
                              in faults.step_scalars(i).items()})
            t0 = time.monotonic()
            if faults is not None:
                faults.on_timed_step(i)
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.monotonic() - t0
            durations.append(dt)
            med = float(np.median(durations[-50:]))
            if len(durations) > 5 and dt > loop.straggler_factor * med:
                counters["straggler_steps"] += 1
                emit({"event": "straggler", "step": i,
                      "sec_per_step": dt, "median_s": med})

            loss = float(np.asarray(metrics["loss"]))
            device_anomaly = bool(np.asarray(metrics["anomaly"]))
            if tr.enabled:
                # attached at the step's EXISTING host sync (the
                # block_until_ready above) — no extra device round-trip
                tr.span_at("train.step", t0, t0 + dt, step=i,
                           loss=loss, anomaly=device_anomaly)
            if guard is not None:
                verdict = guard.observe(i, loss, device_anomaly)
                counters.update(guard.counters)
                if verdict == "rewind":
                    target = ckpt.latest_intact_step() if ckpt else None
                    if (target is not None
                            and guard.counters["rewinds"]
                            < gcfg.max_rewinds):
                        # freeze the flight recorder FIRST: the rewind
                        # restores older state, so the recent-span ring
                        # is the only record of the anomalous run-up
                        tr.postmortem("train_rewind", step=i,
                                      consecutive=guard.consecutive,
                                      rewinds=guard.counters["rewinds"])
                        state = ckpt.restore_state(state, shardings=shd)
                        counters["ckpt_fallbacks"] = ckpt.fallbacks
                        new_i = int(np.asarray(state.step))
                        guard.note_rewind(i, new_i)
                        counters.update(guard.counters)
                        emit({"event": "rewind", "step": i,
                              "to_step": new_i,
                              "consecutive": gcfg.max_consecutive})
                        i = new_i
                        continue
                    if ckpt is not None:
                        tr.postmortem(
                            "training_diverged", step=i,
                            consecutive=guard.consecutive,
                            rewinds=guard.counters["rewinds"])
                        raise TrainingDivergedError(
                            i, guard.consecutive,
                            guard.counters["rewinds"])
                    # no checkpointing: log and push on
                    guard.reset()
                    emit({"event": "rewind_unavailable", "step": i})

            if i % loop.log_every == 0 or i == loop.total_steps - 1:
                m = {k: float(np.asarray(v)) for k, v in metrics.items()}
                if ckpt:
                    counters["ckpt_fallbacks"] = ckpt.fallbacks
                m.update(step=i, sec_per_step=dt, **counters)
                history.append(m)
                if log_fn:
                    log_fn(m)
                else:
                    print(f"step {i:5d} loss {m['loss']:.4f} "
                          f"sparsity {m['sparsity']:.3f} {dt:.2f}s")
            if ckpt and ((i + 1) % loop.ckpt_every == 0):
                ckpt.save(i + 1, state)
            if stop["flag"]:
                print(f"[preempt] signal at step {i}; checkpointing")
                if ckpt:
                    ckpt.save(i + 1, state, blocking=True)
                break
            i += 1
    finally:
        propagating = sys.exc_info()[1] is not None
        if ckpt:
            if propagating:
                try:          # don't mask the in-flight exception
                    ckpt.wait()
                except Exception:
                    pass
            else:
                ckpt.wait()
        for sig, h in old_handlers.items():
            signal.signal(sig, h)
    return state, history

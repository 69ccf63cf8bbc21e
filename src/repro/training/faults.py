"""Deterministic fault injection for PRETRAINING + the training stack's
failure taxonomy (the training counterpart of ``serving/faults.py``).

BLaST is an inference *and pretraining* method, and a prune-grow
schedule makes divergent steps more likely exactly when the sparsifier
just zeroed whole weight blocks — a lost step or a torn checkpoint at
that moment costs a restart, and restart cost dominates training
economics at scale. This module is the TEST SUBSTRATE for the training
loop's recovery guarantees: a seeded ``TrainFaultPlan`` consumed at
fixed step indices so chaos tests are bitwise-reproducible, plus the
structured error types the checkpoint/guard paths raise.

Fault points (all keyed by the HOST step index ``i`` of the train
loop — one ``step_fn`` call):

  * ``nan_grads(step)``      — multiply the loss by ``(1 + NaN/Inf)``
    inside the jitted step, poisoning EVERY gradient; the in-step
    anomaly guard must skip the update (identity state transition);
    the 0.0 no-fault value is a bitwise-exact identity (x * (1+0));
  * ``loss_spike(step, m)``  — add ``m`` to the REPORTED loss only
    (gradients untouched): the host-side EMA/z-score detector must
    flag it while the device-side finite check stays green;
  * ``force_skip(step)``     — force the skip path with healthy
    gradients: the parity oracle's control arm ("a run that never
    applies step k's update");
  * ``hard_kill(step)``      — SIGKILL our own process at the top of
    the step: the subprocess chaos harness's crash; resume must be
    bitwise-identical to an uninterrupted run;
  * ``slow_step(step, s)``   — sleep inside the timed region: the
    straggler watchdog must emit structured telemetry;
  * ``corrupt_checkpoint(nth_save)`` — bit-flip the nth checkpoint's
    array file AFTER it lands on disk (post-rename, post-checksum):
    restore must detect the mismatch and fall back to the newest
    intact checkpoint.

The module also hosts the subprocess chaos child
(``python -m repro.training.faults spec.json``): a self-contained
training run built from a JSON spec that tests and the chaos benchmark
SIGKILL, resume, and compare bitwise against uninterrupted runs.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np


# --------------------------------------------------------------- errors
class TrainingFault(Exception):
    """Base class for every structured training-stack failure."""


class CheckpointCorruptionError(TrainingFault):
    """A checkpoint failed integrity verification (crc32 manifest
    mismatch, torn directory, unreadable arrays)."""

    def __init__(self, step: int | None, directory: str,
                 reason: str = "checksum mismatch"):
        self.step, self.directory, self.reason = step, directory, reason
        super().__init__(
            f"checkpoint step {step} in {directory} failed integrity "
            f"verification: {reason}")


class TrainingDivergedError(TrainingFault):
    """K consecutive anomalous steps and the rewind budget is spent (or
    no intact checkpoint exists to rewind to): the run is diverging
    deterministically — replaying will not help, a human must look."""

    def __init__(self, step: int, consecutive: int, rewinds: int):
        self.step, self.consecutive, self.rewinds = (step, consecutive,
                                                     rewinds)
        super().__init__(
            f"training diverged at step {step}: {consecutive} "
            f"consecutive anomalous steps after {rewinds} rewind(s)")


# ------------------------------------------------------------- the plan
class TrainFaultPlan:
    """A seeded, replayable schedule of injected training faults.

    Build one, arm faults at chosen step indices, and hand it to
    ``train_loop.train(..., faults=plan)``. The plan is consumed as it
    fires — a rewind replays the faulted steps CLEANLY (transient
    hardware faults do not recur on replay), and rerunning the same
    plan instance needs a fresh plan. ``seed`` feeds ``rng`` for tests
    that want randomized-but-reproducible fault placement; the plan
    never draws from it implicitly. ``fired`` is the audit trail."""

    def __init__(self, seed: int = 0):
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self._nan: dict[int, str] = {}          # step -> "nan" | "inf"
        self._spikes: dict[int, float] = {}     # step -> magnitude
        self._skips: set[int] = set()
        self._kills: set[int] = set()
        self._slow: dict[int, float] = {}       # step -> seconds
        self._corrupt_saves: dict[int, int] = {}  # nth save -> bit
        self._n_saves = 0
        self.fired: list[str] = []

    # ----------------------------------------------------------- arming
    def nan_grads(self, step: int, kind: str = "nan") -> "TrainFaultPlan":
        assert kind in ("nan", "inf")
        self._nan[step] = kind
        return self

    def loss_spike(self, step: int,
                   magnitude: float = 1e3) -> "TrainFaultPlan":
        self._spikes[step] = float(magnitude)
        return self

    def force_skip(self, step: int) -> "TrainFaultPlan":
        self._skips.add(step)
        return self

    def hard_kill(self, step: int) -> "TrainFaultPlan":
        self._kills.add(step)
        return self

    def slow_step(self, step: int, seconds: float) -> "TrainFaultPlan":
        self._slow[step] = float(seconds)
        return self

    def corrupt_checkpoint(self, nth_save: int = 0,
                           bit: int = 0) -> "TrainFaultPlan":
        self._corrupt_saves[nth_save] = bit
        return self

    # ------------------------------------------------------- loop hooks
    def step_scalars(self, idx: int) -> dict:
        """Per-step injection scalars riding the batch into the jitted
        step. Always returns all three keys (stable batch pytree
        structure across steps); the no-fault values are bitwise-exact
        identities inside the step."""
        gp = 0.0
        if idx in self._nan:
            kind = self._nan.pop(idx)
            gp = np.nan if kind == "nan" else np.inf
            self.fired.append(f"nan_grads:{kind}@{idx}")
        lp = 0.0
        if idx in self._spikes:
            lp = self._spikes.pop(idx)
            self.fired.append(f"loss_spike@{idx}:{lp:g}")
        fs = 0.0
        if idx in self._skips:
            self._skips.discard(idx)
            fs = 1.0
            self.fired.append(f"force_skip@{idx}")
        return {"grad_poison": np.float32(gp),
                "loss_poison": np.float32(lp),
                "force_skip": np.float32(fs)}

    def on_host_step(self, idx: int) -> None:
        """Top of the host loop iteration: hard process kill (the
        subprocess chaos harness's crash point — nothing after this
        line runs, including any in-flight async checkpoint write)."""
        if idx in self._kills:
            self._kills.discard(idx)
            os.kill(os.getpid(), signal.SIGKILL)

    def on_timed_step(self, idx: int) -> None:
        """Inside the timed region, before the jitted call: a slow step
        the straggler watchdog must notice."""
        s = self._slow.pop(idx, None)
        if s:
            self.fired.append(f"slow@{idx}:{s:g}s")
            time.sleep(s)

    def on_ckpt_saved(self, path: str, step: int) -> None:
        """Checkpointer hook, called AFTER the directory was renamed
        into place (checksums already computed): bit-flip one byte in
        the middle of the array file — host-RAM/disk rot the restore
        verify must catch."""
        nth = self._n_saves
        self._n_saves += 1
        bit = self._corrupt_saves.pop(nth, None)
        if bit is None:
            return
        f = os.path.join(path, "arrays.npz")
        with open(f, "r+b") as fh:
            fh.seek(0, os.SEEK_END)
            off = fh.tell() // 2
            fh.seek(off)
            b = fh.read(1)
            fh.seek(off)
            fh.write(bytes([b[0] ^ (1 << (bit % 8))]))
        self.fired.append(f"ckpt_bitflip:save{nth}@step{step}")


# ----------------------------------------------- subprocess chaos child
def _src_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def default_chaos_spec(workdir: str, **overrides) -> dict:
    """The tiny CPU-runnable training spec the chaos harness kills and
    resumes. ``step_size=5`` with ``kill_at=11`` / ``ckpt_every=4``
    puts the resume replay ACROSS a prune-grow refresh (restore step 8,
    refresh fires at step 10), so masks and params must rewind
    consistently for the bitwise oracle to pass."""
    spec = {
        "model": dict(name="chaos-tiny", family="dense", num_layers=2,
                      d_model=32, num_heads=2, num_kv_heads=2,
                      head_dim=16, d_ff=64, vocab_size=64,
                      mlp_kind="glu", mlp_act="silu",
                      norm_kind="rmsnorm", remat=False,
                      compute_dtype="float32", chunk_size=8),
        "blast": dict(enabled=True, b_in=16, b_out=16, s_max=0.75,
                      total_steps=20, step_size=5, dense_last=1),
        "steps": 16, "seq_len": 32, "batch": 8, "data_seed": 3,
        "opt": dict(peak_lr=2e-2, warmup_steps=5, total_steps=60,
                    weight_decay=0.0),
        "ckpt_dir": None, "ckpt_every": 4, "keep": 3,
        "kill_at": None, "nan_at": [],
        "out": os.path.join(workdir, "final.npz"),
        "meta_out": os.path.join(workdir, "meta.json"),
    }
    spec.update(overrides)
    return spec


def run_child(spec: dict, spec_path: str,
              timeout: float = 600) -> subprocess.CompletedProcess:
    """Write ``spec`` to ``spec_path`` and run the chaos child on it in
    a subprocess (so a ``hard_kill`` SIGKILLs the child, not the
    caller). Returns the CompletedProcess; a killed child has
    ``returncode == -SIGKILL``.

    The child is pinned to the CPU: it tests recovery semantics, not
    speed, and an accelerator belongs to one process at a time — the
    caller may already hold it."""
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (_src_root() + os.pathsep
                         + env.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "-m", "repro.training.faults", spec_path],
        capture_output=True, text=True, env=env, timeout=timeout)


def chaos_child_main(argv: list[str]) -> None:
    """Entry point of the subprocess chaos child: build the spec'd
    model, train (resuming from any intact checkpoint in ckpt_dir),
    then dump the final TrainState to ``out`` and run metadata to
    ``meta_out`` for the parent's bitwise comparison."""
    with open(argv[0]) as f:
        spec = json.load(f)
    import jax

    from repro.checkpointing.checkpoint import Checkpointer, _flatten
    from repro.configs.base import ModelConfig
    from repro.core.prune_grow import BlastSpec
    from repro.data.pipeline import SyntheticLM
    from repro.optim import adamw
    from repro.training import train_loop

    cfg = ModelConfig(**spec["model"], blast=BlastSpec(**spec["blast"]))
    src = SyntheticLM(cfg.vocab_size, spec["seq_len"], spec["batch"],
                      seed=spec["data_seed"])
    opt = adamw.AdamWConfig(**spec["opt"])
    plan = TrainFaultPlan()
    if spec.get("kill_at") is not None:
        plan.hard_kill(spec["kill_at"])
    for s in spec.get("nan_at", []):
        plan.nan_grads(s)
    resumed_from = None
    restore_s = 0.0
    if spec.get("ckpt_dir"):
        t0 = time.monotonic()
        resumed_from = Checkpointer(spec["ckpt_dir"],
                                    keep=spec["keep"]).latest_intact_step()
        restore_s = time.monotonic() - t0
    loop = train_loop.TrainLoopConfig(
        total_steps=spec["steps"], ckpt_dir=spec.get("ckpt_dir"),
        ckpt_every=spec["ckpt_every"], keep=spec["keep"],
        log_every=10 ** 9)
    t0 = time.monotonic()
    state, hist = train_loop.train(cfg, opt, src, loop, faults=plan,
                                   log_fn=lambda m: None)
    wall = time.monotonic() - t0
    flat = _flatten({"step": state.step, "params": state.params,
                     "opt_state": state.opt_state, "masks": state.masks,
                     "rng": state.rng})
    np.savez(spec["out"],
             **{k: np.asarray(jax.device_get(v)) for k, v in flat.items()})
    counters = {k: hist[-1].get(k) for k in
                ("anomaly_steps", "skipped_steps", "rewinds",
                 "ckpt_fallbacks")} if hist else {}
    with open(spec["meta_out"], "w") as f:
        json.dump({"resumed_from": resumed_from, "wall_s": wall,
                   "verify_latency_s": restore_s, "fired": plan.fired,
                   "counters": counters}, f)


if __name__ == "__main__":
    chaos_child_main(sys.argv[1:])

"""Distribution correctness: sharding rules + sharded-vs-single-device
equivalence (the latter in a subprocess so the forced device count never
leaks into other tests)."""
import json
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P


def test_spec_for_divisibility():
    from repro.distributed.sharding import spec_for
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    # kv=4 heads on a 1-wide model axis: divisible -> sharded
    assert spec_for((4, 16), ("kv_heads", "head_dim"), mesh) == \
        P("model", None)


def test_spec_for_fallback_replicates():
    import jax
    from repro.distributed.sharding import spec_for
    from repro.launch.mesh import make_mesh
    if len(jax.devices()) != 1:
        pytest.skip("needs single-device run")
    mesh = make_mesh((1, 1), ("data", "model"))
    # 3 not divisible by nothing... size-1 axes always divide
    assert spec_for((3,), ("ff",), mesh) == P("model")


@pytest.mark.parametrize("model_axis, blocks",
                         [(1, (128, 128)), (4, (128, 64)), (16, (128, 16))])
def test_shard_blocks_follow_model_axis(model_axis, blocks):
    """BLaST blocks tile the d_ff shard one device holds (stablelm-3b:
    6912 / model_axis); the rest of the schedule is kept."""
    import dataclasses
    from jax.sharding import AbstractMesh
    from repro.configs import get_config
    from repro.launch.mesh import shard_blocks
    cfg = get_config("stablelm-3b")
    cfg = dataclasses.replace(cfg, blast=dataclasses.replace(
        cfg.blast, total_steps=7, s_max=0.5))
    out = shard_blocks(cfg, AbstractMesh((1, model_axis),
                                         ("data", "model")))
    assert (out.blast.b_in, out.blast.b_out) == blocks
    assert (out.blast.total_steps, out.blast.s_max) == (7, 0.5)
    dense = dataclasses.replace(cfg, blast=dataclasses.replace(
        cfg.blast, enabled=False))
    assert shard_blocks(dense, AbstractMesh((1, 4), ("data", "model"))) \
        is dense


_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
import sys
sys.path.insert(0, "tests")
from conftest import tiny_cfg
from repro.distributed import sharding as shd
from repro.distributed.context import DistContext
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.training import step as ts

cfg = tiny_cfg(num_heads=4, num_kv_heads=2, d_model=64, d_ff=128,
               head_dim=16)
opt = adamw.AdamWConfig(total_steps=20, warmup_steps=1)
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                 cfg.vocab_size),
    "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                                 cfg.vocab_size),
}
state = ts.init_state(cfg, jax.random.PRNGKey(0))

# single-device reference
step1 = jax.jit(ts.make_train_step(cfg, opt))
_, m1 = step1(state, batch)

# 2x4 mesh sharded
mesh = make_mesh((2, 4), ("data", "model"))
dist = DistContext(mesh=mesh)
state_shd = ts.state_sharding(cfg, mesh)
batch_shd = {k: shd.batch_sharding(mesh, v.ndim, v.shape[0])
             for k, v in batch.items()}
with mesh:
    step2 = jax.jit(ts.make_train_step(cfg, opt, dist=dist),
                    in_shardings=(state_shd, batch_shd),
                    out_shardings=(state_shd, None))
    _, m2 = step2(state, batch)
print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
                  "gn1": float(m1["grad_norm"]),
                  "gn2": float(m2["grad_norm"])}))
"""


@pytest.mark.slow
def test_sharded_equals_single_device(tmp_path):
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _EQUIV_SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    vals = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(vals["loss1"] - vals["loss2"]) < 1e-3, vals
    assert abs(vals["gn1"] - vals["gn2"]) / max(vals["gn1"], 1) < 2e-2


@pytest.mark.slow
def test_dryrun_cell_on_host_devices():
    """Full dry-run entry on a small forced topology happens in the
    dedicated dryrun sweep; here we assert the module at least lowers a
    decode cell on 512 host devices end-to-end."""
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "internvl2-2b", "--shape", "decode_32k", "--out", ""],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "dry-run OK" in out.stdout

"""Elastic restart: a checkpoint written from an 8-device (2x4) mesh
restores onto a 4-device (2x2) mesh (e.g. after losing half a pod) and
training continues with identical loss — checkpoints are logical, not
per-device (DESIGN.md §4)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os, sys, json, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, "tests")
from conftest import tiny_cfg
from repro.checkpointing.checkpoint import Checkpointer
from repro.distributed import sharding as shd
from repro.distributed.context import DistContext
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.training import step as ts

cfg = tiny_cfg(num_heads=4, num_kv_heads=2, d_model=64, d_ff=128,
               head_dim=16)
opt = adamw.AdamWConfig(total_steps=20, warmup_steps=0)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                      cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                                      cfg.vocab_size)}

def run_step(mesh, state):
    dist = DistContext(mesh=mesh)
    s_shd = ts.state_sharding(cfg, mesh)
    b_shd = {k: shd.batch_sharding(mesh, v.ndim, v.shape[0])
             for k, v in batch.items()}
    with mesh:
        f = jax.jit(ts.make_train_step(cfg, opt, dist=dist),
                    in_shardings=(s_shd, b_shd),
                    out_shardings=(s_shd, None))
        return f(state, batch)

d = tempfile.mkdtemp()
# step 0 on the BIG mesh (2x4 = "two pods"), checkpoint
big = make_mesh((2, 4), ("data", "model"))
state = ts.init_state(cfg, jax.random.PRNGKey(0))
state, m0 = run_step(big, state)
ck = Checkpointer(d)
ck.save(1, state, blocking=True)

# "lose a pod": restore onto a 2x2 mesh built from 4 devices
small = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
tmpl = ts.init_state(cfg, jax.random.PRNGKey(0))
restored = ck.restore_state(tmpl, shardings=None)
restored = jax.tree_util.tree_map(jnp.asarray, restored)
_, m_small = run_step(small, restored)

# reference: continue on the big mesh
_, m_big = run_step(big, state)
print(json.dumps({"small": float(m_small["loss"]),
                  "big": float(m_big["loss"])}))
"""


@pytest.mark.slow
def test_elastic_restart_across_meshes():
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    v = json.loads(out.stdout.strip().splitlines()[-1])
    assert v["small"] == pytest.approx(v["big"], rel=1e-4), v

"""Deferred-compressed DP gradient reduction (training/deferred.py) —
the partial-manual shard_map train step must match the GSPMD step."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax, jax.numpy as jnp, numpy as np
import sys
sys.path.insert(0, "tests")
from conftest import tiny_cfg
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.training import step as ts, deferred

mesh = make_mesh((2, 4), ("data", "model"))
cfg = tiny_cfg(num_heads=4, num_kv_heads=2, d_model=64, d_ff=128,
               head_dim=16)
opt = adamw.AdamWConfig(total_steps=20, warmup_steps=0)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                      cfg.vocab_size),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                                      cfg.vocab_size)}
state = ts.init_state(cfg, jax.random.PRNGKey(0))
s_ref, m_ref = jax.jit(ts.make_train_step(cfg, opt))(state, batch)

state_d = dataclasses.replace(
    state, opt_state=deferred.init_opt_state(cfg, state.params, False))
with mesh:
    step = jax.jit(deferred.make_train_step_deferred(
        cfg, opt, mesh, microbatches=2, compress_grads=False))
    s_d, m_d = step(state_d, batch)

# uncompressed deferred must match the GSPMD step closely
dp = max(float(jnp.abs(a - b).max()) for a, b in
         zip(jax.tree_util.tree_leaves(s_ref.params),
             jax.tree_util.tree_leaves(s_d.params)))

state_c = dataclasses.replace(
    state, opt_state=deferred.init_opt_state(cfg, state.params, True))
with mesh:
    step_c = jax.jit(deferred.make_train_step_deferred(
        cfg, opt, mesh, microbatches=2, compress_grads=True))
    s_c, m_c = step_c(state_c, batch)

print(json.dumps({
    "loss_ref": float(m_ref["loss"]), "loss_d": float(m_d["loss"]),
    "loss_c": float(m_c["loss"]), "param_diff": dp,
    "sparsity_d": float(m_d["sparsity"]),
}))
"""


@pytest.mark.slow
def test_deferred_matches_gspmd_step():
    env = dict(os.environ, PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    v = json.loads(out.stdout.strip().splitlines()[-1])
    assert v["loss_ref"] == pytest.approx(v["loss_d"], rel=1e-5)
    assert v["loss_ref"] == pytest.approx(v["loss_c"], rel=1e-5)
    assert v["param_diff"] < 5e-5, v

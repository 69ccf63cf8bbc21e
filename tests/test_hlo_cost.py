"""Loop-weighted HLO cost model validation (the roofline backbone —
EXPERIMENTS.md §Roofline methodology)."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import hlo_cost


def _analyze(fn, *args):
    return hlo_cost.analyze_text(
        jax.jit(fn).lower(*args).compile().as_text())


def test_plain_matmul_flops_exact():
    r = _analyze(lambda a, b: a @ b, jnp.ones((64, 32)),
                 jnp.ones((32, 16)))
    assert r["flops"] == 2 * 64 * 32 * 16


def test_scan_flops_weighted_by_trip_count():
    x = jnp.ones((128, 128))
    r = _analyze(lambda x: jax.lax.scan(
        lambda c, _: (c @ c, None), x, None, length=10)[0], x)
    assert r["flops"] == pytest.approx(10 * 2 * 128 ** 3, rel=1e-3)


def test_xla_cost_analysis_undercounts_scans():
    """The reason this module exists: XLA counts loop bodies once."""
    x = jnp.ones((128, 128))
    f = jax.jit(lambda x: jax.lax.scan(
        lambda c, _: (c @ c, None), x, None, length=10)[0])
    xla = f.lower(x).compile().cost_analysis()
    if isinstance(xla, list):      # older jax returned one dict per device
        xla = xla[0]
    assert xla["flops"] < 2.1 * 2 * 128 ** 3   # ~1 body, not 10


def test_nested_scan_weights_multiply():
    x = jnp.ones((32, 32))

    def inner(c):
        return jax.lax.scan(lambda c, _: (c @ c, None), c, None,
                            length=4)[0]

    def outer(x):
        return jax.lax.scan(lambda c, _: (inner(c), None), x, None,
                            length=3)[0]

    r = _analyze(outer, x)
    assert r["flops"] == pytest.approx(12 * 2 * 32 ** 3, rel=1e-3)


def test_scan_memory_not_charged_full_stack():
    """Per-trip dynamic-slice must charge the slice, not the stack."""
    ws = jnp.ones((100, 64, 64))   # 100 x 16 KiB stacked weights
    x = jnp.ones((8, 64))

    def f(x, ws):
        return jax.lax.scan(lambda c, w: (c @ w, None), x, ws)[0]

    r = _analyze(f, x, ws)
    stack_bytes = ws.size * 4
    # full-stack charging would be >= 100 * stack = 163 MB; windowed
    # charging is ~100 x (slice + activations) ~= 2 MB
    assert r["bytes_accessed"] < 10 * stack_bytes


def test_collectives_weighted(tmp_path):
    import os
    import subprocess
    import sys
    # collective inside a scan on 8 fake devices, counted x trips
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, sys
sys.path.insert(0, "src")
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.roofline import hlo_cost
mesh = make_mesh((2, 4), ("data", "model"))
def step(x, w):
    return jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]
xs = jnp.ones((16, 256)); ws = jnp.ones((6, 256, 256))
with mesh:
    f = jax.jit(step, in_shardings=(
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P(None, "model", None))))
    r = hlo_cost.analyze_text(f.lower(xs, ws).compile().as_text())
ar = r["collectives"]["bytes"]["all-reduce"]
assert ar == 6 * (16 // 2) * 256 * 4, ar
print("OK")
"""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))), timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-1500:]

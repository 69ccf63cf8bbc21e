"""The Pallas kernels of the main path, compiled for a described TPU v5e
chip at stablelm-3b widths with the served config's block shape. Nothing
runs and no chip is needed: the TPU compiler refuses here what it would
refuse on the chip (block tiling, VMEM use)."""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import sparse_mlp as sm
from repro.core.packing import PackedBCSC

CFG = get_config("stablelm-3b")
SPARSITY = 0.8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off:
    a compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _packed(sharding, path, joint=False):
    """Abstract packed weight of ``path`` at the served block shape."""
    k, n = {"w_gate": (CFG.d_model, CFG.d_ff),
            "w_up": (CFG.d_model, CFG.d_ff),
            "w_down": (CFG.d_ff, CFG.d_model)}[path.split("/")[-1]]
    bi, bo = sm.block_dims_for(CFG.blast, path)
    kb, nb = k // bi, n // bo
    nnz = math.ceil((1 - SPARSITY) * kb)
    return PackedBCSC(blocks=_sds(sharding, (nb, nnz, bi, bo), jnp.bfloat16),
                      idx=_sds(sharding, (nb, nnz), jnp.int32), kb=kb,
                      joint=joint)


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_served_block_shape_is_lane_wide():
    assert (CFG.blast.b_in, CFG.blast.b_out) == (128, 128)


@pytest.mark.parametrize("m", [4, 128])
@pytest.mark.parametrize("path", ["layers/mlp/w_gate", "layers/mlp/w_down"])
def test_bspmm_compiles(one_chip, path, m):
    from repro.kernels import bspmm as bk
    p = _packed(one_chip, path)
    x = _sds(one_chip, (m, p.kb * p.b_in), jnp.bfloat16)
    _assert_kernel_compiles(lambda x, p: bk.bspmm(x, p), x, p)


@pytest.mark.parametrize("m", [4, 128])
@pytest.mark.parametrize("joint", [False, True], ids=["two-index", "joint"])
def test_fused_glu_compiles(one_chip, joint, m):
    from repro.kernels import bspmm as bk
    pg = _packed(one_chip, "layers/mlp/w_gate", joint=joint)
    pu = _packed(one_chip, "layers/mlp/w_up", joint=joint)
    x = _sds(one_chip, (m, CFG.d_model), jnp.bfloat16)
    _assert_kernel_compiles(
        lambda x, a, b: bk.fused_glu(x, a, b, act=CFG.mlp_act), x, pg, pu)


def test_paged_flash_decode_compiles(one_chip):
    from repro.kernels import paged_attention as pk
    b, r, ps, n_pages = 4, 8, 16, 256
    kvh, hd = CFG.num_kv_heads, CFG.head_dim
    g = CFG.num_heads // kvh
    pool = _sds(one_chip, (n_pages, ps, kvh, hd), jnp.bfloat16)
    _assert_kernel_compiles(
        lambda q, k, v, bt, bias: pk.paged_flash_decode(
            q, k, v, bt, bias, scale=1.0 / math.sqrt(hd)),
        _sds(one_chip, (b, kvh, g, hd), jnp.bfloat16), pool, pool,
        _sds(one_chip, (b, r), jnp.int32),
        _sds(one_chip, (b, r * ps), jnp.float32))

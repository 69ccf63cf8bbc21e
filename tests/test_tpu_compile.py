"""The Pallas kernels of the main path, compiled for a described TPU v5e
chip at stablelm-3b widths with the served config's block shape, and the
served decode slab and mixed step at the served cell's sizes. Nothing
runs and no chip is needed: the TPU compiler refuses here what it would
refuse on the chip (block tiling, VMEM use), and shows how the serving
steps hold the KV pool."""
import math
import os
import re

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
    pool = _sds(one_chip, (1, n_pages, ps, kvh, hd), jnp.bfloat16)
    _assert_kernel_compiles(
        lambda q, k, v, bt, bias: pk.paged_flash_decode(
            q, k, v, bt, bias, scale=1.0 / math.sqrt(hd), layer=0),
        _sds(one_chip, (b, kvh, g, hd), jnp.bfloat16), pool, pool,
        _sds(one_chip, (b, r), jnp.int32),
        _sds(one_chip, (b, r * ps), jnp.float32))


def test_paged_flash_decode_compiles_on_stacked_pool(one_chip):
    """The kernel reads one layer's pages straight out of the whole
    layer-stacked pool that the decode step carries (no sliced layer)."""
    from repro.kernels import paged_attention as pk
    b, r, ps, n_pages = 4, 8, 16, 256
    kvh, hd = CFG.num_kv_heads, CFG.head_dim
    g = CFG.num_heads // kvh
    pool = _sds(one_chip, (CFG.num_layers, n_pages, ps, kvh, hd),
                jnp.bfloat16)
    _assert_kernel_compiles(
        lambda q, k, v, bt, bias, layer: pk.paged_flash_decode(
            q, k, v, bt, bias, scale=1.0 / math.sqrt(hd), layer=layer),
        _sds(one_chip, (b, kvh, g, hd), jnp.bfloat16), pool, pool,
        _sds(one_chip, (b, r), jnp.int32),
        _sds(one_chip, (b, r * ps), jnp.float32),
        _sds(one_chip, (), jnp.int32))


# The served cell's engine (bench/configs/stablelm-3b-s80.json): lanes,
# pool pages, page size, max_len, slab length, prefill chunk; 64 read
# pages is the bucket most of its decode slabs run at.
LANES, POOL_PAGES, PAGE, MAX_LEN, SLAB_K, CHUNK, READ = \
    16, 480, 16, 2048, 8, 16, 64


def _served_params(sharding):
    """Abstract bf16 stablelm-3b parameters, MLP packed at SPARSITY."""
    from repro.models import registry
    params = jax.tree_util.tree_map(
        lambda x: _sds(sharding, x.shape, jnp.bfloat16),
        registry.abstract_params(CFG))
    mlp = params["layers"]["mlp"]
    for name in mlp:
        p = _packed(sharding, f"layers/mlp/{name}", joint=name != "w_down")
        mlp[name] = PackedBCSC(
            blocks=_sds(sharding, (CFG.num_layers,) + p.blocks.shape,
                        jnp.bfloat16),
            idx=_sds(sharding, (CFG.num_layers,) + p.idx.shape, jnp.int32),
            kb=p.kb, joint=p.joint)
    return params


def _compile_serving_step(sharding, step):
    """The engine's jitted call (cache donated, as ``Engine`` jits it)
    at the cell's sizes -> (compiled, pool bytes)."""
    from repro.models import registry
    from repro.serving import step as st
    cache = jax.tree_util.tree_map(
        lambda x: _sds(sharding, x.shape, x.dtype),
        jax.eval_shape(lambda: registry.init_paged_cache(CFG, POOL_PAGES,
                                                         PAGE)))
    lanes = _sds(sharding, (LANES,), jnp.int32)
    tables = _sds(sharding, (LANES, MAX_LEN // PAGE), jnp.int32)
    poison = _sds(sharding, (LANES,), jnp.float32)
    if step == "paged_decode_slab":
        fn = st.make_paged_decode_slab_step(CFG, SLAB_K, MAX_LEN, PAGE)
        flags = _sds(sharding, (LANES,), jnp.bool_)
        args = (cache, {"pending": lanes, "frontier": lanes,
                        "offsets": lanes, "remaining": lanes,
                        "live": flags, "poison": poison,
                        "faulted": flags, "bt": tables})
        kw = {}
    elif step == "paged_prefill":
        fn = st.make_paged_prefill_chunk_step(CFG)
        args = (cache, _sds(sharding, (LANES, CHUNK), jnp.int32),
                _sds(sharding, (), jnp.int32), lanes,
                _sds(sharding, (LANES,), jnp.bool_), tables)
        kw = {}
    else:
        fn = st.make_mixed_step(CFG)
        args = (cache, _sds(sharding, (LANES, CHUNK), jnp.int32), lanes,
                lanes, lanes, tables)
        kw = {"poison": poison}
    jitted = jax.jit(fn, static_argnames=("read_pages",),
                     donate_argnums=(1,))
    compiled = jitted.lower(_served_params(sharding), *args,
                            read_pages=READ, **kw).compile()
    pool = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(cache))
    return compiled, pool


@pytest.mark.parametrize("step", ["paged_decode_slab", "mixed_step",
                                  "paged_prefill"])
def test_serving_step_updates_pool_in_place(one_chip, step):
    """The KV pool rides the layer scan's carry and is donated: no value
    of the program has the shape of one layer's pool (the scan would
    slice each layer's pool out and restack it), no whole-pool copy
    sits inside a loop, nothing is rematerialised, and the scratch
    stays under two pools. Compiled for a v5e: the slab needs 1.6 pools
    of scratch (4.03 GB); with the pool scanned as xs/ys it needed 4.9
    (12.38 GB) and rematerialised the gathered window. The mixed step
    and the paged prefill chunk need 1.6 pools too, for their whole-pool
    layout copies at entry and exit (1.27 and 1.22 in their xs/ys form,
    donated)."""
    compiled, pool = _compile_serving_step(one_chip, step)
    text = compiled.as_text()
    layer_pool = "bf16[{},{},{},{}]".format(POOL_PAGES, PAGE,
                                            CFG.num_kv_heads, CFG.head_dim)
    assert layer_pool not in text
    whole = re.compile(r"= bf16\[{},{}[^ ]* copy\(".format(
        CFG.num_layers, POOL_PAGES))
    bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    for body in bodies:
        comp = re.search(r"^%{} .*?^\}}".format(re.escape(body)), text,
                         re.M | re.S).group(0)
        assert not whole.search(comp), body
    assert "remat" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * pool

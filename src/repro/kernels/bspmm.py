"""BLaST BSpMM as a Pallas TPU kernel (paper §3.3, TPU-native redesign).

Computes ``Y[M, N] = X[M, K] @ W`` where W is block-sparse in *balanced
BCSC*: every block-column holds exactly ``nnz`` kept (b_in, b_out) blocks
(``core/packing.py``). The TPU adaptation of the paper's Triton kernel
(DESIGN.md §2):

  * grid = (M tiles, block-columns, nnz)  — static because the sparsifier
    produces balanced structure (the paper's "no skewed load imbalance",
    taken to its static-shape conclusion);
  * the scalar-prefetched block-row index table drives the
    ``BlockSpec.index_map`` of the dense operand X, so Mosaic's pipeline
    only DMAs the X tiles that the sparsity structure actually needs —
    the TPU analogue of the paper's "only necessary blocks of X can be
    loaded" (paper Listing 2's pointer algebra becomes an index map);
  * accumulation in an f32 VMEM scratch tile, written out on the last
    nnz step; MXU engaged via jnp.dot with preferred f32 accumulation.

Validated in interpret mode against ``ref.py`` over shape/dtype sweeps
(tests/test_kernels_bspmm.py) and compiled for a TPU v5e at stablelm-3b
widths (tests/test_tpu_compile.py). The X and output tiles
``(blk_m, b_in)`` / ``(blk_m, b_out)`` must be lane-wide on the chip:
b_in and b_out multiples of 128 (or the whole K and N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PackedBCSC


def _bspmm_kernel(idx_ref, x_ref, w_ref, o_ref, acc_ref):
    """One (i, j, k) grid step: acc += X[i, idx[j,k]] @ Wblk[j,k]."""
    k = pl.program_id(2)
    nnz = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0, 0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nnz - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_m", "interpret"))
def bspmm(x: jax.Array, packed: PackedBCSC, *, blk_m: int = 128,
          interpret: bool = False) -> jax.Array:
    """Y = X @ W (packed balanced BCSC). ``blk_m`` is the paper's blk_M —
    rows of X reused per VMEM-resident sparse block (COSMA-style reuse).

    Requires M % blk_m == 0 (callers pad; serving shapes are multiples of
    8 already)."""
    m, k_dim = x.shape
    nb, nnz, b_in, b_out = packed.blocks.shape
    assert packed.kb * b_in == k_dim, (packed.kb, b_in, k_dim)
    blk_m = min(blk_m, m)
    assert m % blk_m == 0, f"M={m} not a multiple of blk_m={blk_m}"
    n = nb * b_out

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // blk_m, nb, nnz),
        in_specs=[
            pl.BlockSpec((blk_m, b_in),
                         lambda i, j, k, idx: (i, idx[j, k])),
            pl.BlockSpec((1, 1, b_in, b_out),
                         lambda i, j, k, idx: (j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((blk_m, b_out),
                               lambda i, j, k, idx: (i, j)),
        scratch_shapes=[pltpu.VMEM((blk_m, b_out), jnp.float32)],
    )
    return pl.pallas_call(
        _bspmm_kernel,
        grid_spec=grid_spec,
        name="bspmm",
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(packed.idx, x, packed.blocks)


def _fused_glu_kernel(act_id, idx_g_ref, idx_u_ref, xg_ref, xu_ref,
                      wg_ref, wu_ref, o_ref, accg_ref, accu_ref):
    """Fused front half of the Sparse MLP (paper §3.3.3):
    H[i, j] = act(sum_k X @ Wg) * (sum_k X @ Wu), both sums sparse."""
    k = pl.program_id(2)
    nnz = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    accg_ref[...] += jnp.dot(xg_ref[...], wg_ref[0, 0],
                             preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(xu_ref[...], wu_ref[0, 0],
                             preferred_element_type=jnp.float32)

    @pl.when(k == nnz - 1)
    def _flush():
        hg = accg_ref[...]
        if act_id == 0:
            a = jax.nn.silu(hg)
        elif act_id == 1:
            a = jax.nn.gelu(hg, approximate=True)
        else:
            a = jax.nn.relu(hg)
        o_ref[...] = (a * accu_ref[...]).astype(o_ref.dtype)


def _fused_glu_joint_kernel(act_id, idx_ref, x_ref, wg_ref, wu_ref,
                            o_ref, accg_ref, accu_ref):
    """Joint-structure variant: gate and up share ONE idx table, so each
    X tile is a single operand — Mosaic DMAs it once per (i, j, k) step
    instead of twice (the gate/up weight streams stay separate)."""
    k = pl.program_id(2)
    nnz = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    xt = x_ref[...]
    accg_ref[...] += jnp.dot(xt, wg_ref[0, 0],
                             preferred_element_type=jnp.float32)
    accu_ref[...] += jnp.dot(xt, wu_ref[0, 0],
                             preferred_element_type=jnp.float32)

    @pl.when(k == nnz - 1)
    def _flush():
        hg = accg_ref[...]
        if act_id == 0:
            a = jax.nn.silu(hg)
        elif act_id == 1:
            a = jax.nn.gelu(hg, approximate=True)
        else:
            a = jax.nn.relu(hg)
        o_ref[...] = (a * accu_ref[...]).astype(o_ref.dtype)


_ACT_IDS = {"silu": 0, "gelu": 1, "relu": 2}


def _fused_glu_joint(x, p_gate, p_up, *, act, blk_m, interpret):
    """Single-X-stream fused GLU (``PackedBCSC.joint`` pack-time
    promise: identical gate/up idx tables)."""
    m, _ = x.shape
    nb, nnz, b_in, b_out = p_gate.blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // blk_m, nb, nnz),
        in_specs=[
            pl.BlockSpec((blk_m, b_in),
                         lambda i, j, k, idx: (i, idx[j, k])),
            pl.BlockSpec((1, 1, b_in, b_out),
                         lambda i, j, k, idx: (j, k, 0, 0)),
            pl.BlockSpec((1, 1, b_in, b_out),
                         lambda i, j, k, idx: (j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((blk_m, b_out),
                               lambda i, j, k, idx: (i, j)),
        scratch_shapes=[pltpu.VMEM((blk_m, b_out), jnp.float32),
                        pltpu.VMEM((blk_m, b_out), jnp.float32)],
    )
    kernel = functools.partial(_fused_glu_joint_kernel, _ACT_IDS[act])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="fused_glu",
        out_shape=jax.ShapeDtypeStruct((m, nb * b_out), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(p_gate.idx, x, p_gate.blocks, p_up.blocks)


@functools.partial(jax.jit,
                   static_argnames=("act", "blk_m", "interpret"))
def fused_glu(x: jax.Array, p_gate: PackedBCSC, p_up: PackedBCSC, *,
              act: str = "silu", blk_m: int = 128,
              interpret: bool = False) -> jax.Array:
    """H = act(X Wg) * (X Wu) in ONE kernel — the memory-bound
    nonlinearity fused into the compute-bound SpMM epilogue (paper
    §3.3.3). Wg and Wu normally have independent sparsity structures
    (two scalar-prefetched index tables, two accumulators); when both
    carry the pack-time ``joint`` promise (identical idx tables, the
    common joint-pruning case) X becomes a single operand and each of
    its tiles is DMA'd once instead of twice."""
    m, k_dim = x.shape
    if p_gate.nnz != p_up.nnz:   # align (zero-block padding, exact)
        from repro.core.packing import pad_nnz
        nnz_max = max(p_gate.nnz, p_up.nnz)
        p_gate = pad_nnz(p_gate, nnz_max)
        p_up = pad_nnz(p_up, nnz_max)
    nb, nnz, b_in, b_out = p_gate.blocks.shape
    assert p_up.blocks.shape == (nb, nnz, b_in, b_out)
    blk_m = min(blk_m, m)
    assert m % blk_m == 0
    if p_gate.joint and p_up.joint:
        return _fused_glu_joint(x, p_gate, p_up, act=act, blk_m=blk_m,
                                interpret=interpret)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // blk_m, nb, nnz),
        in_specs=[
            pl.BlockSpec((blk_m, b_in),
                         lambda i, j, k, ig, iu: (i, ig[j, k])),
            pl.BlockSpec((blk_m, b_in),
                         lambda i, j, k, ig, iu: (i, iu[j, k])),
            pl.BlockSpec((1, 1, b_in, b_out),
                         lambda i, j, k, ig, iu: (j, k, 0, 0)),
            pl.BlockSpec((1, 1, b_in, b_out),
                         lambda i, j, k, ig, iu: (j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((blk_m, b_out),
                               lambda i, j, k, ig, iu: (i, j)),
        scratch_shapes=[pltpu.VMEM((blk_m, b_out), jnp.float32),
                        pltpu.VMEM((blk_m, b_out), jnp.float32)],
    )
    kernel = functools.partial(_fused_glu_kernel, _ACT_IDS[act])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="fused_glu",
        out_shape=jax.ShapeDtypeStruct((m, nb * b_out), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(p_gate.idx, p_up.idx, x, x, p_gate.blocks, p_up.blocks)

"""Transposed BSpMM: dX = dY @ W^T with W in packed balanced BCSC —
the backward kernel that makes PACKED weights trainable (sparse
fine-tuning at fixed masks), not just servable.

W^T scatters: block (row=idx[j,k], col=j) of W contributes its transpose
at output block-column idx[j,k]. The TPU grid is sequential over
("arbitrary") dimensions, so read-modify-write accumulation into a
revisited output block is safe; a scalar-prefetched FIRST-VISIT flag
table (host-computed from idx — static) selects init-vs-accumulate, and
a final pass zeroes never-visited blocks via a visited-count table.

To keep never-visited output blocks defined, the wrapper zero-initialises
the output via input_output_aliasing of a zeros buffer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.packing import PackedBCSC


def first_visit_flags(idx: np.ndarray, kb: int) -> np.ndarray:
    """(Nb, nnz) int32: 1 where this (j,k) is the first occurrence of
    idx[j,k] in (j,k)-lexicographic traversal order."""
    seen = np.zeros(kb, bool)
    nb, nnz = idx.shape
    flags = np.zeros((nb, nnz), np.int32)
    for j in range(nb):
        for k in range(nnz):
            r = int(idx[j, k])
            if not seen[r]:
                flags[j, k] = 1
                seen[r] = True
    return flags


def _bspmm_t_kernel(idx_ref, first_ref, dy_ref, w_ref, o_ref):
    j = pl.program_id(1)
    k = pl.program_id(2)
    part = jnp.dot(dy_ref[...], w_ref[0, 0].T,
                   preferred_element_type=jnp.float32)

    @pl.when(first_ref[j, k] == 1)
    def _init():
        o_ref[...] = part.astype(o_ref.dtype)

    @pl.when(first_ref[j, k] != 1)
    def _acc():
        o_ref[...] = (o_ref[...].astype(jnp.float32)
                      + part).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("kb", "blk_m", "interpret"))
def _bspmm_t_call(dy, blocks, idx, first, kb, *, blk_m=128,
                  interpret=False):
    m = dy.shape[0]
    nb, nnz, b_in, b_out = blocks.shape
    blk_m = min(blk_m, m)
    assert m % blk_m == 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(m // blk_m, nb, nnz),
        in_specs=[
            pl.BlockSpec((blk_m, b_out),
                         lambda i, j, k, idx, first: (i, j)),
            pl.BlockSpec((1, 1, b_in, b_out),
                         lambda i, j, k, idx, first: (j, k, 0, 0)),
        ],
        out_specs=pl.BlockSpec((blk_m, b_in),
                               lambda i, j, k, idx, first: (i, idx[j, k])),
    )
    return pl.pallas_call(
        _bspmm_t_kernel,
        grid_spec=grid_spec,
        name="bspmm_t",
        out_shape=jax.ShapeDtypeStruct((m, kb * b_in), dy.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(idx, first, dy, blocks)


def bspmm_t(dy: jax.Array, packed: PackedBCSC, *, blk_m: int = 128,
            interpret: bool = False) -> jax.Array:
    """dX[M, K] = dY[M, N] @ W^T (packed balanced BCSC).

    Block-rows of W never touched by any kept block produce zero output
    columns (handled by a host-computed mask of visited rows)."""
    idx_np = np.asarray(jax.device_get(packed.idx))
    first = jnp.asarray(first_visit_flags(idx_np, packed.kb))
    dx = _bspmm_t_call(dy, packed.blocks, packed.idx, first, packed.kb,
                       blk_m=blk_m, interpret=interpret)
    visited = np.zeros(packed.kb, bool)
    visited[idx_np.reshape(-1)] = True
    if visited.all():
        return dx
    keep = jnp.repeat(jnp.asarray(visited), packed.b_in)
    # never-visited output blocks hold garbage (not written): hard-zero
    return jnp.where(keep[None, :], dx, 0).astype(dx.dtype)

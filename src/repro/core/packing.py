"""Packed balanced-BCSC representation for serving (DESIGN.md §2).

After training, each sparse weight W (K, N) with a *balanced* block mask
(the same number ``nnz`` of kept blocks in every block-column) is packed
into:

    blocks : (Nb, nnz, b_in, b_out)   kept block values, column-major
    idx    : (Nb, nnz) int32          block-row index of each kept block

which is the static-shape TPU analogue of the paper's BCSC format. The
Pallas kernel and the XLA scan formulation both consume this layout. For
*unbalanced* (global top-k) masks, columns are padded with zero blocks up
to the max per-column count (idx points at block-row 0; the zero values
make the contribution exact).

Pure-jnp, differentiable where it matters (pack is gather; unpack is
scatter) — but serving treats packed weights as constants.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PackedBCSC:
    blocks: jax.Array   # (..., Nb, nnz, b_in, b_out)
    idx: jax.Array      # (..., Nb, nnz) int32
    kb: int             # number of block-rows (STATIC pytree metadata)
    # STATIC pack-time promise: this operand's idx table is identical to
    # its fused-GLU partner's (joint gate/up pruning), so the fused
    # kernel may stream each X tile ONCE for both contractions. Being
    # pytree metadata it survives jit tracing — set it via mark_joint().
    joint: bool = False

    @property
    def nnz(self) -> int:
        return self.idx.shape[-1]

    @property
    def nb(self) -> int:
        return self.idx.shape[-2]

    @property
    def b_in(self) -> int:
        return self.blocks.shape[-2]

    @property
    def b_out(self) -> int:
        return self.blocks.shape[-1]

    def dense_shape(self):
        return (self.kb * self.b_in, self.nb * self.b_out)


jax.tree_util.register_dataclass(
    PackedBCSC, data_fields=["blocks", "idx"], meta_fields=["kb", "joint"])


def mark_joint(p_gate: PackedBCSC, p_up: PackedBCSC
               ) -> tuple[PackedBCSC, PackedBCSC]:
    """Verify (on concrete arrays) that two fused-GLU operands share one
    idx table and, if so, mark both ``joint`` — enabling the single-X
    fast path of ``kernels.fused_glu``. No-op when the structures differ."""
    import numpy as np
    ig, iu = jax.device_get(p_gate.idx), jax.device_get(p_up.idx)
    if ig.shape == iu.shape and bool(np.array_equal(ig, iu)):
        return (dataclasses.replace(p_gate, joint=True),
                dataclasses.replace(p_up, joint=True))
    return p_gate, p_up


def max_nnz_per_col(block_mask: jax.Array) -> int:
    """Static upper bound used to size the pack (requires concrete mask)."""
    counts = jnp.asarray(block_mask).sum(axis=-2)
    return int(counts.max())


def pack(w: jax.Array, block_mask: jax.Array, b_in: int, b_out: int,
         nnz: int | None = None) -> PackedBCSC:
    """Pack masked weight into balanced BCSC.

    w: (K, N); block_mask: (Kb, Nb) bool. ``nnz`` defaults to the max
    per-column count (must be >= it). Leading batch dims are supported
    via vmap by callers; this function handles a single matrix.
    """
    k, n = w.shape
    kb, nb = k // b_in, n // b_out
    assert block_mask.shape == (kb, nb)
    if nnz is None:
        nnz = max_nnz_per_col(block_mask)
    # order rows of each column: kept blocks first (stable), then padding
    keyed = jnp.where(block_mask, 0, 1)                    # kept -> 0
    order = jnp.argsort(keyed, axis=0, stable=True)        # (Kb, Nb)
    sel = order[:nnz].T.astype(jnp.int32)                  # (Nb, nnz)
    valid = jnp.take_along_axis(block_mask.T, sel, axis=1) # (Nb, nnz)
    idx = jnp.where(valid, sel, 0)
    wb = w.reshape(kb, b_in, nb, b_out).transpose(2, 0, 1, 3)  # (Nb,Kb,bi,bo)
    blocks = jnp.take_along_axis(
        wb, idx[:, :, None, None], axis=1)                 # (Nb,nnz,bi,bo)
    blocks = jnp.where(valid[:, :, None, None], blocks, 0.0).astype(w.dtype)
    return PackedBCSC(blocks=blocks, idx=idx, kb=kb)


def unpack(p: PackedBCSC) -> jax.Array:
    """Packed -> dense (K, N). Padding blocks are zero so scatter-add is
    exact even with duplicate idx 0 entries."""
    nb, nnz, b_in, b_out = p.blocks.shape
    dense_blocks = jnp.zeros((nb, p.kb, b_in, b_out), p.blocks.dtype)
    dense_blocks = dense_blocks.at[
        jnp.arange(nb)[:, None], p.idx].add(p.blocks)
    # (Nb, Kb, bi, bo) -> (K, N)
    return dense_blocks.transpose(1, 2, 0, 3).reshape(
        p.kb * b_in, nb * b_out)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def pack_stacked(w: jax.Array, block_mask: jax.Array, b_in: int, b_out: int,
                 nnz: int) -> PackedBCSC:
    """``pack`` over arbitrary leading dims (layers, experts), one matrix
    at a time (``lax.map``): the pack's transposed copy and gather then
    hold one layer's weight, not the whole stack's."""
    fn = lambda wm: pack(*wm, b_in, b_out, nnz)
    for _ in w.shape[:-2]:
        fn = functools.partial(jax.lax.map, fn)
    return fn((w, block_mask))


def pad_nnz(p: PackedBCSC, nnz: int) -> PackedBCSC:
    """Pad per-column block count with zero blocks (idx 0 — exact, the
    zero values contribute nothing). Used to align two operands of the
    fused kernel."""
    cur = p.idx.shape[-1]
    if cur == nnz:
        return p
    assert nnz > cur, (nnz, cur)
    pad_b = [(0, 0)] * (p.blocks.ndim - 3) + [(0, nnz - cur), (0, 0),
                                              (0, 0)]
    pad_i = [(0, 0)] * (p.idx.ndim - 1) + [(0, nnz - cur)]
    # padding edits the idx table, voiding any joint-structure promise
    return PackedBCSC(blocks=jnp.pad(p.blocks, pad_b),
                      idx=jnp.pad(p.idx, pad_i), kb=p.kb)


def pad_fraction(block_mask, nnz: int | None = None) -> float:
    """Fraction of packed block slots that are zero padding under an
    UNBALANCED mask: columns with fewer kept blocks than the max are
    padded up to ``nnz`` (idx 0, zero values). 0.0 for a balanced mask.
    The padding is numerically exact but inflates ``storage_bytes`` /
    ``memory_report`` — export warns on it and the artifact manifest
    records it (serving/artifact.py)."""
    import numpy as np
    m = np.asarray(jax.device_get(block_mask))
    counts = m.sum(axis=-2)
    if nnz is None:
        nnz = int(counts.max())
    total = nnz * counts.size
    return float((total - counts.sum()) / total) if total else 0.0


def structure_violations(p: PackedBCSC, b_in: int | None = None,
                         b_out: int | None = None,
                         dense_shape: tuple | None = None) -> list[str]:
    """Static structural invariants of a PackedBCSC, checked on host
    arrays; returns human-readable violation strings (empty = sound).
    The artifact layer (serving/artifact.py) maps these onto typed
    errors BEFORE a single token is served:

      * shape consistency between ``blocks`` and ``idx`` (and, when
        given, against the registry's expected block dims and dense
        leaf shape);
      * every ``idx`` entry in ``[0, kb)`` — an out-of-range entry
        makes the BSpMM gather garbage blocks silently;
      * per-column duplicate ``idx`` entries may only carry ZERO blocks
        (the zero-padding convention): a duplicate with data would
        double-count that block-row in the contraction.
    """
    import numpy as np
    out: list[str] = []
    blocks = np.asarray(jax.device_get(p.blocks))
    idx = np.asarray(jax.device_get(p.idx))
    if idx.dtype != np.int32:
        out.append(f"idx dtype {idx.dtype}, expected int32")
    if blocks.ndim != idx.ndim + 2 or blocks.shape[:-2] != idx.shape:
        return out + [f"blocks shape {blocks.shape} inconsistent with "
                      f"idx shape {idx.shape}"]
    if b_in is not None and (p.b_in, p.b_out) != (b_in, b_out):
        out.append(f"block dims ({p.b_in}, {p.b_out}) != configured "
                   f"({b_in}, {b_out})")
    if dense_shape is not None:
        got = blocks.shape[:-4] + p.dense_shape()
        if tuple(got) != tuple(dense_shape):
            out.append(f"dense extent {got} != expected "
                       f"{tuple(dense_shape)}")
    if idx.size and (idx.min() < 0 or idx.max() >= p.kb):
        out.append(f"idx out of range [0, {p.kb}): "
                   f"min {int(idx.min())}, max {int(idx.max())}")
        return out       # duplicate analysis is meaningless past this
    nnz = idx.shape[-1]
    cols_i = idx.reshape(-1, nnz)
    cols_b = blocks.reshape(-1, nnz, p.b_in * p.b_out)
    nz = np.any(cols_b != 0, axis=-1)                    # (C, nnz)
    order = np.argsort(cols_i, axis=1, kind="stable")
    si = np.take_along_axis(cols_i, order, axis=1)
    sz = np.take_along_axis(nz, order, axis=1)
    dup = si[:, 1:] == si[:, :-1]
    bad = dup & sz[:, 1:] & sz[:, :-1]
    if bad.any():
        c = int(np.argwhere(bad.any(axis=1))[0, 0])
        out.append(f"duplicate idx entries with nonzero blocks in "
                   f"{int(bad.any(axis=1).sum())} column(s) "
                   f"(first: flat column {c}) — block-rows would be "
                   "double-counted")
    return out


def storage_bytes(p: PackedBCSC) -> int:
    """HBM bytes of the packed representation (paper Fig. 7 analogue)."""
    return (p.blocks.size * p.blocks.dtype.itemsize
            + p.idx.size * p.idx.dtype.itemsize)

"""Operations and bytes from shapes, for the decoder of
``reference/decoder.py`` (``Dims``). A multiply-add counts 2 FLOPs.

Served (packed) token: every weight matmul at the blocks it keeps, plus
the LM head. Attention's context-dependent products (q k^T and p v,
4 H hd FLOPs per position attended) are left out of ``served_token_flops``:
the engine does not report the context of each token it processes, so
the count is a lower bound by ``attention_context_flops`` per position.

Training token (BLaST's masked-dense step with the straight-through
estimator): the MLP forward and input gradient at the kept blocks, the
MLP weight gradient dense (the STE returns the dense gradient), the
attention projections, its causal context products and the head dense,
three passes each (forward, input gradient, weight gradient), and no
recomputation.
"""
from __future__ import annotations


def attention_projection_flops(d) -> int:
    """q, k, v and o projections of one layer, per token."""
    return 2 * d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)


def attention_context_flops(d) -> int:
    """q k^T and p v of one layer, per (query, key) position pair."""
    return 4 * d.heads * d.head_dim


def mlp_flops_kept(d) -> int:
    """W_gate, W_up and W_down of one layer at their kept blocks."""
    return (2 * 2 * d.nnz_up * d.b_in * d.d_ff
            + 2 * d.nnz_down * d.b_out * d.d_model)


def mlp_flops_dense(d) -> int:
    return 3 * 2 * d.d_model * d.d_ff


def head_flops(d) -> int:
    return 2 * d.d_model * d.vocab


def served_body_flops(d) -> int:
    """One token through the packed layers, without attention's context
    products (the LM head apart: only a token that emits one needs it)."""
    return d.layers * (attention_projection_flops(d) + mlp_flops_kept(d))


def served_token_flops(d) -> int:
    """One decode token: the layers and the head."""
    return served_body_flops(d) + head_flops(d)


def train_token_flops(d, seq: int) -> int:
    """One token of a training step at sequence length ``seq`` (causal:
    (seq + 1) / 2 positions attended on average)."""
    ctx = attention_context_flops(d) * (seq + 1) / 2
    dense = attention_projection_flops(d) + ctx
    per_layer = (3 * dense + 2 * mlp_flops_kept(d) + mlp_flops_dense(d))
    return int(d.layers * per_layer + 3 * head_flops(d))


def packed_weight_bytes(d) -> int:
    """Bytes of the served weights: bf16 leaves, and each sparse matrix
    as bf16 kept blocks plus its int32 block-row index (W_gate and W_up
    each store their own index, as the program packs them)."""
    D, F = d.d_model, d.d_ff
    attn = D * d.head_dim * (2 * d.heads + 2 * d.kv_heads)
    norms = 4 * D
    up_cols, dn_cols = F // d.b_out, D // d.b_in
    mlp_vals = (2 * up_cols * d.nnz_up + dn_cols * d.nnz_down) * d.b_in \
        * d.b_out
    mlp_idx = 2 * up_cols * d.nnz_up + dn_cols * d.nnz_down
    per_layer = 2 * (attn + norms + mlp_vals) + 4 * mlp_idx
    return d.layers * per_layer + 2 * (2 * D * d.vocab + 2 * D)


def kv_bytes_per_position(d) -> int:
    """bf16 K and V of one position, every layer."""
    return 2 * 2 * d.layers * d.kv_heads * d.head_dim


def decode_step_bytes(d, batch: int, read_positions: int) -> int:
    """Least bytes one decode step of ``batch`` lanes moves: the weights
    once, and ``read_positions`` cached positions per lane."""
    return packed_weight_bytes(d) + batch * read_positions \
        * kv_bytes_per_position(d)

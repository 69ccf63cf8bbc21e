"""Weights from a seed, and the plain float32 reference, for a pre-norm
decoder with LayerNorm, rotary attention and a gated (SiLU) MLP whose
three matrices are block-sparse: stablelm-3b's layer equations.

This module imports nothing of the program under test. It makes the
weights in the layout the program serves (bf16 dense leaves, and each
sparse MLP matrix as balanced BCSC ``blocks`` + ``idx`` arrays), layer by
layer from ``(seed, layer)``, so the reference can make any one layer
again after the program's state is gone. The reference then runs
teacher-forced over prompts and their served tokens, one layer at a
time, in float32 at the highest matmul precision, and reports for every
served token how far its logit lies below the reference's best.

``precision="fp8"`` computes the same forward with every matmul operand
rounded to float8 e4m3 (per-tensor scale): the control, one precision
step below the bf16 the configuration states.

Layer equations (positions p = 0..T-1, one sequence):

    h = LN(x; s1, b1);  q, k, v = h Wq, h Wk, h Wv;  rope(q, k, p)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    h = LN(x; s2, b2);  x = x + (silu(h Wg) * (h Wu)) Wd
    logits = LN(x; sf, bf) W_head

Rotary: half-split pairs (i, i + hd/2) over all ``head_dim`` dims with
frequencies theta^(-2i/hd); stablelm-3b-4e1t rotates only the first 25%
of each head (``partial_rotary_factor``), and this repository rotates
all of them: the configuration file lists that departure.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# norm parameters are drawn around their identity (scale 1, bias 0) so
# that a swapped or dropped norm parameter shows in the logits
NORM_JITTER = 0.1
EMBED_STD = 0.02
# the key stream of the non-layer weights, apart from every layer index
GLOBAL_STREAM = 1 << 30


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    b_in: int          # block rows on the d_model side
    b_out: int         # block columns on the d_ff side
    sparsity: float

    @property
    def nnz_up(self) -> int:
        """Kept blocks per block-column of W_gate / W_up (d_model rows)."""
        return keep(self.sparsity, self.d_model // self.b_in)

    @property
    def nnz_down(self) -> int:
        """Kept blocks per block-column of W_down (d_ff rows)."""
        return keep(self.sparsity, self.d_ff // self.b_out)


def keep(sparsity: float, n_blocks: int) -> int:
    """ceil((1 - s) n) kept blocks of n, at least one (balanced BCSC:
    every block-column keeps the same number)."""
    return min(n_blocks, max(1, math.ceil((1.0 - sparsity) * n_blocks
                                          - 1e-9)))


def dims_from_config(conf: dict) -> Dims:
    """``Dims`` from a benchmark configuration file (HF key names)."""
    m, s = conf["model"], conf["sparse_mlp"]
    return Dims(layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
                d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                rope_theta=float(m["rope_theta"]),
                eps=float(m["layer_norm_eps"]), b_in=s["block"][0],
                b_out=s["block"][1], sparsity=float(s["sparsity"]))


# ------------------------------------------------------------- weights
def _normal(key, shape, std):
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)


def _norm_pair(key, d):
    ks, kb = jax.random.split(key)
    scale = 1.0 + NORM_JITTER * jax.random.normal(ks, (d,), jnp.float32)
    bias = NORM_JITTER * jax.random.normal(kb, (d,), jnp.float32)
    return scale.astype(jnp.bfloat16), bias.astype(jnp.bfloat16)


def _balanced_idx(key, nb, kb, nnz):
    """(nb, nnz) int32: for each block-column, ``nnz`` distinct block-rows
    of ``kb``, ascending."""
    order = jnp.argsort(jax.random.uniform(key, (nb, kb)), axis=-1)
    return jnp.sort(order[:, :nnz], axis=-1).astype(jnp.int32)


def _packed(key, idx, nb, b_rows, b_cols, std):
    nnz = idx.shape[-1]
    return _normal(key, (nb, nnz, b_rows, b_cols), std)


def layer_weights(d: Dims, seed_key, layer) -> dict:
    """One layer's served weights: bf16 dense leaves, and W_gate, W_up,
    W_down as (blocks, idx) of balanced BCSC. W_gate and W_up share one
    block structure (joint pruning). Kept blocks are scaled by
    sqrt(kb / nnz), so the sparse MLP keeps the dense one's output scale."""
    k = jax.random.split(jax.random.fold_in(seed_key, layer), 10)
    D, H, KV, hd, F = d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff
    down = 1.0 / math.sqrt(2 * d.layers)
    s1, b1 = _norm_pair(k[0], D)
    s2, b2 = _norm_pair(k[1], D)
    kb_up, nb_up = D // d.b_in, F // d.b_out
    kb_dn, nb_dn = F // d.b_out, D // d.b_in
    idx_up = _balanced_idx(k[6], nb_up, kb_up, d.nnz_up)
    idx_dn = _balanced_idx(k[7], nb_dn, kb_dn, d.nnz_down)
    std_up = math.sqrt(kb_up / d.nnz_up) / math.sqrt(D)
    std_dn = math.sqrt(kb_dn / d.nnz_down) / math.sqrt(F) * down
    return {
        "ln_attn_scale": s1, "ln_attn_bias": b1,
        "wq": _normal(k[2], (D, H, hd), 1 / math.sqrt(D)),
        "wk": _normal(k[3], (D, KV, hd), 1 / math.sqrt(D)),
        "wv": _normal(k[4], (D, KV, hd), 1 / math.sqrt(D)),
        "wo": _normal(k[5], (H, hd, D), down / math.sqrt(H * hd)),
        "ln_mlp_scale": s2, "ln_mlp_bias": b2,
        "gate_blocks": _packed(k[8], idx_up, nb_up, d.b_in, d.b_out,
                               std_up),
        "up_blocks": _packed(k[9], idx_up, nb_up, d.b_in, d.b_out, std_up),
        "up_idx": idx_up,
        "down_blocks": _packed(jax.random.fold_in(k[9], 1), idx_dn, nb_dn,
                               d.b_out, d.b_in, std_dn),
        "down_idx": idx_dn,
    }


def global_weights(d: Dims, seed_key) -> dict:
    k = jax.random.split(jax.random.fold_in(seed_key, GLOBAL_STREAM), 3)
    sf, bf = _norm_pair(k[0], d.d_model)
    return {"embed": _normal(k[1], (d.vocab, d.d_model), EMBED_STD),
            "ln_f_scale": sf, "ln_f_bias": bf,
            "lm_head": _normal(k[2], (d.d_model, d.vocab),
                               1 / math.sqrt(d.d_model))}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def all_weights(d: Dims, key) -> tuple[dict, dict]:
    """(globals, per-layer leaves stacked on a leading layer axis): traced
    as one program by the caller's ``jax.jit``."""
    layers = jax.vmap(lambda l: layer_weights(d, key, l))(
        jnp.arange(d.layers))
    return global_weights(d, key), layers


def unpack(blocks, idx, kb: int) -> jax.Array:
    """Balanced BCSC (nb, nnz, br, bc) + (nb, nnz) -> dense (kb*br, nb*bc)."""
    nb, _, br, bc = blocks.shape
    dense = jnp.zeros((nb, kb, br, bc), blocks.dtype)
    dense = dense.at[jnp.arange(nb)[:, None], idx].set(blocks)
    return dense.transpose(1, 2, 0, 3).reshape(kb * br, nb * bc)


# ----------------------------------------------------------- reference
def _round_fp8(x):
    """Per-tensor scaled float8 e4m3 rounding, back in float32."""
    x = x.astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _operand(precision):
    """How a matmul operand enters the product: float32, or rounded
    through float8 for the control."""
    if precision == "fp8":
        return _round_fp8
    return lambda a: a.astype(jnp.float32)


def _layernorm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * freqs     # (T,1,hd/2)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


Q_BLOCK = 256


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(d: Dims, key, layer, x, precision: str):
    """x: (S, T, D) float32, S sequences right-padded to T (causal: the
    pad never reaches a real row)."""
    w = layer_weights(d, key, layer)
    op = _operand(precision)
    n, t, _ = x.shape
    pos = jnp.arange(t)
    h = op(_layernorm(x, w["ln_attn_scale"], w["ln_attn_bias"], d.eps))
    q = jnp.einsum("ntd,dhk->nthk", h, op(w["wq"]))
    k = jnp.einsum("ntd,dhk->nthk", h, op(w["wk"]))
    v = jnp.einsum("ntd,dhk->nthk", h, op(w["wv"]))
    q = jax.vmap(lambda a: _rope(a, pos, d.rope_theta))(q)
    k = jax.vmap(lambda a: _rope(a, pos, d.rope_theta))(k)
    g = d.heads // d.kv_heads
    k = op(jnp.repeat(k, g, axis=2))
    v = op(jnp.repeat(v, g, axis=2))
    scale = 1.0 / math.sqrt(d.head_dim)

    def attend(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Q_BLOCK, 1)
        s = jnp.einsum("nqhk,nshk->nhqs", op(qb), k) * scale
        qp = start + jnp.arange(Q_BLOCK)
        s = jnp.where(qp[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nhqs,nshk->nqhk", op(p), v)

    o = jax.lax.map(attend, jnp.arange(0, t, Q_BLOCK))   # (T/Qb, n, Qb..)
    o = o.transpose(1, 0, 2, 3, 4).reshape(n, t, d.heads, d.head_dim)
    x = x + jnp.einsum("nthk,hkd->ntd", op(o), op(w["wo"]))
    h = op(_layernorm(x, w["ln_mlp_scale"], w["ln_mlp_bias"], d.eps))
    wg = unpack(w["gate_blocks"], w["up_idx"], d.d_model // d.b_in)
    wu = unpack(w["up_blocks"], w["up_idx"], d.d_model // d.b_in)
    wd = unpack(w["down_blocks"], w["down_idx"], d.d_ff // d.b_out)
    a = jax.nn.silu(h @ op(wg)) * (h @ op(wu))
    return x + op(a) @ op(wd)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(d: Dims, key, tokens):
    return global_weights(d, key)["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head(d: Dims, key, x, rows, tokens, precision: str):
    """At the hidden rows ``rows`` (R, 2) of (sequence, position): the
    best logit minus the logit of ``tokens`` (R,), and the argmax."""
    gw = global_weights(d, key)
    op = _operand(precision)
    h = _layernorm(x[rows[:, 0], rows[:, 1]], gw["ln_f_scale"],
                   gw["ln_f_bias"], d.eps)
    logits = op(h) @ op(gw["lm_head"])
    gap = logits.max(-1) - jnp.take_along_axis(logits, tokens[:, None],
                                               1)[:, 0]
    return gap, logits.argmax(-1).astype(jnp.int32)


def _pad_len(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


def _teacher_forced(requests, pad_to: int):
    """Token rows (S, T) of prompt + served tokens but the last, right-
    padded to at least ``pad_to`` (one program for every sample), and the
    (sequence, position) of each row that chose a served token."""
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32)
            for p, s in requests]
    toks = np.zeros((len(seqs), _pad_len(max(pad_to,
                                             *(s.size for s in seqs)))),
                    np.int32)
    rows = []
    for i, ((p, s), seq) in enumerate(zip(requests, seqs)):
        toks[i, :seq.size] = seq
        rows += [(i, p.size - 1 + j) for j in range(s.size)]
    return toks, np.asarray(rows, np.int32)


def hidden_states(d: Dims, seed: int, toks: np.ndarray,
                  precision: str = "f32") -> jax.Array:
    """Final-layer hidden rows (S, T, D) float32, one layer at a time."""
    key = seed_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(d, key, jnp.asarray(toks))
        for layer in range(d.layers):
            x = _layer(d, key, jnp.int32(layer), x, precision)
    return x


def served_gaps(d: Dims, seed: int, requests, pad_to: int = 0
                ) -> np.ndarray:
    """``requests``: [(prompt, served tokens)]. Teacher-forced over each
    prompt and its served tokens: for every served token, the
    reference's best logit minus that token's logit (0 where the served
    token is the reference's argmax), concatenated over the requests."""
    toks, rows = _teacher_forced(requests, pad_to)
    served = np.concatenate([s for _, s in requests]).astype(np.int32)
    x = hidden_states(d, seed, toks)
    with jax.default_matmul_precision("highest"):
        gap, _ = _head(d, seed_key(seed), x, jnp.asarray(rows),
                       jnp.asarray(served), "f32")
    return np.asarray(gap)


def control_gaps(d: Dims, seed: int, requests, precision: str = "fp8",
                 pad_to: int = 0) -> np.ndarray:
    """The control: at each position of the same prompts and served
    tokens, the token that ``precision`` puts first, and that token's
    gap under the float32 reference."""
    toks, rows = _teacher_forced(requests, pad_to)
    served = jnp.asarray(np.concatenate([s for _, s in requests]),
                         jnp.int32)
    key, rows = seed_key(seed), jnp.asarray(rows)
    with jax.default_matmul_precision("highest"):
        xl = hidden_states(d, seed, toks, precision)
        _, low_first = _head(d, key, xl, rows, served, precision)
        del xl
        x = hidden_states(d, seed, toks)
        gap, _ = _head(d, key, x, rows, low_first, "f32")
    return np.asarray(gap)

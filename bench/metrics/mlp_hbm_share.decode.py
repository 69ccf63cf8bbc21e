"""How close the packed MLP runs to the chip's HBM bandwidth: the bytes
of its weights (bf16 kept blocks and int32 block-row indices of W_gate,
W_up and W_down, every layer: the MLP term of
``flops.packed_weight_bytes``) once per decode step, over its device
time at ``hbm_bytes_per_s``, in percent."""
import scopes


def mlp_weight_bytes(d) -> int:
    up_cols, dn_cols = d.d_ff // d.b_out, d.d_model // d.b_in
    blocks = 2 * up_cols * d.nnz_up + dn_cols * d.nnz_down
    return d.layers * (2 * blocks * d.b_in * d.b_out + 4 * blocks)


def read(ctx):
    ms = scopes.per_decode_step_ms(ctx, lambda s: s == "mlp")
    if not ms:
        return None
    per_step = mlp_weight_bytes(ctx["dims"]) / ctx["chips"]
    return 100.0 * per_step / (ms * 1e-3 * ctx["peaks"]["hbm_bytes_per_s"])

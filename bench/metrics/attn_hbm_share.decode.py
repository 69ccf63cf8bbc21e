"""How close the attention layers run to the chip's HBM bandwidth: the
least bytes they must move in the traced window over their device time
at ``hbm_bytes_per_s``, in percent. Bytes: the cached K and V of every
page the engine counted as read (``pages_read``, pages per processed
token, times ``page_size`` positions of ``flops.kv_bytes_per_position``)
plus the bf16 q, k, v and o projection weights of every layer once per
decode step."""
import flops
import scopes


def attn_weight_bytes(d) -> int:
    return 2 * d.layers * d.d_model * d.head_dim * (2 * d.heads
                                                    + 2 * d.kv_heads)


def read(ctx):
    ms = scopes.per_decode_step_ms(ctx, scopes.is_attn)
    if not ms:
        return None
    c, d = ctx["trace_counters"], ctx["dims"]
    moved = (c["pages_read"] * ctx["engine"]["page_size"]
             * flops.kv_bytes_per_position(d)
             + attn_weight_bytes(d) * c["decode_steps"])
    per_step = moved / c["decode_steps"] / ctx["chips"]
    return 100.0 * per_step / (ms * 1e-3 * ctx["peaks"]["hbm_bytes_per_s"])

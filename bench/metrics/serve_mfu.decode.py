"""Model FLOPs of every token the engine processed in the window, per
second, over the chips' bf16 peak: prompt and decode tokens through the
layers with the MLP at its kept blocks, and the LM head once per emitted
token (``flops.py``; attention's context products are not counted)."""
import flops


def read(ctx):
    c, d = ctx["counters"], ctx["dims"]
    work = ((c["prefill_tokens"] + c["decode_tokens"])
            * flops.served_body_flops(d)
            + c["generated_tokens"] * flops.head_flops(d))
    if work <= 0:
        return None
    return 100.0 * work / ctx["window_s"] / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])

"""Device time per on-device decode step in the attention layers: every
op under the ``attn`` scope or one of its ``attn.*`` parts (projections,
KV write, KV read, scores and softmax, output projection), read from the
traced window's device ops by ``scopes.py`` and normalised as
``decode_step_ms.decode`` is: over the decode steps the engine counted
there, per chip."""
import scopes


def read(ctx):
    return scopes.per_decode_step_ms(ctx, scopes.is_attn)

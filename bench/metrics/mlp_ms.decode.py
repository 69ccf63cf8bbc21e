"""Device time per on-device decode step in the packed block-sparse MLP:
every op under the ``mlp`` scope (``scopes.py``), normalised as
``decode_step_ms.decode`` is."""
import scopes


def read(ctx):
    return scopes.per_decode_step_ms(ctx, lambda s: s == "mlp")

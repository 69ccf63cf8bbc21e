"""Device time per on-device decode step that no scope of the model
claims (``scopes.py``): the layer loop's own slicing and stacking,
whole-array copies, anything outside the model. The guard on the
coverage of the per-layer split; normalised as ``decode_step_ms.decode``
is."""
import scopes


def read(ctx):
    return scopes.per_decode_step_ms(ctx, lambda s: s == scopes.UNSCOPED)

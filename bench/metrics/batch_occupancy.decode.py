"""Live decode lanes per on-device decode step, over ``max_batch``, from
the engine's counters over the whole window: decode slabs count
``slab_k`` steps and mixed steps one, each with the tokens they emitted."""


def read(ctx):
    c = ctx["counters"]
    if not c["decode_steps"]:
        return None
    return 100.0 * c["decode_tokens"] / c["decode_steps"] \
        / ctx["engine"]["max_batch"]

"""A run of the serving driver with the look for a chip skipped (the CPU,
at a tiny size): sound, ``correct`` is true; with a fault planted in the
timed path, ``correct`` comes out false. The faults a one-chip serving
cell can have: a token altered where the engine produces it, and a
decode step that returns its state (the KV cache) unchanged."""
import jax
import jax.numpy as jnp
import pytest

import harness
import tiny

SERVE = harness.load_module("drivers", "serve")
SEED = 2**31 + 4242


def _run(corrupt=None, compiles=None):
    cell = tiny.tiny_cell()
    result, checks = SERVE.run(cell, SEED, 3.0, False, jax.devices()[:1],
                               compiles or harness.CompileCounter(),
                               corrupt=corrupt)
    return result, dict((n, v) for n, v, _ in checks)


def alter_tokens(every: int = 3):
    """Add 1 (mod vocab) to every lane's token on every ``every``-th call
    of the decode slab and of the mixed step: the token is wrong where
    it is produced and is fed back as the next input."""
    def corrupt(eng):
        vocab, calls = eng.cfg.vocab_size, [0]
        slab, mixed = eng._slab, eng._mixed_fn

        def bump(toks):
            calls[0] += 1
            return (toks + (calls[0] % every == 0)) % vocab

        def bad_slab(*a, **k):
            block, state, cache = slab(*a, **k)
            return bump(block), state, cache

        def bad_mixed(*a, **k):
            nxt, faulted, cache = mixed(*a, **k)
            return bump(nxt).astype(jnp.int32), faulted, cache

        eng._slab, eng._mixed_fn = bad_slab, bad_mixed
    return corrupt


def stale_cache():
    """The decode slab returns the KV cache it was given: the keys and
    values of the tokens it decoded are never written."""
    def corrupt(eng):
        slab = eng._slab

        def bad_slab(params, cache, *a, **k):
            block, state, _ = slab(params, cache, *a, **k)
            return block, state, cache

        eng._slab = bad_slab
    return corrupt


def test_sound_run_is_correct():
    compiles = harness.CompileCounter()
    result, checks = _run(compiles=compiles)
    assert result["correct"] is True, checks
    # the warm-up ran every program shape the window reached
    assert compiles.count == 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"output_tok_s", "setup_s"}
    assert result["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", [alter_tokens, stale_cache],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    result, checks = _run(fault())
    assert result["correct"] is False
    assert checks["max_served_logit_gap"] > \
        tiny.tiny_cell().config["correct"]["max_gap_logits"]

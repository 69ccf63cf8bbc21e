"""The served steps carry the model's layer names into the compiled
program: every matmul, gather, scatter, convert and fusion of the paged
decode slab and of the mixed step, in the entry computation and in every
loop body, lies under a named scope of ``obs.trace.SCOPES`` (the names
a device trace attributes its ops by), save the layer loop's own
slicing and stacking, which the benchmark's trace reader counts for
the layer it slices for or stacks from. An op the compiler makes
itself carries no ``op_name``; it counts for the scope of the operand
it was made from, as the trace reader counts it."""
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_cfg
from repro.launch.serve import served_params
from repro.models import registry
from repro.obs.trace import SCOPES
from repro.serving import export
from repro.serving import step as st

B, PAGE, N_PAGES, MAX_LEN, SLAB_K, READ_PAGES, WIDTH = 2, 4, 16, 32, 4, 4, 4
KINDS = {"dot", "gather", "scatter", "convert", "fusion"}
# the layer loop's bookkeeping: slicing the stacked weights and cache,
# stacking its outputs, its counter (op_names right inside a loop body)
LOOP = re.compile(r"while/body(/add|(/closed_call)?"
                  r"(/(dynamic_slice|dynamic_update_slice|squeeze))?)$")
INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\((.*)$")


@pytest.fixture(scope="module")
def model():
    # float32 throughout: the CPU compiler widens bf16 operands with
    # converts of its own, which a TPU does not need
    cfg = tiny_cfg()
    dense, masks = served_params(cfg, seed=0, sparsity=0.5)
    dense = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), dense)
    params = export.pack_params(cfg, dense, masks, dtype=jnp.float32)
    cache = registry.init_paged_cache(cfg, N_PAGES, PAGE,
                                      dtype=jnp.float32)
    return cfg, params, cache


def _slab_hlo(cfg, params, cache):
    i32 = jnp.zeros(B, jnp.int32)
    state = {"pending": i32, "frontier": i32, "offsets": i32,
             "remaining": i32 + 5, "live": jnp.ones(B, bool),
             "poison": jnp.zeros(B, jnp.float32),
             "faulted": jnp.zeros(B, bool),
             "bt": jnp.zeros((B, MAX_LEN // PAGE), jnp.int32)}
    slab = jax.jit(st.make_paged_decode_slab_step(cfg, SLAB_K, MAX_LEN,
                                                  PAGE),
                   static_argnames=("read_pages",))
    return slab.lower(params, cache, state,
                      read_pages=READ_PAGES).compile().as_text()


def _mixed_hlo(cfg, params, cache):
    i32 = jnp.zeros(B, jnp.int32)
    mixed = jax.jit(st.make_mixed_step(cfg),
                    static_argnames=("read_pages",))
    return mixed.lower(
        params, cache, jnp.zeros((B, WIDTH), jnp.int32), i32, i32 + 1,
        i32, jnp.zeros((B, MAX_LEN // PAGE), jnp.int32),
        read_pages=READ_PAGES,
        poison=jnp.zeros(B, jnp.float32)).compile().as_text()


def _computations(text):
    """({computation: [instruction lines]}, entry computation)."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        m = re.match(r"^(ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


def _root_kind(lines):
    for line in lines:
        m = INSTR.match(line)
        if m and line.lstrip().startswith("ROOT"):
            return m.group(2)
    return None


def _scopes_of(comps, lines):
    """[(instruction, kind, scope or None, op_name, root kind of a
    fusion)] of one computation's listed kinds."""
    ins = {}
    for line in lines:
        m = INSTR.match(line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)
            calls = re.search(r"calls=%([\w.\-]+)", line)
            ins[m.group(1)] = (m.group(2), op and op.group(1),
                               re.findall(r"%([\w.\-]+)",
                                          m.group(3).split("),")[0]),
                               calls and _root_kind(
                                   comps[calls.group(1)]))
    memo = {}

    def scope(name):
        if name not in memo:
            memo[name] = None
            if name in ins:
                _, op, operands, _ = ins[name]
                if op:
                    memo[name] = next((p for p in reversed(op.split("/"))
                                       if p in SCOPES), None)
                else:
                    memo[name] = next((s for s in map(scope, operands)
                                       if s is not None), None)
        return memo[name]

    return [(n, k, scope(n), op, root) for n, (k, op, _, root)
            in ins.items() if k in KINDS]


@pytest.mark.parametrize("step", ["paged_decode_slab", "mixed_step"])
def test_every_op_under_a_scope(model, step):
    text = (_slab_hlo if step == "paged_decode_slab"
            else _mixed_hlo)(*model)
    comps, entry = _computations(text)
    bodies = {entry} | {b for lines in comps.values() for line in lines
                        for b in re.findall(r"body=%([\w.\-]+)", line)}
    assert len(bodies) >= 2           # the entry and the layer loop
    found = set()
    for body in bodies:
        for name, kind, scope, op, root in _scopes_of(comps, comps[body]):
            if scope is not None:
                found.add(scope)
                continue
            # unscoped: only the loop's own bookkeeping, never a
            # matmul, gather, scatter or convert of the model
            assert kind == "fusion", (step, name, kind, op)
            assert (LOOP.search(op) if op else root == "broadcast"), \
                (step, name, op, root)
    assert found == set(SCOPES), set(SCOPES) ^ found

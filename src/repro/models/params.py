"""Parameter declaration system: one source of truth per model for
(shape, dtype, init, logical sharding axes).

From a ``ParamSpec`` tree we derive, without duplication:
  * real initialization (``init_params``),
  * allocation-free abstract params for the dry-run (``abstract_params``),
  * ``PartitionSpec`` trees via the logical-axis rules in
    ``distributed/sharding.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp

Initializer = Callable[[jax.Array, tuple, jnp.dtype], jax.Array]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]          # logical axis names per dim
    init: str = "normal"                  # normal|zeros|ones|embed
    scale: float = 1.0                    # fan-in scaling multiplier
    dtype: str = "float32"
    # contracted input size of a "normal" matrix; 0 = the last-but-one
    # dim. Projections whose input spans several dims (attention's
    # (d, heads, head_dim) wq and (heads, head_dim, d) wo) must set it.
    fan_in: int = 0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(rng: jax.Array, spec: ParamSpec) -> jax.Array:
    dt = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    if spec.init == "embed":
        return (jax.random.normal(rng, spec.shape, jnp.float32)
                * 0.02 * spec.scale).astype(dt)
    # fan-in scaled normal (last-but-one dim is fan-in for matrices)
    fan_in = spec.fan_in or (spec.shape[-2] if len(spec.shape) >= 2
                             else spec.shape[-1])
    std = spec.scale / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(rng, spec.shape, jnp.float32) * std
            ).astype(dt)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_leaf_as(rng: jax.Array, spec: ParamSpec, dtype) -> jax.Array:
    """``_init_leaf`` cast to ``dtype`` inside one program, so the
    float32 draw is never held as an array of its own."""
    return _init_leaf(rng, spec).astype(dtype)


def init_params(specs: dict, rng: jax.Array, dtype=None) -> dict:
    """Materialize a (nested) ParamSpec tree into arrays. With ``dtype``
    every float32 leaf is made in that dtype, leaf by leaf (serving: the
    cast float32 tree, up to one rounding where XLA fuses the scale and
    the cast, without ever holding it)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    rngs = jax.random.split(rng, len(leaves))
    vals = [_init_leaf_as(r, s, jnp.dtype(dtype))
            if dtype is not None and jnp.dtype(s.dtype) == jnp.float32
            else _init_leaf(r, s) for r, s in zip(rngs, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(specs: dict) -> dict:
    """ShapeDtypeStruct tree — no allocation (dry-run path)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def axes_tree(specs: dict) -> dict:
    """Logical-axes tree parallel to the params tree."""
    return jax.tree_util.tree_map(
        lambda s: s.axes, specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))

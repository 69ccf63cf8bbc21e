"""Mesh builders. Every mesh of the program goes through ``make_mesh``,
which gives each axis the ``Auto`` type: the model code places
activations with ``with_sharding_constraint`` (``distributed/context.py``),
which only ``Auto`` axes accept, and ``jax.make_mesh`` defaults to
``Explicit`` axes.

The builders are FUNCTIONS so importing this module never touches jax
device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.configs.base import with_blast


def make_mesh(shape, axes, devices=None) -> Mesh:
    """A mesh of ``shape`` over ``axes`` (all ``Auto``), on ``devices``
    when given, else on every device."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The 16x16 pod (or 2x16x16 multi-pod) the dry-run compiles for."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Every device there is on the model axis of a (data=1, model=n)
    mesh: the weights and optimizer state then shard across all of them,
    which is what lets a model that does not fit one chip train at
    all."""
    return make_mesh((1, len(jax.devices())), ("data", "model"))


def shard_blocks(cfg, mesh):
    """``cfg`` with its BLaST blocks derived for the shard one device of
    ``mesh`` holds: the ``model`` axis splits d_ff that many ways
    (``configs.with_blast``'s ``tp``). A block grid whose columns do not
    split evenly over that axis makes GSPMD gather every MLP weight to
    apply the masks: stablelm-3b's (128, 128) grid has 54 columns, and
    its train step on a (1, 4) v5e mesh then needs 17.97 GB per chip,
    against 15.88 GB with the 4-way shard's (128, 64) blocks. A config
    without BLaST is returned as it is."""
    if not cfg.blast.enabled:
        return cfg
    return with_blast(cfg, tp=mesh.shape["model"])

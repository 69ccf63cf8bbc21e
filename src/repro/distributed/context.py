"""DistContext: the one object threaded through model forwards that
knows the mesh and axis conventions. Keeps models mesh-agnostic (None =
single device, e.g. smoke tests)."""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class DistContext:
    mesh: Mesh | None = None
    model_axis: str = "model"
    inside_shard_map: bool = False
    sp: bool = True          # sequence-parallel residual stream
    # True inside a partial-manual shard_map over the data axes:
    # sharding constraints may then reference only the model axis
    manual_data: bool = False

    @property
    def batch_axes(self) -> tuple[str, ...]:
        if self.mesh is None:
            return ()
        return tuple(a for a in ("pod", "data")
                     if a in self.mesh.axis_names)

    def batch_pspec(self, ndim: int) -> P:
        ax = self.batch_axes
        first = ax if len(ax) > 1 else (ax[0] if ax else None)
        return P(*([first] + [None] * (ndim - 1)))

    def enter_shard_map(self) -> "DistContext":
        return dataclasses.replace(self, inside_shard_map=True)

    def _model_size(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return sizes.get(self.model_axis, 1)

    def constrain_seq(self, x):
        """Sequence-parallel residual stream: (B,S,D) -> S sharded over
        the model axis (Megatron-SP; bounds the per-layer saved residual
        to 1/TP — DESIGN.md §4)."""
        if self.mesh is None or self.inside_shard_map or x.ndim != 3 \
                or not self.sp:
            return x
        if x.shape[1] % self._model_size() != 0:
            return x
        from jax.sharding import NamedSharding
        ax = () if self.manual_data else self.batch_axes
        first = ax if len(ax) > 1 else (ax[0] if ax else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(first, self.model_axis, None)))

    def constrain_logits(self, x):
        """Vocab-parallel logits: (B,S,V) -> V sharded over model (the
        f32 logits of a 150k-vocab LM never materialise unsharded)."""
        if self.mesh is None or self.inside_shard_map or x.ndim != 3:
            return x
        if x.shape[-1] % self._model_size() != 0:
            return x
        from jax.sharding import NamedSharding
        ax = () if self.manual_data else self.batch_axes
        first = ax if len(ax) > 1 else (ax[0] if ax else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(first, None, self.model_axis)))

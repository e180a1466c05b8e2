"""ParallelCtx: the runtime handle models use to stay mesh-aware.

Carries the mesh + axis-name conventions and provides activation sharding
constraints (sequence-parallel residual stream). ``ctx=None`` everywhere
means single-device execution (CPU smoke tests).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

@dataclass(frozen=True)
class ParallelCtx:
    mesh: Mesh
    batch_axes: Tuple[str, ...] = ("data",)  # axes sharding the batch dim
    model_axis: str = "model"
    seq_shard: bool = True  # sequence-parallel residual stream between blocks
    expert_parallel: bool = True

    @property
    def n_model(self) -> int:
        return self.mesh.shape[self.model_axis]

    @property
    def n_batch(self) -> int:
        n = 1
        for ax in self.batch_axes:
            n *= self.mesh.shape[ax]
        return n

    def activation_spec(self, x: jax.Array) -> Optional[P]:
        """Residual-stream spec for (B, S, d) activations."""
        if x.ndim != 3:
            return None
        B, S, _ = x.shape
        batch = self.batch_axes if B % self.n_batch == 0 and B >= self.n_batch else None
        seq = (
            self.model_axis
            if self.seq_shard and S % self.n_model == 0 and S >= self.n_model
            else None
        )
        return P(batch, seq, None)

    def constrain_activations(self, x: jax.Array) -> jax.Array:
        spec = self.activation_spec(x)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def batch_spec(self, ndim: int = 2) -> P:
        return P(self.batch_axes, *([None] * (ndim - 1)))

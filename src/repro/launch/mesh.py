"""Mesh builders: the one place meshes are made.

FUNCTIONS, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and the dry-run
must set XLA_FLAGS before that happens).

Every axis is ``AxisType.Auto``. ``jax.make_mesh`` defaults to ``Explicit``
axes, under which ``with_sharding_constraint`` (``ParallelCtx``) is refused
and ``jit`` asks for ``jax.set_mesh``; the step builders shard through GSPMD
propagation and need ``Auto``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """(data, model) mesh over every device this process sees."""
    n = len(jax.devices())
    if n % model:
        raise ValueError(f"{n} devices do not split into model={model}")
    return make_mesh((n // model, model), ("data", "model"))


def batch_axes_of(mesh) -> tuple:
    return tuple(ax for ax in mesh.axis_names if ax in ("pod", "data"))

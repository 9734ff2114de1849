"""Mesh construction.  Functions only — importing this module never touches
jax device state (spec requirement)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh whose axes are all Auto: shardings come from
    `with_sharding_constraint` and the partitioner, which Explicit axes
    (the `jax.make_mesh` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips for the multi-pod run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_node_mesh(n_chips: int):
    """Per-backend-node mesh (TP within one heterogeneous serving node)."""
    return _auto_mesh((n_chips,), ("model",))


def make_host_mesh():
    """Single-device mesh for CPU smoke tests / tiny serving replicas."""
    return _auto_mesh((1,), ("model",))

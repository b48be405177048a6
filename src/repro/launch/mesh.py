"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before the first jax call; tests and
benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips ("data","model").
    Multi-pod: 2 pods x 256 = 512 chips ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small host-device meshes). Axes are
    Auto: the sharding rules constrain with ``with_sharding_constraint``,
    which Explicit axes (``jax.make_mesh``'s default) refuse."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))

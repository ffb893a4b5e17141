"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches JAX device
state (the dry-run sets XLA_FLAGS before any jax import; tests see one
device). Every axis is ``AxisType.Auto``: sharding follows the
in/out shardings and the ``shard`` annotations, not explicit types.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 v5e pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_for(n_devices: int, model_axis: int = 2):
    """Small host meshes for tests/examples (e.g. 8 = 4×2)."""
    data = n_devices // model_axis
    return jax.make_mesh((data, model_axis), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

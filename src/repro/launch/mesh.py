"""Production meshes. Importing this module never touches jax device state."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: sharding follows the logical-axis
    rules' constraints (models/sharding.py), not explicit-axis typing."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CI-scale dry-run tests (host device count permitting)."""
    return make_mesh(shape, axes)


def make_choice_mesh(choice):
    """Mesh for an arbitrary MeshChoice (Swan exploration)."""
    return make_mesh(choice.mesh_shape, choice.axis_names)

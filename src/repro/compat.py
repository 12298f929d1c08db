"""Auto-typed meshes.

``jax.make_mesh`` and ``jax.sharding.Mesh`` give every axis
``AxisType.Explicit`` unless told otherwise.  The sharding rules here
(``repro.sharding``) place arrays through ``NamedSharding`` and
``with_sharding_constraint`` and want ``Auto`` axes, so every mesh the
program builds comes from these two helpers.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType

__all__ = ["make_mesh", "mesh_from_devices"]


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with an ``Auto`` type on every axis."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def mesh_from_devices(device_array, axis_names):
    """``jax.sharding.Mesh`` over an explicit device array, Auto-typed."""
    return jax.sharding.Mesh(np.asarray(device_array), axis_names,
                             axis_types=(AxisType.Auto,) * len(axis_names))

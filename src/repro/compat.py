"""Mesh helpers shared by every mesh construction and mesh-context entry.

Meshes are built with explicit ``Auto`` axis types and entered with
``jax.set_mesh``; keeping both here gives the code base one place that
names the mesh API.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis typed ``Auto``."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


use_mesh = jax.set_mesh

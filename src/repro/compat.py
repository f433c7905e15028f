"""Mesh construction for the installed jax (0.9.0).

``make_mesh`` builds Auto-sharded meshes (``jax.make_mesh`` defaults to
Explicit axes otherwise).
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh"]


def make_mesh(axis_shapes, axis_names, devices=None):
    """``jax.make_mesh`` with Auto axis types on every axis."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(
        tuple(axis_shapes),
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices,
    )


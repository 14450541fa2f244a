"""Mesh construction and activation on the installed jax mesh API.

``jax.make_mesh(..., axis_types=...)`` builds meshes, ``jax.set_mesh``
activates them, ``jax.sharding.get_abstract_mesh`` reports the context mesh
while tracing and ``jax.sharding.get_mesh`` the concrete one with devices
attached.  All mesh touch-points go through this module.
"""

from __future__ import annotations

import jax

__all__ = ["make_auto_mesh", "enter_mesh", "current_mesh_axis_names",
           "current_mesh"]


def make_auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types on every axis."""
    auto = jax.sharding.AxisType.Auto
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(auto,) * len(axes))


def enter_mesh(mesh: jax.sharding.Mesh) -> None:
    """Make ``mesh`` the context mesh for the rest of the process
    (callers are process-scoped scripts: dry-run cells and subprocess
    lowering tests)."""
    jax.set_mesh(mesh)


def current_mesh_axis_names() -> tuple[str, ...]:
    """Axis names of the active context mesh (empty off-mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    return () if mesh.empty else tuple(mesh.axis_names)


def current_mesh() -> jax.sharding.Mesh | None:
    """The active *concrete* context mesh, or None off-mesh.

    Unlike :func:`current_mesh_axis_names` this returns a mesh with real
    devices attached: the sharded offload backend scatters work onto them.
    """
    mesh = jax.sharding.get_mesh()
    return None if mesh.empty else mesh

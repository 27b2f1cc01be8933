"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.
"""
from __future__ import annotations

import jax


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """Target deployment mesh.

    single-pod: (16, 16) = 256 chips, axes ("data", "model")
    multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model")

    The "data" (x "pod") axis enumerates serving instances / DP+FSDP shards;
    "model" is the tensor-parallel axis inside an instance.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape, axes):
    """Generic helper for tests / small host-device meshes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_auto(len(axes)))


def mesh_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def batch_axes(mesh) -> tuple:
    """Axes the global batch shards over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))

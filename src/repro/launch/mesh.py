"""Production mesh construction (defined as functions so importing this
module never touches jax device state)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple, axes: tuple, devices=None):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model). Multi-pod: 2 pods of
    256 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Auto-typed mesh helper (tests / small runs)."""
    return _mesh(shape, axes)


def mesh_on(devices) -> jax.sharding.Mesh:
    """The serving mesh over exactly ``devices``: ("data", "model") =
    (1, len(devices)), so doc shards map one per device."""
    devices = list(devices)
    return _mesh((1, len(devices)), ("data", "model"), devices=devices)


def dp_axes_of(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_size(mesh) -> int:
    return mesh.shape.get("model", 1)


def dp_size(mesh) -> int:
    s = 1
    for a in dp_axes_of(mesh):
        s *= mesh.shape[a]
    return s

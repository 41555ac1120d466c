"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (module import never touches jax
device state) returning the target topology:

* single-pod: ``(16, 16)`` over ``("data", "model")``  — 256 chips,
* multi-pod:  ``(2, 16, 16)`` over ``("pod", "data", "model")`` — 512 chips.

Smaller test meshes come from :func:`make_mesh`.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)

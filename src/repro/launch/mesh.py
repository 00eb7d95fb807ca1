"""Mesh builders over ``jax.make_mesh``.

Functions, not module-level constants: importing this module never
touches jax device state.  Every mesh has ``AxisType.Auto`` axes: the
model zoo places arrays through the logical sharding rules
(``distributed.logical``) and lets GSPMD propagate the rest.  Single
pod: 16×16 = 256 chips (data, model); multi-pod: 2×16×16 = 512 chips
with an explicit "pod" axis that the default sharding rules fold into
data parallelism (DESIGN.md §3).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes, over ``devices`` if given."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


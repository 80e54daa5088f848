"""Production meshes.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Shapes per the brief: single-pod (16, 16) = 256 chips,
multi-pod (2, 16, 16) = 512 chips with a leading "pod" axis.

FV3 uses its own topology-locked mesh: ("tile", "y", "x") with 6 tiles —
multi-pod expressed as a leading ensemble axis ("ens"), the production
multi-pod workload for NWP (ensemble forecasting).

Every axis is ``Auto``: ``jax.make_mesh`` defaults to explicit axes, whose
sharding-in-types rules the dycore's shard_map step does not follow.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_fv3_mesh(*, layout: tuple[int, int] = (8, 8), ensemble: int = 1):
    """Cubed-sphere mesh: 6 × py × px ranks (+ optional ensemble axis)."""
    py, px = layout
    if ensemble > 1:
        return _auto_mesh((ensemble, 6, py, px), ("ens", "tile", "y", "x"))
    return _auto_mesh((6, py, px), ("tile", "y", "x"))

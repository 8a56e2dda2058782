"""Production mesh construction (MULTI-POD DRY-RUN spec).

A FUNCTION, not a module-level constant, so importing this module never touches
jax device state.  Single pod: 16×16 = 256 chips, ("data","model").  Multi-pod:
2×16×16 = 512 chips, ("pod","data","model") — the leading "pod" axis maps to
the slower inter-pod links (DCN/ICI-over-optical); batch is sharded over
("pod","data") and cross-pod traffic is gradient reduction only (optionally
int8-compressed, optim/compression.py).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "POD_SHAPE", "MULTI_POD_SHAPE"]

POD_SHAPE = (16, 16)
MULTI_POD_SHAPE = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

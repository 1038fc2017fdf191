"""Production mesh factory.

Function (not module constant) so importing never touches jax device
state. Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod: leading pure-DP "pod" axis across DCI -> 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """1-device mesh with the same axis names (tests / smoke runs)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

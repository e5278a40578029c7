"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  Single pod: v5e-256 as (16, 16) with axes
(data, model).  Multi-pod: (2, 16, 16) with a leading ``pod`` axis; per the
paper's design the ``pod`` axis carries only inter-op (pipeline) traffic.

Every axis is ``AxisType.Auto`` (``jax.make_mesh`` defaults to Explicit):
the model code shards through GSPMD constraints (``shard_act``), not through
sharding-typed arrays.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (e.g. (2,2,2) on 8 host devices, or one host's chips)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)

"""The mesh and shard_map surface the repo builds on (jax >= 0.9).

`shard_map` and `make_mesh` are jax's own; `auto_axis_types` spells the
fully-automatic axis types every mesh here uses.
"""
from __future__ import annotations

import jax
from jax import make_mesh, shard_map

__all__ = ["auto_axis_types", "make_mesh", "shard_map"]


def auto_axis_types(n: int) -> tuple:
    """(AxisType.Auto,) * n — the axis types of every mesh in the repo."""
    return (jax.sharding.AxisType.Auto,) * n

"""One import point for the JAX API surface the repo uses (JAX 0.9).

Use sites import these names from here, so a future JAX rename is one
edit.  Each is the installed JAX's own function; only ``make_mesh``
adds a default (Auto axis types) and ``tpu_compiler_params`` a short
name.
"""
from __future__ import annotations

from typing import Sequence

import jax

__all__ = [
    "shard_map",
    "make_mesh",
    "set_mesh",
    "axis_size",
    "pcast_varying",
    "tpu_compiler_params",
]

axis_size = jax.lax.axis_size
shard_map = jax.shard_map
set_mesh = jax.set_mesh
pcast_varying = jax.lax.pcast


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names))


def tpu_compiler_params(**kwargs):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)

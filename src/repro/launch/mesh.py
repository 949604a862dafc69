"""Mesh builders.

Functions (not module constants) so importing never touches jax device state.

Every mesh the program builds goes through :func:`make_mesh`, which gives
its axes the *Auto* type: the code shards through in/out shardings and
lets the partitioner propagate them, so a gather from a sharded array
(an embedding lookup, a per-shard row of a stacked slab) needs no
``out_sharding``. ``jax.make_mesh`` defaults to *Explicit* axes, which
refuse such gathers.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (see module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))


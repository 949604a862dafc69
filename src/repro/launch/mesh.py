"""Production mesh builders.

Functions (not module constants) so importing never touches jax device state.
Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is an outer data-parallel axis whose collectives cross DCN.

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


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1):
    """Small mesh over the locally available devices (tests/examples)."""
    n = len(jax.devices())
    data = n // model_axis
    return make_mesh((data, model_axis), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)

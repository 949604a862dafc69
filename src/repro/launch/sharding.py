"""Logical-axis -> mesh-axis mapping (partition rules).

Model code declares per-dimension LOGICAL axes ("embed", "q_heads", "mlp",
"vocab", "expert", "inner", ...). This module maps them to physical mesh axes
with divisibility gating: a dimension is sharded on "model" only when its
size divides evenly — otherwise it is replicated (XLA padding of uneven
shards is avoided by construction).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

# logical axes that map to the tensor-parallel ("model") mesh axis
_MODEL_AXES = ("q_heads", "kv_heads", "mlp", "vocab", "expert", "inner")


def _is_spec(x):
    return isinstance(x, tuple) and all(isinstance(a, (str, type(None)))
                                        for a in x)


def logical_to_pspec(spec: tuple, shape: tuple, mesh, fsdp: bool = False) -> P:
    """One param's logical spec + shape -> PartitionSpec on this mesh.

    fsdp=True additionally shards the largest remaining divisible dim over
    "data" (ZeRO-3 semantics: GSPMD inserts the per-layer all-gathers).
    """
    msize = mesh.shape["model"]
    axes = []
    used = False  # at most one dim per mesh axis; first eligible wins
    for dim, name in zip(shape, spec):
        if not used and name in _MODEL_AXES and dim % msize == 0:
            axes.append("model")
            used = True
        else:
            axes.append(None)
    if fsdp and "data" in mesh.axis_names:
        dsize = mesh.shape["data"]
        named = list(spec) + [None] * (len(shape) - len(spec))
        # only NAMED dims are fsdp-eligible: the anonymous leading dim of
        # stacked layer params is scanned over and must stay unsharded
        cand = sorted(((d, i) for i, d in enumerate(shape)
                       if axes[i] is None and named[i] is not None
                       and d % dsize == 0 and d >= dsize),
                      reverse=True)
        if cand:
            axes[cand[0][1]] = "data"
    # strip trailing Nones for tidiness
    while axes and axes[-1] is None:
        axes.pop()
    return P(*axes)


def param_shardings(spec_tree, shape_tree, mesh, fsdp: bool = False):
    """NamedSharding tree for params (and, reused, optimizer moments)."""
    def one(spec, shaped):
        return NamedSharding(mesh, logical_to_pspec(tuple(spec), shaped.shape,
                                                    mesh, fsdp))
    return jax.tree.map(one, spec_tree, shape_tree, is_leaf=_is_spec)


def batch_pspec(mesh) -> P:
    """Global-batch sharding over (pod?, data)."""
    if "pod" in mesh.axis_names:
        return P(("pod", "data"))
    return P("data")


def replicated(mesh):
    return NamedSharding(mesh, P())


"""Sharded MultiSketch construction (paper §3.3 composability, on a mesh).

The distributed build of a multi-objective summary over data sharded along
a mesh axis is three steps, all device-side:

  1. shard_map local build — every device runs the one-shot selection over
     ITS shard only (O(n/m) work, no communication);
  2. all_gather of the fixed-capacity wire slabs — the ONLY collective,
     |F|-independent byte count c * (slots) per device pair;
  3. one batched re-selection over the m * c gathered slots
     (multisketch_merge_stacked) — exact by the threshold-closure merge
     invariant (core.multi_sketch), so the result is bit-identical to a
     one-shot build over the full data.

Because step 3 runs replicated on every device, the merged sketch comes
back un-sharded and immediately queryable. The serving tier
(launch.query.SegmentQueryEngine) instead keeps step 3 LAZY:
``sharded_multisketch_shards`` stops after step 1 and returns the stacked
per-shard slabs, which the engine holds resident and merges on demand
(memoized per absorb epoch) — the eager replicated re-selection here is
for build-then-broadcast pipelines, the engine for query serving.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.multi_sketch import (MultiSketch, MultiSketchSpec,
                                     multisketch_build,
                                     multisketch_finalize,
                                     multisketch_merge_stacked)


def sharded_multisketch(spec: MultiSketchSpec, mesh, keys, weights,
                        active=None, axis: str = "data") -> MultiSketch:
    """Build S^(F) ∪ Z of globally-sharded data: local build -> all_gather
    slabs -> one re-selection. Exact (same member set/probs/taus as a
    one-shot build over the unsharded data).

    keys/weights/active are global arrays sharded (or shardable) along
    ``axis``; their length must be a multiple of the axis size. Returns a
    replicated MultiSketch.
    """
    keys = jnp.asarray(keys, jnp.int32)
    weights = jnp.asarray(weights, jnp.float32)
    active = (jnp.ones(keys.shape, bool) if active is None
              else jnp.asarray(active, bool))

    def local(k, w, a):
        sk = multisketch_build(spec, k, w, a, use_kernels=False)
        gathered = jax.tree.map(
            lambda x: jax.lax.all_gather(x, axis), sk)
        return multisketch_merge_stacked(spec, MultiSketch(*gathered),
                                         use_kernels=False)

    # fully manual (all axes): the off-``axis`` axes just see replicated data
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=jax.tree.map(lambda _: P(), multisketch_shape(spec)),
        check_vma=False)
    # re-finalize at host level: the in-trace finalize inlined into the
    # shard_map program, and canonical prob bits require the one
    # fixed-shape finalizer program (core.multi_sketch)
    return multisketch_finalize(jax.jit(fn)(keys, weights, active),
                                spec=spec)


def sharded_multisketch_shards(spec: MultiSketchSpec, mesh, keys, weights,
                               active=None, axis: str = "data"
                               ) -> MultiSketch:
    """Step 1 only: per-device local builds, returned as STACKED slabs
    (leaves [m, ...], one row per device along ``axis``) with no gather and
    no re-selection — the resident state of the lazy serving tier
    (launch.query.SegmentQueryEngine.load_stacked). Exactness of any later
    merge over these rows is the threshold-closure invariant; merging all
    m rows reproduces ``sharded_multisketch`` bit-identically.
    """
    keys = jnp.asarray(keys, jnp.int32)
    weights = jnp.asarray(weights, jnp.float32)
    active = (jnp.ones(keys.shape, bool) if active is None
              else jnp.asarray(active, bool))

    def local(k, w, a):
        sk = multisketch_build(spec, k, w, a, use_kernels=False)
        return jax.tree.map(lambda x: x[None], sk)  # [1, ...] rows to stack

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=jax.tree.map(lambda _: P(axis), multisketch_shape(spec)),
        check_vma=False)
    return jax.jit(fn)(keys, weights, active)


def merge_host_slabs(spec: MultiSketchSpec, slabs,
                     use_kernels: Optional[bool] = None) -> MultiSketch:
    """Step 3 for HOST-level slabs: one stacked re-selection over a list
    of already-merged per-host slabs — the cross-host read path of the
    scale-out pool (launch.pool.ShardedEnginePool).

    Exactness is the same threshold-closure argument as the mesh build
    above: each host's merged slab is S^(F) ∪ Z of that host's shard
    union, and one re-selection over the stacked host slabs recovers the
    sample of the GLOBAL union (paper §3.3 — composability is transitive
    through intermediate merges). Bit-identity with a single-host engine
    over the same data holds because this routes through the engine's own
    fold family (``launch.query._full_remerge``: the stacked delta fold
    into a fresh empty slab + the canonical fixed-shape finalizer), so no
    separately-jitted program can disagree in the last ulp of ``probs``.
    """
    slabs = list(slabs)
    if not slabs:
        raise ValueError("merge_host_slabs needs >= 1 host slab")
    if len(slabs) == 1:
        return slabs[0]
    from repro.launch.query import _full_remerge
    return _full_remerge(slabs, spec=spec, use_kernels=use_kernels)


def multisketch_shape(spec: MultiSketchSpec) -> MultiSketch:
    """ShapeDtypeStruct pytree of a sketch (for out_specs/eval_shape)."""
    c, nf = spec.cap, spec.nf
    f = jax.ShapeDtypeStruct
    return MultiSketch(
        keys=f((c,), jnp.int32), weights=f((c,), jnp.float32),
        probs=f((c,), jnp.float32), seeds=f((nf, c), jnp.float32),
        member=f((c,), bool), aux=f((c,), bool), valid=f((c,), bool),
        taus=f((nf,), jnp.float32))

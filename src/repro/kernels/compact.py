"""Compaction/merge kernel for fixed-capacity MultiSketch wire slabs.

Compacting S^(F) ∪ Z into ``capacity`` slots is a selection problem in
disguise: assign every entry a retention PRIORITY (members first, then aux,
each ordered by weight descending; dropped/duplicate/invalid entries +inf)
and take the ``capacity`` smallest priorities. The take ranks equal
priorities by position, and the inputs come in ascending key order, so the
slot order is members by weight desc, then aux by weight desc, equal
priorities by key: the order of ``core.multi_sketch._compact``'s XLA take.
That reuses the batched block-select kernel for the take, so the only new
device code is the fused priority pass implemented here:

  one VMEM-resident sweep computes, per entry,
    dup    — key equals the previous key (inputs are key-sorted with
             weight-descending tiebreak, so the FIRST occurrence carries the
             max weight: the paper's w_x = max rule for merged data sets)
    pri    — member: w/(1+w) mapped to (0,1]   via 1/(1+w)
             aux:    2 + 1/(1+w) in (2,3]
             else    +inf
  i.e. one HBM read of (keys, prev_keys, member, keep, w) and one write of
  the f32 priority row — the merge path's only elementwise full pass.

``compact_take`` chains this with ``bottomk_select`` (Pallas block-select +
one top_k merge, or one top_k where the capacity is past the block width:
``blockselect.select_plan``) to emit gather indices for the compacted
slab.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels._util import pad_tail, resolve_interpret, round_up
from repro.kernels.blockselect import bottomk_select

LANES = 128
BLOCK_ROWS = 64   # rows of 128 lanes per grid step (8192 entries)
_INF = np.float32(np.inf)


def _priority_kernel(keys_ref, prev_ref, member_ref, keep_ref, w_ref,
                     out_ref):
    k = keys_ref[...]
    dup = (k == prev_ref[...]) | (k < 0)
    keep = (keep_ref[...] != 0) & ~dup
    member = member_ref[...] != 0
    w = jnp.maximum(w_ref[...].astype(jnp.float32), 0.0)
    inv = 1.0 / (1.0 + w)                       # weight desc -> pri asc
    pri = jnp.where(member, inv, np.float32(2.0) + inv)
    out_ref[...] = jnp.where(keep, pri, _INF)


@partial(jax.jit, static_argnames=("interpret",))
def retention_priority(sorted_keys, weights, member, keep, interpret=None):
    """Fused dedup + retention-priority pass (one launch).

    Inputs must be sorted by (key asc, weight desc); duplicate keys (all but
    the first, max-weight occurrence) and negative keys (empty slots) get
    priority +inf, as do entries with ``keep`` False. Returns pri [n] f32
    whose ascending order is: members by weight desc, then aux by weight
    desc, then dropped; equal priorities are left to the take, which ranks
    them by position, i.e. by key.
    """
    interpret = resolve_interpret(interpret)
    n = sorted_keys.shape[0]
    # the pass is elementwise: lay the rows out as dense [rows, 128] tiles
    # (full vreg occupancy) and stream BLOCK_ROWS of them per grid step; a
    # delta-slab input of a few slab capacities is ONE step, padded to the
    # (8, 128) tile. Pad rows are sliced off, so sizing never affects the
    # retained bits.
    rows = round_up(-(-max(n, 1) // LANES), 8)
    rb = min(BLOCK_ROWS, rows)
    rows = round_up(rows, rb)
    npad = rows * LANES
    sk = pad_tail(sorted_keys.astype(jnp.int32), npad, -1)
    prev = jnp.concatenate([jnp.full((1,), -2, jnp.int32), sk[:-1]])
    w = pad_tail(weights.astype(jnp.float32), npad, 0.0)
    mem = pad_tail(member.astype(jnp.int32), npad, 0)
    kp = pad_tail(keep.astype(jnp.int32), npad, 0)
    spec = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _priority_kernel,
        grid=(rows // rb,),
        in_specs=[spec] * 5,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        interpret=interpret,
        name="retention_priority",
    )(*(x.reshape(rows, LANES) for x in (sk, prev, mem, kp, w)))
    return out.reshape(-1)[:n]


def compact_take(sorted_keys, weights, member, keep, capacity: int,
                 interpret=None):
    """Gather indices compacting retained entries into ``capacity`` slots.

    Returns (take [capacity] int32, taken_valid [capacity] bool): positions
    of the ``capacity`` highest-retention entries (members by weight desc,
    then aux, equal weights by key since the inputs are key-sorted), -1 /
    False on slots past the retained count. Exact via the two-level
    block-select (the capacity smallest priorities, ties by position).
    """
    pri = retention_priority(sorted_keys, weights, member, keep,
                             interpret=interpret)
    n = pri.shape[0]
    if n < capacity + 1:  # block-select needs >= capacity+1 candidates
        pri = pad_tail(pri, capacity + 1, _INF)
    vals, idx, _tau = bottomk_select(pri, capacity, interpret=interpret)
    valid = jnp.isfinite(vals) & (idx >= 0) & (idx < n)
    return jnp.where(valid, idx, -1).astype(jnp.int32), valid

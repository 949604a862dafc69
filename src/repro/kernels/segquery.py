"""Fused segment-query kernel: B predicates x |F| objectives, ONE launch.

Serving answers many segment-sum queries Q^(f, H) against one resident
MultiSketch slab (paper §2-3: a single summary answers every f in F with
per-objective CV guarantees). Evaluated one (f, H) pair at a time, each
query pays a full launch + an O(c) pass over the slab; this kernel fuses a
whole query batch into one VMEM-resident launch:

  per slab block of c_b slots (ONE HBM read of keys/weights/probs/member):
    ht      [c_b]       member ? 1 / p^(F) : 0         (HT weight, Eq. 5)
    contrib [F, c_b]    f_j(w) * ht for every objective (objectives are
                        compile-time (kind, param) pairs, same encoding as
                        kernels.seeds)
    sel     [B, c_b]    the predicate wire table (core.predicates) applied
                        to the block's keys — range / bitmask / hashed-
                        fraction tests, hash computed in-kernel
    out    += contrib @ sel^T                           [F, B] accumulate

The objective axis rides the sublane dimension and the predicate batch the
lane dimension (the MXU/VPU-native layout, like blockselect's batched
rows), so launch count AND grid size are flat in both B and |F| — only the
O(c) slab-bandwidth term plus the O(F B) accumulator remain. B and |F| are
padded to tile multiples (128 / 8) and the result sliced back.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.predicates import FLAG_ON_HASH, PRED_COLS
from repro.kernels._util import pad_tail, resolve_interpret, round_up
from repro.kernels.seeds import _fval, _mix

# slab slots per grid step; a 1D operand longer than one block must tile
# as XLA lays it out on the TPU (1024-element tiles)
BLOCK = 1024
_LANES = 128      # predicate-batch padding quantum
_SUBLANES = 8     # objective-axis padding quantum
_GOLDEN = np.uint32(0x9E3779B9)


def _segquery_kernel(keys_ref, w_ref, p_ref, m_ref, pred_ref, out_ref, *,
                     objectives, nf_pad):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    k = keys_ref[...]                                   # [c_b] int32
    w = w_ref[...].astype(jnp.float32)
    prob = p_ref[...].astype(jnp.float32)
    member = m_ref[...] != 0

    # HT contributions, one row per objective (zero rows pad to nf_pad)
    ht = jnp.where(member, 1.0 / jnp.maximum(prob, 1e-30), 0.0)
    rows = [_fval(kind, param, w) * ht for kind, param in objectives]
    rows += [jnp.zeros_like(ht)] * (nf_pad - len(rows))
    contrib = jnp.stack(rows)                           # [nf_pad, c_b]

    # predicate selection — same semantics as core.predicates.predicate_matrix
    lo = pred_ref[0, :][:, None]                        # [B, 1]
    hi = pred_ref[1, :][:, None]
    mask = pred_ref[2, :][:, None]
    want = pred_ref[3, :][:, None]
    salt = pred_ref[4, :][:, None].astype(jnp.uint32)
    on_hash = (pred_ref[5, :][:, None] & FLAG_ON_HASH) != 0
    ku = k[None, :].astype(jnp.uint32)                  # [1, c_b]
    h = _mix(ku + _GOLDEN + salt)                       # [B, c_b]
    h = _mix(h ^ (salt * np.uint32(0x85EBCA6B) + np.uint32(1)))
    hv = (h >> np.uint32(1)).astype(jnp.int32)          # hash31, in [0, 2^31)
    v = jnp.where(on_hash, hv, k[None, :])
    sel = ((v >= lo) & (v <= hi) & ((v & mask) == want)
           & (k[None, :] >= 0)).astype(jnp.float32)     # [B, c_b]

    out_ref[...] += jax.lax.dot_general(
        contrib, sel, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,            # f32, not bf16
        preferred_element_type=jnp.float32)             # [nf_pad, B]


@partial(jax.jit, static_argnames=("objectives", "interpret"))
def segment_query_slab(keys, weights, probs, member, table, objectives,
                       interpret=None):
    """Batched segment queries over one slab: -> estimates [|F|, B].

    keys/weights/probs/member: the MultiSketch wire slab fields [c];
    table: int32 predicate wire table [B, PRED_COLS] (core.predicates);
    objectives: static tuple of (kind, param) pairs (kernels.seeds encoding).
    ONE pallas launch regardless of B and |F|; the grid runs only over slab
    blocks (c / BLOCK steps, accumulating the [F, B] output in place).
    """
    interpret = resolve_interpret(interpret)
    nf = len(objectives)
    b = table.shape[0]
    if table.shape[1] != PRED_COLS:
        raise ValueError(f"predicate table must be [B, {PRED_COLS}], "
                         f"got {table.shape}")
    c = keys.shape[0]
    cpad = round_up(max(c, 1), BLOCK)
    nf_pad = round_up(nf, _SUBLANES)
    bpad = round_up(b, _LANES)

    k = pad_tail(jnp.asarray(keys, jnp.int32), cpad, -1)
    w = pad_tail(jnp.asarray(weights, jnp.float32), cpad, 0.0)
    p = pad_tail(jnp.asarray(probs, jnp.float32), cpad, 0.0)
    m = pad_tail(jnp.asarray(member).astype(jnp.int32), cpad, 0)
    # predicates ride the lane axis: transpose the table to [PRED_COLS, Bpad]
    t = jnp.asarray(table, jnp.int32)
    t = jnp.pad(t, ((0, bpad - b), (0, 0))).T

    out = pl.pallas_call(
        partial(_segquery_kernel, objectives=tuple(objectives),
                nf_pad=nf_pad),
        grid=(cpad // BLOCK,),
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((PRED_COLS, bpad), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nf_pad, bpad), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((nf_pad, bpad), jnp.float32),
        interpret=interpret,
        name="segment_query",
    )(k, w, p, m, t)
    return out[:nf, :b]

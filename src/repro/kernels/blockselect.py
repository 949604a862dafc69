"""Block-local bottom-k selection kernel (paper §2.2's core primitive).

Bottom-k sampling needs the k smallest f-seeds of n keys. Heaps don't map
to the VPU; the TPU-native plan is two-level selection:
  1. THIS KERNEL: per VMEM block, select the block's k smallest seeds with
     k min+mask rounds (a ``lax.fori_loop`` of pure vector ops, no
     data-dependent control flow), emitting each block's candidates +
     their indices in a row padded to the 128-lane quantum;
  2. host/XLA: one top_k over the n/B * k << n candidates.

The k smallest of the union are always among the per-block k smallest, so
the two-level result is exact. One HBM read of the seeds, k*n/B vector
mins — bandwidth-optimal for k << B.

The kernel is natively BATCHED over objectives: it consumes the [|F|, n]
seed matrix of ``fused_seeds`` directly as (|F|, B) VMEM slabs — the
(|F|, n/B) block decomposition with the |F| axis vectorized into the VPU
sublane dimension (full occupancy at |F| >= 8) instead of serialized into
grid steps. A multi-objective sample therefore costs ONE launch whose
per-step work is the pure O(|F| B) bandwidth term, plus one top_k over
[|F|, nb*k] candidates — not |F| launches + 2|F| full-n scans. The 1D
entry points are views of the batched path with |F| = 1.

Plans (``select_plan``, chosen from the sizes alone): the block plan
needs a block's candidate row — k rounded up to the lane quantum — to be
narrower than the block, so that its output block fits the grid and the
kernel compiles. Past that (k at or beyond the block width, e.g. a
compaction taking a whole slab capacity) one XLA top_k over the row
computes the same answer. The threshold is set for compilation, not
speed: which plan is faster at a given k is not settled here.

Ragged n is auto-padded with +inf seeds (idx -1), which never survive
selection ahead of a finite seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels._util import pad_tail, resolve_interpret, round_up

BLOCK = 2048
_LANES = 128
_INF = np.float32(np.inf)


def _block_width(n: int) -> int:
    """Lanes per block: the streaming BLOCK, or the input rounded to the
    lane quantum when it is smaller (an absorb-time delta fold re-selects
    a few slab capacities, far below a streaming batch)."""
    return min(BLOCK, round_up(max(n, 1), _LANES))


def select_plan(n: int, k: int) -> str:
    """'block' or 'top_k': how the k smallest of n per row are found."""
    return "block" if round_up(k, _LANES) < _block_width(n) else "top_k"


def _blockselect_kernel(seeds_ref, vals_ref, idx_ref, *, k: int, block: int):
    nf, kpad = vals_ref.shape
    base = pl.program_id(0) * block                 # block index along n
    local_idx = jax.lax.broadcasted_iota(jnp.int32, (nf, block), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (nf, kpad), 1)

    def one_round(j, carry):
        s, vals, idx = carry
        m = jnp.min(s, axis=1, keepdims=True)       # [F, 1], all rows at once
        # first position attaining each row's min (iota tiebreak)
        pos = jnp.min(jnp.where(s == m, local_idx, block), axis=1,
                      keepdims=True)
        vals = jnp.where(slot == j, m, vals)
        idx = jnp.where(slot == j, jnp.where(jnp.isfinite(m), base + pos, -1),
                        idx)
        return jnp.where(local_idx == pos, _INF, s), vals, idx

    _, vals, idx = jax.lax.fori_loop(
        0, k, one_round,
        (seeds_ref[...].astype(jnp.float32),
         jnp.full((nf, kpad), _INF, jnp.float32),
         jnp.full((nf, kpad), -1, jnp.int32)))
    vals_ref[...] = vals
    idx_ref[...] = idx


@partial(jax.jit, static_argnames=("k", "interpret"))
def _block_candidates(seeds, k: int, interpret=None):
    """seeds [F, n] -> (vals, idx) [F, nb * kpad]: each block's k smallest
    in ascending order, the row padded to kpad = k rounded up to the lane
    quantum with (+inf, -1)."""
    interpret = resolve_interpret(interpret)
    nf, n = seeds.shape
    b = _block_width(n)
    kpad = round_up(k, _LANES)
    npad = round_up(n, b)
    s = pad_tail(seeds.astype(jnp.float32), npad, _INF)
    nb = npad // b
    return pl.pallas_call(
        partial(_blockselect_kernel, k=k, block=b),
        grid=(nb,),
        in_specs=[pl.BlockSpec((nf, b), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((nf, kpad), lambda i: (0, i)),
                   pl.BlockSpec((nf, kpad), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((nf, nb * kpad), jnp.float32),
                   jax.ShapeDtypeStruct((nf, nb * kpad), jnp.int32)],
        interpret=interpret,
        name="block_select",
    )(s)


@partial(jax.jit, static_argnames=("k", "interpret"))
def batched_block_bottomk(seeds, k: int, interpret=None):
    """seeds [F, n] -> (vals [F, nb*k], idx [F, nb*k]) block-local k smallest.

    One pallas launch for ALL objectives: grid (n/B,), each step selecting
    the k smallest of every objective row of a (F, B) slab simultaneously;
    n is padded to a block multiple with +inf seeds (idx -1).
    """
    nf = seeds.shape[0]
    kpad = round_up(k, _LANES)
    vals, idx = _block_candidates(seeds, k, interpret=interpret)

    def cut(x):
        return x.reshape(nf, -1, kpad)[:, :, :k].reshape(nf, -1)
    return cut(vals), cut(idx)


@partial(jax.jit, static_argnames=("k", "interpret"))
def batched_bottomk_select(seeds, k: int, interpret=None):
    """Exact global bottom-k per objective: one launch + one batched merge.

    seeds [F, n] -> (vals [F, k] ascending, idx [F, k]; invalid slots =
    (+inf, -1)) and tau [F] = the (k+1)-th smallest seed per objective
    (+inf if fewer), matching core.bottomk semantics row-wise.
    """
    nf, n = seeds.shape
    ksel = min(k + 1, n)
    if select_plan(n, ksel) == "block":
        vals, idx = _block_candidates(seeds, ksel, interpret=interpret)
    else:
        vals = seeds.astype(jnp.float32)
        idx = jnp.where(jnp.isfinite(vals),
                        jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1), -1)
    m = min(k + 1, vals.shape[1])
    neg_top, pos = jax.lax.top_k(-vals, m)          # ONE scan for all F
    cand_vals = -neg_top
    cand_idx = jnp.take_along_axis(idx, pos, axis=1)
    tau = (cand_vals[:, k] if cand_vals.shape[1] > k
           else jnp.full((nf,), _INF, jnp.float32))
    return cand_vals[:, :k], cand_idx[:, :k], tau


def block_bottomk(seeds, k: int, interpret=None):
    """seeds [n] -> (vals [nb, k], idx [nb, k]) block-local k smallest."""
    vals, idx = batched_block_bottomk(seeds[None, :], k, interpret=interpret)
    return vals[0], idx[0]


def bottomk_select(seeds, k: int, interpret=None):
    """Exact global bottom-k via block-local selection + candidate merge.

    Returns (vals [k] ascending, idx [k]; invalid slots = (+inf, -1)) and
    tau = the (k+1)-th smallest seed (+inf if fewer), matching
    core.bottomk semantics.
    """
    vals, idx, tau = batched_bottomk_select(seeds[None, :], k,
                                            interpret=interpret)
    return vals[0], idx[0], tau[0]

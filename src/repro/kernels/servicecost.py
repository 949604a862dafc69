"""Fused service-cost kernel: Q center sets x sample slab, ONE launch.

Center-set optimization (launch.cluster) scores thousands of candidate
sets per local-search round; evaluated one set at a time each candidate
pays a kernel launch plus an O(c) pass over the resident sample slab.
This kernel fuses the whole Q-batch into one VMEM-resident launch over a
(Q-tile, slab-block) grid:

  per Q-tile of qb sets x slab block of 128 slots (coords/HT read once
  per Q-tile):
    ht      [1, 128]         member ? w / p : 0        (HT weight, Eq. 5)
    d2      [qb*Cmax, 128]   squared distances of every center of every
                             set in the tile to the block's points — ONE
                             MXU contraction (centers ride the sublane
                             axis, slab slots the lane axis)
    mind2   [qb, 128]        min over each set's Cmax center slots
    fv      [qb, 128]        mind2^(mu/2)  (cost mode, per-set mu)
                             or 1[mind2 <= r^2]  (ball mode, per-set r)
    out    += fv * ht        [qb, 128] per-lane partial sums

and the [Q, 128] accumulator is reduced to [Q] once at the end. Launch
count is flat in both Q and Cmax. The Q-tile holds at most ``_ROWS``
center rows, so the distance block's VMEM footprint is bounded whatever
the batch size — callers never split Q. Q pads to the tile, Cmax to the
sublane quantum (8), dim to 8 with one spare column, slots to 128. The
engine's pad of Q to its ``q_quantum`` bucket happens on the host for a
host table (``core.costs.pad_cost_table``); for a table already on the
device it happens in the same compiled program as this layout and the
launch (``core.costs.estimate_bucketed``), so the table is not copied
whole before the launch.

Per-center validity rides that spare column of the center rows (1.0 =
invalid slot: ragged sets, padded rows), so it arrives already laid out
along the sublanes; the points' matching row is zero, so the flag never
enters the contraction. Invalid slots are masked to +inf before the min,
so an all-invalid row estimates exactly 0 (the padding element of the
Q bucket). Per-set parameters (mu, r, mode) ride one [Q, 128]
row block, columns 0..2.

Wire semantics are defined by ``core.costs.service_cost_values`` (the XLA
oracle); both paths share the quadratic distance expansion
d2 = |x|^2 + |c|^2 - 2 x.c clamped at 0, contracted at HIGHEST precision
(the TPU's default f32 matmul rounds operands to bf16, which the
cancellation in that expansion would amplify), so they agree to float
tolerance.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.costs import MODE_BALL, CostTable, encode_cost_queries
from repro.kernels._util import pad_tail, resolve_interpret, round_up

BLOCK = 128      # slab slots per grid step (one lane tile)
_SUBLANES = 8    # Q, Cmax and dim padding quantum
_ROWS = 2048     # center rows (sets x Cmax) per Q-tile: 1 MB distance block


def _q_tile(qn: int, cmax: int) -> int:
    """Sets per Q-tile: as many as fit ``_ROWS`` center rows, a multiple
    of the sublane quantum, no more than the (padded) batch."""
    fit = max(_SUBLANES, (_ROWS // cmax) // _SUBLANES * _SUBLANES)
    return min(fit, round_up(qn, _SUBLANES))


def _servicecost_kernel(pts_ref, ht_ref, ctr_ref, prm_ref, out_ref, *,
                        qb, cmax, dim):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pts = pts_ref[...]                                  # [dpad, 128]
    ht = ht_ref[...]                                    # [1, 128]
    ctr = ctr_ref[...]                                  # [qb*Cmax, dpad]
    invalid = ctr[:, dim:dim + 1] != 0                  # [qb*Cmax, 1]

    # squared distances, one MXU contraction for every (set, center) row;
    # rows >= dim of pts are zero, so the validity column drops out
    dots = jax.lax.dot_general(ctr, pts, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    cn2 = jnp.sum(ctr * ctr, axis=1, keepdims=True)     # [qb*Cmax, 1]
    pn2 = jnp.sum(pts * pts, axis=0, keepdims=True)     # [1, 128]
    d2 = jnp.maximum(cn2 + pn2 - 2.0 * dots, 0.0)
    d2 = jnp.where(invalid, jnp.float32(jnp.inf), d2)
    mind2 = jnp.min(d2.reshape(qb, cmax, BLOCK), axis=1)     # [qb, 128]

    prm = prm_ref[...]                                  # [qb, 128]
    mu = prm[:, 0:1]
    r = prm[:, 1:2]
    ball_mode = prm[:, 2:3] == MODE_BALL
    finite = jnp.isfinite(mind2)
    # mind2^(mu/2) = d^mu via exp/log (Mosaic-safe power); d = 0 -> 0
    cost = jnp.where(mind2 > 0,
                     jnp.exp(0.5 * mu * jnp.log(jnp.maximum(mind2, 1e-38))),
                     0.0)
    ball = (mind2 <= r * r).astype(jnp.float32)
    fv = jnp.where(finite, jnp.where(ball_mode, ball, cost), 0.0)
    out_ref[...] += fv * ht                             # per-lane partials


@partial(jax.jit, static_argnames=("interpret",))
def _service_cost_jit(points, probs, member, table, point_weights, interpret):
    interpret = resolve_interpret(interpret)
    c, dim = points.shape
    qn, cm, cdim = table.centers.shape
    if cdim != dim:
        raise ValueError(f"center dim {cdim} != point dim {dim}")
    cpad = round_up(max(c, 1), BLOCK)
    cmax = round_up(cm, _SUBLANES)
    qb = _q_tile(qn, cmax)
    qpad = round_up(qn, qb)
    dpad = round_up(dim + 1, _SUBLANES)     # + the validity column

    pw = (jnp.ones((c,), jnp.float32) if point_weights is None
          else jnp.asarray(point_weights, jnp.float32))
    ht = jnp.where(jnp.asarray(member, bool),
                   pw / jnp.maximum(jnp.asarray(probs, jnp.float32), 1e-30),
                   0.0)
    pts = jnp.pad(jnp.asarray(points, jnp.float32),
                  ((0, cpad - c), (0, dpad - dim))).T          # [dpad, cpad]
    ht = pad_tail(ht, cpad, 0.0)[None, :]                      # [1, cpad]
    valid = jnp.pad(jnp.asarray(table.cvalid, bool),
                    ((0, qpad - qn), (0, cmax - cm)))
    ctr = jnp.pad(jnp.asarray(table.centers, jnp.float32),
                  ((0, qpad - qn), (0, cmax - cm), (0, dpad - dim)))
    ctr = ctr.at[:, :, dim].set(jnp.where(valid, 0.0, 1.0))
    ctr = ctr.reshape(qpad * cmax, dpad)
    prm = jnp.stack([jnp.asarray(table.mu, jnp.float32),
                     jnp.asarray(table.param, jnp.float32),
                     jnp.asarray(table.mode, jnp.int32).astype(jnp.float32)],
                    axis=1)
    prm = jnp.pad(prm, ((0, qpad - qn), (0, BLOCK - prm.shape[1])))

    out = pl.pallas_call(
        partial(_servicecost_kernel, qb=qb, cmax=cmax, dim=dim),
        grid=(qpad // qb, cpad // BLOCK),
        in_specs=[
            pl.BlockSpec((dpad, BLOCK), lambda i, j: (0, j)),
            pl.BlockSpec((1, BLOCK), lambda i, j: (0, j)),
            pl.BlockSpec((qb * cmax, dpad), lambda i, j: (i, 0)),
            pl.BlockSpec((qb, BLOCK), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((qb, BLOCK), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((qpad, BLOCK), jnp.float32),
        interpret=interpret,
        name="service_cost",
    )(pts, ht, ctr, prm)
    return jnp.sum(out, axis=1)[:qn]


def service_cost_slab(points, probs, member, queries, point_weights=None,
                      interpret=None):
    """Batched service-cost estimates over one sampled slab -> [Q].

    points: slot coordinates [c, dim] aligned with probs/member (the
    MultiSketch slab fields); queries: ServiceCostQuery batch or encoded
    ``CostTable`` (core.costs). ONE pallas launch regardless of Q and Cmax;
    the grid runs over Q-tiles x slab blocks (c / 128 steps per tile,
    accumulating the [Q, 128] partial sums in place).
    """
    table = encode_cost_queries(queries)
    return _service_cost_jit(
        jnp.asarray(points, jnp.float32), jnp.asarray(probs, jnp.float32),
        jnp.asarray(member, bool),
        CostTable(*(jnp.asarray(x) for x in table)),
        point_weights if point_weights is None
        else jnp.asarray(point_weights, jnp.float32),
        interpret)

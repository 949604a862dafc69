"""Blocked pairwise rank-count kernel — universal sample membership.

Membership in the universal samples is a rank condition (DESIGN.md §3):
  monotone (Lemma 5.1):  x in S^(M,k)  <=>  h_x < k,
      h_x = #{y : w_y >= w_x  and  u_y < u_x}
  capping  (Lemma 6.3):  x in S^(C,k)  <=>  h_x + l_x < k,
      l_x = #{y : w_y <  w_x  and  r_y/w_y < r_x/w_x}

The paper's heap algorithms are sequential; the TPU-native batch form is a
blocked all-pairs count: grid (nx, ny), each step loads an x-block and a
y-block into VMEM and accumulates counts for the x-block. O(n^2 / B) work
but entirely VMEM-resident, VPU-aligned tiles, zero HBM intermediates — for
the n <= 2^20 per-training-step uses (gradient compression, telemetry) this
beats the sort path's all-to-HBM round trips.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._util import pad_tail, resolve_interpret, round_up

# a 1D operand longer than one block must tile as XLA lays it out on the
# TPU (1024-element tiles), so both blocks are one such tile
BLOCK_X = 1024
BLOCK_Y = 1024


def _rankcount_kernel(wx_ref, hx_ref, lx_ref, ax_ref,
                      wy_ref, hy_ref, ly_ref, ay_ref,
                      h_ref, l_ref):
    """Accumulate h and l for the x-block against one y-block.

    h uses the u-statistic (hx/hy); l uses the r/w-statistic (lx/ly).
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)
        l_ref[...] = jnp.zeros_like(l_ref)

    wx = wx_ref[...].astype(jnp.float32)[:, None]   # [BX,1]
    shx = hx_ref[...].astype(jnp.float32)[:, None]
    slx = lx_ref[...].astype(jnp.float32)[:, None]
    ax = ax_ref[...][:, None] != 0
    wy = wy_ref[...].astype(jnp.float32)[None, :]   # [1,BY]
    shy = hy_ref[...].astype(jnp.float32)[None, :]
    sly = ly_ref[...].astype(jnp.float32)[None, :]
    ay = ay_ref[...][None, :] != 0

    both = ax & ay
    ge = both & (wy >= wx) & (shy < shx)
    lt = both & (wy < wx) & (sly < slx)
    h_ref[...] += jnp.sum(ge, axis=1).astype(jnp.int32)
    l_ref[...] += jnp.sum(lt, axis=1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("interpret",))
def rank_counts(weights, s_h, s_l, active, interpret=None):
    """Returns (h, l) int32 [n]; h vs order stat s_h (u), l vs s_l (r/w).

    Ragged n is auto-padded with inactive entries (never counted on either
    side of a pair) and the counts sliced back. The diagonal never
    self-counts: the strict comparison s_y < s_x is false at y == x.
    """
    interpret = resolve_interpret(interpret)
    n = weights.shape[0]
    # n <= BLOCK_X fits a (1, 1) grid unpadded; otherwise round up to a
    # BLOCK_Y multiple (also a BLOCK_X multiple since BLOCK_X | BLOCK_Y)
    npad = n if n <= BLOCK_X else round_up(n, BLOCK_Y)
    bx = min(BLOCK_X, npad)
    by = min(BLOCK_Y, npad)
    w32 = pad_tail(weights.astype(jnp.float32), npad, 0.0)
    sh32 = pad_tail(s_h.astype(jnp.float32), npad, 0.0)
    sl32 = pad_tail(s_l.astype(jnp.float32), npad, 0.0)
    a32 = pad_tail(active.astype(jnp.int32), npad, 0)
    grid = (npad // bx, npad // by)

    xspec = lambda b: pl.BlockSpec((b,), lambda i, j: (i,))
    yspec = lambda b: pl.BlockSpec((b,), lambda i, j: (j,))
    h, l = pl.pallas_call(
        _rankcount_kernel,
        grid=grid,
        in_specs=[xspec(bx), xspec(bx), xspec(bx), xspec(bx),
                  yspec(by), yspec(by), yspec(by), yspec(by)],
        out_specs=[pl.BlockSpec((bx,), lambda i, j: (i,)),
                   pl.BlockSpec((bx,), lambda i, j: (i,))],
        out_shape=[jax.ShapeDtypeStruct((npad,), jnp.int32),
                   jax.ShapeDtypeStruct((npad,), jnp.int32)],
        interpret=interpret,
        name="rank_counts",
    )(w32, sh32, sl32, a32, w32, sh32, sl32, a32)
    return h[:n], l[:n]

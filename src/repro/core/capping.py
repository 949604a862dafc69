"""Universal capping sample S^(C,k), C = {cap_T : T > 0} (paper §6).

Membership (Lemma 6.3):  x in S^(C,k)  <=>  h_x + l_x < k, where
    h_x = #{y : w_y >= w_x and u_y < u_x}                (same h as §5)
    l_x = #{y : w_y <  w_x and r_y / w_y < r_x / w_x}    (ppswor ranks r)

Estimation (Cor. 6.2 + Eq. 3): p_x = Pr_{u_x}[ r_x / w_x < t_x ] where t_x is
the k-th smallest cap_{w_x}-seed among keys y != x, and
cap_{w_x}-seed(y) = r_y / min(w_y, w_x). The k+1 smallest cap_{w_x}-seeds all
belong to keys with h_y + l_y <= k (Lemma 6.1/6.4 argument: a key's seed rank
is minimized at T = w_y), so the final pass may be restricted to the small
candidate set {h + l <= k} — this is the paper's §6.1 algorithm.

Size (Thm 6.1): E|S^(C,k)| <= e k ln(w_max/w_min) — checked in tests.

Production path = two sort+buffer scans (h-scan by (-w, u); l-scan by (w, rw))
+ an O(m^2) pairwise pass on the m candidate keys (m is a static capacity;
expected candidates ~ k ln(w_max/w_min) << n).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .bottomk import conditional_prob
from .hashing import rank_of, uniform01
from .universal import _buffer_scan, _INF


class CappingSample(NamedTuple):
    member: jnp.ndarray  # bool [n]
    prob: jnp.ndarray    # float32 [n] — p_x^(C,k) for members else 0
    aux: jnp.ndarray     # bool [n] — potential/actual auxiliary keys (h+l == k)
    hl: jnp.ndarray      # int32 [n] — h_x + l_x capped at k+1


def _pairwise_capping(w, r, act, k: int):
    """t_x = k-th smallest cap_{w_x}-seed over y != x. O(n^2). w,r: [n]."""
    n = w.shape[0]
    capw = jnp.minimum(w[None, :], w[:, None])            # cap_{w_x}(w_y), [x,y]
    seeds = jnp.where(act[None, :] & (capw > 0), r[None, :] / jnp.maximum(capw, 1e-30), _INF)
    seeds = jnp.where(jnp.eye(n, dtype=bool), _INF, seeds)  # exclude y == x
    srt = jnp.sort(seeds, axis=1)
    t = srt[:, k - 1] if n >= k else jnp.full((n,), _INF)
    return t


def universal_capping_ref(weights, u, active, k: int,
                          scheme: str = "ppswor") -> CappingSample:
    """Exact O(n^2) oracle."""
    w = jnp.asarray(weights, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    act = jnp.asarray(active, bool) & (w > 0)
    r = rank_of(u, scheme)
    rw = jnp.where(act, r / jnp.maximum(w, 1e-30), _INF)

    h = jnp.sum((act[None, :] & (w[None, :] >= w[:, None])
                 & (u[None, :] < u[:, None])), axis=1)
    l = jnp.sum((act[None, :] & (w[None, :] < w[:, None])
                 & (rw[None, :] < rw[:, None])), axis=1)
    hl = (h + l).astype(jnp.int32)
    member = act & (hl < k)
    aux = act & (hl == k)

    t = _pairwise_capping(w, r, act, k)
    p = jnp.where(member, conditional_prob(w, t, scheme), 0.0)
    return CappingSample(member=member, prob=p, aux=aux,
                         hl=jnp.minimum(hl, k + 1))


def universal_capping_sample(keys, weights, active, k: int, m_cap: int,
                             scheme: str = "ppswor", seed=0,
                             u=None) -> CappingSample:
    """Production S^(C,k): two buffer scans + O(m_cap^2) candidate pass.

    m_cap: static capacity for the candidate set {h + l <= k}. If the true
    candidate count exceeds m_cap (raise it ~ e*k*ln(w_max/w_min) + slack),
    excess candidates are dropped from the pairwise pass; membership bits
    remain exact (they come from the scans), only probs of dropped members
    would be wrong — we detect overflow and report it via ``hl`` sentinel.
    """
    w = jnp.asarray(weights, jnp.float32)
    act = jnp.asarray(active, bool) & (w > 0)
    if u is None:
        u = uniform01(keys, seed)
    u = jnp.asarray(u, jnp.float32)
    r = rank_of(u, scheme)
    n = w.shape[0]
    pos = jnp.arange(n)

    # --- h-scan: process by decreasing w (ties: increasing u) ---------------
    order_h = jnp.lexsort((u, -jnp.where(act, w, -_INF)))
    rank_h, _, _ = _buffer_scan(jnp.where(act[order_h], u[order_h], _INF),
                                pos[order_h], k + 1)
    h = jnp.zeros((n,), jnp.int32).at[order_h].set(
        jnp.minimum(rank_h, k + 1).astype(jnp.int32))

    # --- l-scan: process by increasing w (ties: increasing r/w) -------------
    rw = jnp.where(act, r / jnp.maximum(w, 1e-30), _INF)
    order_l = jnp.lexsort((rw, jnp.where(act, w, _INF)))
    sw = jnp.where(act, w, _INF)[order_l]
    rank_l, _, _ = _buffer_scan(jnp.where(act[order_l], rw[order_l], _INF),
                                pos[order_l], k + 1)
    # subtract within-weight-group position: same-weight earlier keys all have
    # smaller r/w and were counted by the scan but are NOT in {w_y < w_x}.
    is_start = jnp.concatenate([jnp.ones((1,), bool), sw[1:] != sw[:-1]])
    gstart = jax.lax.cummax(jnp.where(is_start, jnp.arange(n), 0), axis=0)
    gpos = jnp.arange(n) - gstart
    sat = rank_l >= k + 1  # saturated => h+l > k regardless (see module doc)
    l_sorted = jnp.where(sat, k + 1, jnp.maximum(rank_l - gpos, 0))
    l = jnp.zeros((n,), jnp.int32).at[order_l].set(l_sorted.astype(jnp.int32))

    hl = jnp.minimum(h + l, k + 1)
    member = act & (hl < k)
    aux = act & (hl == k)

    # --- candidate pass: exact t_x over the {h+l <= k} set ------------------
    cand_mask = act & (hl <= k)
    cand_idx = jnp.where(cand_mask, pos, n)
    cand_idx = jnp.sort(cand_idx)[:m_cap]          # first m_cap candidates
    valid = cand_idx < n
    ci = jnp.where(valid, cand_idx, 0)
    cw, cr, cact = w[ci], r[ci], valid & act[ci]
    t_c = _pairwise_capping(cw, cr, cact, k)
    p_c = conditional_prob(cw, t_c, scheme)
    prob = jnp.zeros((n,), jnp.float32).at[jnp.where(valid, ci, n)].set(
        p_c, mode="drop")
    prob = jnp.where(member, prob, 0.0)
    return CappingSample(member=member, prob=prob, aux=aux, hl=hl)


def capping_size_bound(k: int, w_max: float, w_min: float) -> float:
    """Thm 6.1: E|S^(C,k)| <= e k ln(w_max / w_min)."""
    import math
    return math.e * k * max(1.0, math.log(max(w_max / max(w_min, 1e-30), math.e)))

"""Universal monotone sample S^(M,k) (paper §5).

Key facts implemented here:
  Lemma 5.1/5.2:  x in S^(M,k) <=> x in S^(Thresh_{w_x},k)
                  <=> h_x < k where h_x = #{y : w_y >= w_x  and  u_y < u_x}.
  Estimation:     for member x, the conditional inclusion probability is
                  p(w_x) = (k+1)-th smallest u among {y : w_y >= w_x}
                  (or 1 when fewer than k+1 such keys). This equals the
                  paper's "k-th smallest u_y in Y_x = {y != x : w_y >= w_x}"
                  because a member's own u is among the k smallest of the
                  inclusive set, so deleting it shifts k-th -> (k+1)-th.
  Aux keys Z:     the keys realizing those (k+1)-th smallest values for at
                  least one member's weight group, minus S (paper §5).
  Size bound:     E|S^(M,k)| <= k ln n (Thm 5.1) — checked in tests.

Two implementations:
  * ``universal_monotone_ref``  — O(n^2) pairwise oracle (tests, small n).
  * ``universal_monotone_sample`` — production path: one XLA sort by (-w, u)
    + a BLOCKED buffer scan carrying the (k+1) smallest u's seen so far
    (``_buffer_scan``: _SCAN_CHUNK elements per sequential step, each step
    pure cumsum/matmul-shaped vector work; bit-identical to the one-element-
    per-step ``_buffer_scan_ref``). This is paper Algorithm 1 with the
    max-heap replaced by a fixed-shape sorted buffer (TPU adaptation — see
    DESIGN.md §3).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .hashing import uniform01

_INF = jnp.float32(jnp.inf)


class UniversalSample(NamedTuple):
    member: jnp.ndarray  # bool [n] — x in S^(M,k)
    prob: jnp.ndarray    # float32 [n] — p(w_x) for members, else 0
    aux: jnp.ndarray     # bool [n] — x in Z (kept for mergeability/estimation)
    h: jnp.ndarray       # int32 [n] — h_x capped at k+1 (diagnostics/capping)


# ---------------------------------------------------------------------------
# O(n^2) oracle
# ---------------------------------------------------------------------------

def universal_monotone_ref(weights, u, active, k: int) -> UniversalSample:
    """Exact pairwise-definition implementation. O(n^2) memory/compute."""
    w = jnp.asarray(weights, jnp.float32)
    u = jnp.asarray(u, jnp.float32)
    act = jnp.asarray(active, bool) & (w > 0)
    n = w.shape[0]

    # h_x = #{y active : w_y >= w_x and u_y < u_x}
    ge = act[None, :] & (w[None, :] >= w[:, None])   # [x, y]
    lt = u[None, :] < u[:, None]
    h = jnp.sum(ge & lt, axis=1).astype(jnp.int32)
    member = act & (h < k)

    # p(w_x) = (k+1)-th smallest u among {y : w_y >= w_x} (x included)
    cand = jnp.where(ge, u[None, :], _INF)           # [x, y]
    cand_sorted = jnp.sort(cand, axis=1)
    if n > k:
        g = cand_sorted[:, k]
        g_idx = jnp.argsort(cand, axis=1)[:, k]
    else:
        g = jnp.full((n,), _INF)
        g_idx = jnp.zeros((n,), jnp.int32)
    prob = jnp.where(member, jnp.where(jnp.isfinite(g), g, 1.0), 0.0)

    # Z = {argmin-(k+1) key for some member with p < 1} \ S
    need = member & jnp.isfinite(g)
    marks = jnp.zeros((n,), bool).at[jnp.where(need, g_idx, n)].set(
        True, mode="drop")
    aux = marks & ~member
    return UniversalSample(member=member, prob=prob, aux=aux,
                           h=jnp.minimum(h, k + 1))


# ---------------------------------------------------------------------------
# Production path: sort + (k+1)-buffer scan  (Algorithm 1, TPU-adapted)
# ---------------------------------------------------------------------------

def _buffer_scan_ref(values, indices, k_plus_1: int):
    """Reference (sequential) buffer scan — one lax.scan step per element,
    O(n) sequential steps of O(k) vector work. Kept as the bit-exactness
    oracle for the blocked ``_buffer_scan``; see that docstring for the
    emitted (rank, tail_v, tail_i) contract."""
    slots = jnp.arange(k_plus_1)

    def step(carry, xs):
        buf_v, buf_i = carry
        v, i = xs
        rank = jnp.sum(buf_v < v).astype(jnp.int32)
        do_insert = rank < k_plus_1
        # insert v at position ``rank`` — rank counts STRICTLY smaller
        # entries, so a tied v lands before every equal-valued entry —
        # shifting the suffix right and evicting the current tail slot.
        # On a tie at the capacity boundary (v == buf_v[-1]) the insert
        # still happens: the old tail is evicted, tail_v is unchanged and
        # tail_i becomes the index of the shifted equal-valued entry. The
        # blocked scan reproduces this bit-exactly because its phase-1
        # rank uses the same strict-< count (searchsorted side='left').
        rolled_v = jnp.concatenate([buf_v[:1], buf_v[:-1]])
        rolled_i = jnp.concatenate([buf_i[:1], buf_i[:-1]])
        new_v = jnp.where(slots < rank, buf_v,
                          jnp.where(slots == rank, v, rolled_v))
        new_i = jnp.where(slots < rank, buf_i,
                          jnp.where(slots == rank, i, rolled_i))
        buf_v = jnp.where(do_insert, new_v, buf_v)
        buf_i = jnp.where(do_insert, new_i, buf_i)
        return (buf_v, buf_i), (rank, buf_v[-1], buf_i[-1])

    init = (jnp.full((k_plus_1,), _INF), jnp.full((k_plus_1,), -1, jnp.int32))
    _, (rank, tail_v, tail_i) = jax.lax.scan(
        step, init, (values.astype(jnp.float32), indices.astype(jnp.int32)))
    return rank, tail_v, tail_i


_SCAN_CHUNK = 128  # elements folded per blocked rank-scan step


@partial(jax.jit, static_argnames=("k_plus_1",))
def _buffer_scan(values, indices, k_plus_1: int):
    """Scan ``values`` (processing order) keeping the k_plus_1 smallest so far.

    Per step emits:
      rank   — #{processed before this step with value < v}, exact while
               <= k_plus_1 - 1; == k_plus_1 means "saturated" (>= that many).
      tail_v — buffer's largest kept value AFTER inserting v
               (= the k_plus_1-th smallest processed so far, inf if fewer).
      tail_i — index of the key realizing tail_v (-1 if none).

    BLOCKED implementation, bit-identical to ``_buffer_scan_ref``, built on
    two facts about the sequential buffer:

      * the emitted rank equals min(#{earlier with value < v}, k_plus_1) —
        a capped prefix-smaller-count, independent of buffer dynamics;
      * an element whose rank saturates is NEVER inserted, so the tail
        sequence is a function of the INSERTED subsequence only (expected
        size ~ k ln n for hashed/random processing order, paper Thm 5.1's
        harmonic argument), and a dropped position's tail is that of the
        most recent inserted position (forward fill).

    Phase 1 computes every rank with a chunked scan (n / _SCAN_CHUNK
    sequential steps: carry = the k_plus_1 smallest values so far, one
    searchsorted + one [C, C] masked pairwise count per chunk). Phase 2
    compacts the inserted elements with a cumsum scatter and replays only
    them through ``_buffer_scan_ref`` (a static bound ~4x the expected
    inserted count; in the unlikely overflow — e.g. an adversarial
    near-descending order — a ``lax.cond`` falls back to the full
    sequential replay, preserving exactness).
    """
    n = values.shape[0]
    k1 = k_plus_1
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32),
                jnp.zeros((0,), jnp.int32))
    v = values.astype(jnp.float32)
    ix = indices.astype(jnp.int32)
    bound = _insert_bound(n, k1)
    if bound >= n:  # replay wouldn't compress anything — scan directly
        return _buffer_scan_ref(v, ix, k1)

    # ---- phase 1: ranks --------------------------------------------------
    c = min(_SCAN_CHUNK, n)
    npad = -(-n // c) * c
    vp = (jnp.pad(v, (0, npad - n), constant_values=jnp.inf)
          if npad > n else v)  # inert tail pad; outputs sliced back
    s_idx = jnp.arange(c)
    before = s_idx[:, None] < s_idx[None, :]

    def rank_step(bv, cv):
        # carry bv: the k1 smallest values so far (sorted multiset), so
        # searchsorted == min(#{earlier chunks' values < cv}, k1)
        cc = jnp.searchsorted(bv, cv).astype(jnp.int32)
        within = jnp.sum((cv[:, None] < cv[None, :]) & before, axis=0,
                         dtype=jnp.int32)
        rank = jnp.minimum(cc + within, k1)
        return jnp.sort(jnp.concatenate([bv, cv]))[:k1], rank

    _, rank = jax.lax.scan(rank_step, jnp.full((k1,), _INF),
                           vp.reshape(-1, c))
    rank = rank.reshape(-1)[:n]

    # ---- phase 2: tails from the inserted subsequence --------------------
    ins = rank < k1
    fill = jnp.cumsum(ins) - 1        # per position: last inserted slot
    slot = jnp.where(ins, fill, bound)
    num = fill[-1] + 1
    comp_v = jnp.full((bound,), _INF).at[slot].set(v, mode="drop")
    comp_i = jnp.full((bound,), -1, jnp.int32).at[slot].set(ix, mode="drop")

    def replay_compressed(_):
        _, tv, ti = _buffer_scan_ref(comp_v, comp_i, k1)
        return (jnp.where(fill >= 0, tv[jnp.maximum(fill, 0)], _INF),
                jnp.where(fill >= 0, ti[jnp.maximum(fill, 0)], -1))

    def replay_full(_):
        _, tv, ti = _buffer_scan_ref(v, ix, k1)
        return tv, ti

    tail_v, tail_i = jax.lax.cond(num <= bound, replay_compressed,
                                  replay_full, None)
    return rank, tail_v, tail_i


def _insert_bound(n: int, k1: int) -> int:
    """Static capacity for the inserted subsequence: ~4x the padded
    harmonic bound k1 * (2 + ln(n / k1 + 1)) — an upper bound on the
    expected count k1 * (1 + ln(n / k1)) that stays safe when n ~ k1 —
    rounded up to the 128 quantum (floor 256, ceiling n)."""
    import math
    exp = k1 * (2.0 + math.log(max(n, 2) / max(k1, 1) + 1.0))
    return min(n, max(256, -(-4 * int(exp) // 128) * 128))


def _group_last(sorted_w):
    """For each sorted position, the position of the LAST element with the
    same weight (weight-group end)."""
    n = sorted_w.shape[0]
    pos = jnp.arange(n)
    is_last = jnp.concatenate([sorted_w[1:] != sorted_w[:-1],
                               jnp.ones((1,), bool)])
    cand = jnp.where(is_last, pos, n - 1 + jnp.zeros((n,), jnp.int32))
    # backward running min propagates each group-end to its whole group
    return jax.lax.cummin(jnp.where(is_last, pos, n), axis=0, reverse=True)


@partial(jax.jit, static_argnames=("k",))
def universal_monotone_sample(keys, weights, active, k: int,
                              seed=0, u=None) -> UniversalSample:
    """S^(M,k) over a fixed-shape batch: O(n log n) sort + blocked scan.

    jit-compiled per (shape, k): host callers get one dispatch; jitted
    callers (merge/sketch rebuilds) inline it into the enclosing trace.
    """
    w = jnp.asarray(weights, jnp.float32)
    act = jnp.asarray(active, bool) & (w > 0)
    if u is None:
        u = uniform01(keys, seed)
    u = jnp.asarray(u, jnp.float32)
    n = w.shape[0]

    # inactive keys: push to the very end and never count them
    sort_w = jnp.where(act, w, -_INF)
    order = jnp.lexsort((u, -sort_w))          # primary: -w asc (w desc); tie: u asc
    sw, su, sact = sort_w[order], u[order], act[order]

    rank, tail_v, tail_i = _buffer_scan(jnp.where(sact, su, _INF),
                                        jnp.arange(n)[order], k + 1)
    h = jnp.minimum(rank, k + 1)
    s_member = sact & (rank < k)

    # p(w) snapshot at each weight-group end: (k+1)-th smallest u among all
    # keys with weight >= w (ties fully processed by group end).
    gl = _group_last(sw)
    g_v = tail_v[gl]
    g_i = tail_i[gl]
    s_prob = jnp.where(s_member, jnp.where(jnp.isfinite(g_v), g_v, 1.0), 0.0)

    # Z: keys realizing a finite group-end tail for a member's group
    need = s_member & jnp.isfinite(g_v)
    marks = jnp.zeros((n,), bool).at[jnp.where(need, g_i, n)].set(
        True, mode="drop")

    # scatter back to original order
    member = jnp.zeros((n,), bool).at[order].set(s_member)
    prob = jnp.zeros((n,), jnp.float32).at[order].set(s_prob)
    h_out = jnp.zeros((n,), jnp.int32).at[order].set(h.astype(jnp.int32))
    aux = marks & ~member
    return UniversalSample(member=member, prob=prob, aux=aux, h=h_out)


def expected_size_bound(n: int, k: int) -> float:
    """Thm 5.1: E|S^(M,k)| <= sum_i min(1, k/i) < k (1 + ln n)."""
    import math
    return float(sum(min(1.0, k / i) for i in range(1, n + 1)))

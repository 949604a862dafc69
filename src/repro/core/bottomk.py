"""Bottom-k (order) sampling: priority and ppswor (paper §2.2–§2.3).

f-seed(x) = r_x / f(w_x); the sample is the k keys first in (f-seed, key)
order and the retained threshold tau is the f-seed of the (k+1)-th
(``bottomk_members``, the rule every sampler in this package applies).
Conditional inclusion probabilities (paper Eq. 3):
    priority: p_x = min(1, f(w_x) * tau)
    ppswor:   p_x = 1 - exp(-f(w_x) * tau)
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .funcs import StatFn
from .hashing import rank_of, uniform01

_INF = jnp.float32(jnp.inf)


def f_seed(weights, active, f: StatFn, u, scheme: str):
    """f-seed(x) = r_x / f(w_x); inactive or f(w)=0 keys get seed = +inf."""
    r = rank_of(u, scheme)
    fv = f(weights)
    ok = active & (fv > 0)
    return jnp.where(ok, r / jnp.maximum(fv, 1e-30), _INF)


class BottomK(NamedTuple):
    member: jnp.ndarray   # bool [n] — x in S (first k by (f-seed, key))
    prob: jnp.ndarray     # float32 [n] — conditional p_x for members, else 0
    tau: jnp.ndarray      # float32 [] — the (k+1)-th entry's f-seed
    seeds: jnp.ndarray    # float32 [n] — the f-seeds (inf for inactive)


def bottomk_members(seeds, keys, ks, cand=None):
    """The bottom-k membership rule: the one place it is decided.

    For each objective f with budget k_f, the f-members are the k_f
    entries with a finite f-seed that come first in (f-seed, key) order;
    tau_f is the f-seed of the (k_f+1)-th such entry, f's threshold
    entry (tau_f = +inf, and no threshold entry, when at most k_f seeds
    are finite). Breaking seed ties by key gives exactly k_f members
    whenever more than k_f seeds are finite, so no member is left with
    p = 0: u has 24 bits, and equal seeds at the boundary are common.

    seeds [F, n]: f-seeds, +inf where the entry is not eligible for f.
    keys [n] int32: IN ASCENDING ORDER, distinct among eligible entries.
    The selection ranks equal seeds by position, which is then key order.
    ks: per-objective budgets (static).
    cand: optional (vals, idx) [F, >= max(ks)+1], each row's smallest
    seeds ascending with equal seeds in position order, as
    ``kernels.blockselect.batched_bottomk_select`` returns them; by
    default one ``lax.top_k``, which orders ties the same way.

    Returns (member [F, n] bool, taus [F], thr [F] int32: the position of
    f's threshold entry, -1 where tau_f = +inf).
    """
    nf, n = seeds.shape
    kmax = max(ks)
    if cand is None:
        neg, idx = jax.lax.top_k(-seeds, min(kmax + 1, n))
        cand = (-neg, idx)
    vals, idx = cand
    if vals.shape[1] < kmax + 1:                 # n <= kmax: no (k+1)-th
        pad = ((0, 0), (0, kmax + 1 - vals.shape[1]))
        vals = jnp.pad(vals, pad, constant_values=jnp.inf)
        idx = jnp.pad(idx, pad, constant_values=-1)
    rows = jnp.arange(nf)
    kk = jnp.asarray([min(k, n) for k in ks])
    last = vals[rows, kk - 1]                    # the k_f-th entry ...
    last_key = keys[jnp.maximum(idx[rows, kk - 1], 0)]
    taus = vals[rows, kk]                        # ... and the (k_f+1)-th
    thr = jnp.where(jnp.isfinite(taus), idx[rows, kk], -1)
    member = jnp.isfinite(seeds) & (
        (seeds < last[:, None])
        | ((seeds == last[:, None]) & (keys[None, :] <= last_key[:, None])))
    return member, taus, thr.astype(jnp.int32)


def bottomk_members_any_order(seeds, keys, ks):
    """:func:`bottomk_members` for entries in any order: one argsort by
    key, the rule, and the results put back in the input's order."""
    order = jnp.argsort(keys)
    member, taus, thr = bottomk_members(seeds[:, order], keys[order], ks)
    member = jnp.zeros_like(member).at[:, order].set(member)
    return member, taus, jnp.where(thr >= 0, order[thr], -1)


def conditional_prob(fv, tau, scheme: str):
    """Eq. (3): Pr_{u~U[0,1]}[r/f(w) < tau]."""
    t = jnp.maximum(fv, 0.0) * tau
    if scheme == "priority":
        return jnp.minimum(1.0, t)
    # ppswor; tau may be +inf (fewer than k+1 active keys) -> p = 1.
    return jnp.where(jnp.isinf(t), 1.0, -jnp.expm1(-t))


def bottomk_sample(keys, weights, active, f: StatFn, k: int, scheme: str = "ppswor",
                   seed=0) -> BottomK:
    """Bottom-k sample w.r.t. f, with conditional inclusion probabilities.

    For member x the k-th smallest f-seed among OTHER keys equals tau (the
    global (k+1)-th smallest), which is exactly the conditioning the paper
    uses (§2.3). Keys may come in any order.
    """
    u = uniform01(keys, seed)
    seeds = f_seed(weights, active, f, u, scheme)
    member, tau, _ = bottomk_members_any_order(
        seeds[None, :], jnp.asarray(keys, jnp.int32), (k,))
    fv = jnp.where(active, f(weights), 0.0)
    p = jnp.where(member[0], conditional_prob(fv, tau[0], scheme), 0.0)
    return BottomK(member=member[0], prob=p, tau=tau[0], seeds=seeds)

"""Multi-objective samples S^(F) (paper §3).

PPS (§3.1):     p_x^(F) = max_{(f,k_f) in F} p_x^(f,k_f)            (Eq. 4)
Bottom-k (§3.2): S^(F) = U_f S^(f,k_f) under SHARED u_x; estimation uses the
conditional inclusion probability p_x^(F) = max_f p_x^(f), with the auxiliary
key set Z retained so the probabilities are computable from the sample alone.

Estimates from S^(F) dominate every dedicated sample simultaneously
(Thm 3.1): CV[Q^(g,H)] <= min_f sqrt(rho(f,g) / (q^(g)(H) k_f)).
"""
from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax.numpy as jnp

from .bottomk import bottomk_members_any_order, conditional_prob, f_seed
from .funcs import StatFn
from .hashing import uniform01
from .pps import pps_probabilities


class MultiPps(NamedTuple):
    member: jnp.ndarray  # bool [n]
    prob: jnp.ndarray    # float32 [n] — p_x^(F)
    fsums: jnp.ndarray   # float32 [|F|] — auxiliary per-objective totals


def multi_pps_sample(keys, weights, active, objectives: Sequence[Tuple[StatFn, int]],
                     seed=0) -> MultiPps:
    """Multi-objective pps sample (Eq. 4), coordinated via shared u_x."""
    probs = []
    fsums = []
    for f, kf in objectives:
        p, s = pps_probabilities(weights, active, f, kf)
        probs.append(p)
        fsums.append(s)
    p_F = jnp.stack(probs).max(axis=0)
    u = uniform01(keys, seed)
    return MultiPps(member=(u < p_F), prob=p_F, fsums=jnp.stack(fsums))


class MultiBottomK(NamedTuple):
    member: jnp.ndarray   # bool [n] — x in S^(F) = union of dedicated samples
    prob: jnp.ndarray     # float32 [n] — p_x^(F) = max_f p_x^(f) for members
    aux: jnp.ndarray      # bool [n] — x in Z (auxiliary; carries (u_x, w_x))
    taus: jnp.ndarray     # float32 [|F|] — tau^(f,k_f) per objective


def multi_bottomk_sample(keys, weights, active,
                         objectives: Sequence[Tuple[StatFn, int]],
                         scheme: str = "ppswor", seed=0) -> MultiBottomK:
    """Multi-objective bottom-k sample S^(F) with aux keys Z (paper §3.2).

    All per-objective samples share the same u_x (coordination). For each
    objective (f, k_f) the members, tau_f and the threshold key (the
    (k_f+1)-th entry) follow ``bottomk.bottomk_members``: the first k_f
    by (f-seed, key). Keys may come in any order.
    Z collects, for each member x, the threshold key y_x of its most forgiving
    objective g_x — keys that are needed to recompute p_x^(F) from the sample
    but are not themselves members.
    """
    u = uniform01(keys, seed)
    n = weights.shape[0]
    nf = len(objectives)

    # seeds and f-values for every objective under the SAME u_x, [|F|, n]
    seeds_F = jnp.stack([f_seed(weights, active, f, u, scheme)
                         for f, _ in objectives])
    fv_F = jnp.stack([jnp.where(active, f(weights), 0.0)
                      for f, _ in objectives])
    members_F, taus, thr = bottomk_members_any_order(
        seeds_F, jnp.asarray(keys, jnp.int32), tuple(k for _, k in objectives))
    probs = jnp.where(members_F,
                      conditional_prob(fv_F, taus[:, None], scheme), 0.0)
    thr_key_onehots = jnp.arange(n)[None, :] == thr[:, None]

    member = members_F.any(axis=0)
    p_F = probs.max(axis=0)
    # g_x = argmax_f p_x^(f) among objectives with x in S^(f) — since p_f is 0
    # for non-members of f, the plain argmax implements the paper's g_x.
    g_x = probs.argmax(axis=0)          # [n]
    # Z = {y_x : x in S^(F), p_x^(g_x) < 1} \ S^(F): union of threshold keys of
    # objectives that are "g_x" for at least one member with p < 1.
    member_needs = member & (p_F < 1.0)
    needed_f = jnp.any(member_needs[None, :]
                       & (g_x[None, :] == jnp.arange(nf)[:, None]), axis=1)
    aux = jnp.any(thr_key_onehots & needed_f[:, None], axis=0) & ~member
    return MultiBottomK(member=member, prob=jnp.where(member, p_F, 0.0),
                        aux=aux, taus=taus)

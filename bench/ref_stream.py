"""Plain reference of the key-value stream's sample and answers.

The semantics, after arXiv:1509.07445 §2-§3: a key's weight w_x is the
largest weight it was ingested with; u_x is the top 24 bits of a keyed
32-bit hash of x, shifted by half a step into (0, 1); for each statistic
f the f-seed is r_x / f(w_x), with r_x = -ln(1 - u_x), and +inf where
f(w_x) = 0. Keys are ordered by (f-seed, key): the first k_f are the
f-members, and tau_f is the f-seed of the (k_f+1)-th. A member's
inclusion probability is p_x = max over the objectives it is a member of
of 1 - exp(-f(w_x) tau_f); an answer is the Horvitz-Thompson sum of
f(w_x) / p_x over the members in the predicate's segment.

Seeds are float32, as the configuration states them; probabilities and
answers are float64. Nothing is taken from the program: the chunks come
from the benchmark's generator, the hash and the predicate test are
written out here in numpy. ``bf16=True`` is the control: the same
reference with every weight rounded to bfloat16, the next precision below
the float32 the configuration states.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)
_M1, _M2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)


def _mix(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _M1
    h = h ^ (h >> np.uint32(13))
    h = h * _M2
    return h ^ (h >> np.uint32(16))


def hash_u32(keys, seed: int) -> np.ndarray:
    """fmix32 twice, keyed by the seed (uint32 arithmetic, wrapping)."""
    with np.errstate(over="ignore"):
        s = np.uint32(int(seed) & 0xFFFFFFFF)
        h = _mix(np.asarray(keys).astype(np.uint32) + _GOLDEN + s)
        return _mix(h ^ (s * _M1 + np.uint32(1)))


def uniform01(keys, seed: int) -> np.ndarray:
    h = hash_u32(keys, seed) >> np.uint32(8)
    return (h.astype(np.float32) * np.float32(1.0 / (1 << 24))
            + np.float32(0.5 / (1 << 24)))


def to_bf16(x) -> np.ndarray:
    """float32 values rounded to the nearest bfloat16 (ties to even)."""
    b = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


def fvalues(objective, w, dtype=np.float32) -> np.ndarray:
    """f(w) of one objective ``[kind, param]`` of the configuration."""
    kind, param = objective[0], float(objective[1])
    w = np.asarray(w, dtype)
    if kind == "sum":
        return w
    if kind == "count":
        return (w > 0).astype(dtype)
    if kind == "thresh":
        return (w >= param).astype(dtype)
    if kind == "cap":
        return np.minimum(w, dtype(param))
    if kind == "moment":
        return np.where(w > 0, np.power(np.maximum(w, dtype(1e-30)),
                                        dtype(param)), dtype(0)).astype(dtype)
    raise ValueError(f"no statistic {kind!r}")


def max_weights(chunks, ids_log2: int) -> np.ndarray:
    """Each id's largest weight over the chunks (0 where never seen)."""
    wmax = np.zeros(1 << ids_log2, np.float32)
    for keys, weights in chunks:
        np.maximum.at(wmax, keys, weights)
    return wmax


def predicate_match(keys, table) -> np.ndarray:
    """bool [B, n]: the wire rows ``table`` [B, 6] tested on ``keys``."""
    k = np.asarray(keys, np.int64)[None, :]
    t = np.asarray(table, np.int64)
    lo, hi, mask, want, salt, flags = (t[:, i:i + 1] for i in range(6))
    out = np.empty((t.shape[0], k.shape[1]), bool)
    for b in range(t.shape[0]):
        v = k[0]
        if flags[b, 0] & 1:
            v = (hash_u32(v, int(salt[b, 0])) >> np.uint32(1)).astype(
                np.int64)
        out[b] = ((v >= lo[b, 0]) & (v <= hi[b, 0])
                  & ((v & mask[b, 0]) == want[b, 0]) & (k[0] >= 0))
    return out


@dataclasses.dataclass
class Sample:
    """The multi-objective sample: members sorted by key."""

    keys: np.ndarray         # int64 [m]
    weights: np.ndarray      # float32 [m]
    probs: np.ndarray        # float64 [m], p^(F)
    taus: np.ndarray         # float64 [nf]
    objectives: list

    def answers(self, table, fs=None) -> np.ndarray:
        """Horvitz-Thompson answers [|fs|, B] of the wire rows ``table``;
        ``fs`` indexes the objectives (all by default)."""
        fs = range(len(self.objectives)) if fs is None else fs
        hit = predicate_match(self.keys, table).astype(np.float64)  # [B, m]
        contrib = np.stack([fvalues(self.objectives[f], self.weights,
                                    np.float64) / self.probs for f in fs])
        return contrib @ hit.T


def sample(wmax: np.ndarray, objectives, hash_seed: int,
           bf16: bool = False) -> Sample:
    """The sample of the ids' weights ``wmax`` (dense over the id space)."""
    keys = np.flatnonzero(wmax > 0)
    w = wmax[keys]
    if bf16:
        w = to_bf16(w)
    with np.errstate(divide="ignore"):     # u = 1 rounds up: r = +inf
        r = -np.log1p(-uniform01(keys, hash_seed))
    prob = np.zeros(keys.shape[0], np.float64)
    taus = []
    for obj in objectives:
        kf = int(obj[2])
        fv = fvalues(obj, w)
        seeds = np.where(fv > 0, r / np.maximum(fv, np.float32(1e-30)),
                         np.float32(np.inf)).astype(np.float32)
        n_fin = int(np.count_nonzero(np.isfinite(seeds)))
        if n_fin <= kf:                       # every key is a member
            members = np.flatnonzero(np.isfinite(seeds))
            tau = np.inf
        else:
            # the k_f + 1 first by (seed, key) lie among the seeds up to
            # the (k_f+1)-th smallest value, ties at it included
            cut = np.partition(seeds, kf)[kf]
            cand = np.flatnonzero(seeds <= cut)
            cand = cand[np.lexsort((keys[cand], seeds[cand]))]
            members, tau = cand[:kf], float(seeds[cand[kf]])
        p = (np.ones(members.shape[0]) if np.isinf(tau) else
             -np.expm1(-fvalues(obj, w[members], np.float64) * tau))
        prob[members] = np.maximum(prob[members], p)
        taus.append(tau)
    sel = np.flatnonzero(prob > 0)
    return Sample(keys[sel].astype(np.int64), w[sel], prob[sel],
                  np.asarray(taus), [list(o[:2]) for o in objectives])


def member_diff(a_keys, b_keys) -> int:
    """Keys in one sample and not in the other."""
    return int(np.setxor1d(np.unique(np.asarray(a_keys, np.int64)),
                           np.unique(np.asarray(b_keys, np.int64))).size)


def prob_gap(ref: Sample, keys, probs) -> float:
    """Largest relative gap of the inclusion probabilities of the keys
    both samples hold; 0 when they share none."""
    _, i, j = np.intersect1d(ref.keys, np.asarray(keys, np.int64),
                             return_indices=True)
    if not i.size:
        return 0.0
    want = ref.probs[i]
    return _worst(np.abs(np.asarray(probs, np.float64)[j] - want) / want)


def answer_gap(want, got) -> float:
    """Largest relative gap of answers from the reference's; a gap on a
    segment the reference answers 0 counts in full."""
    want = np.asarray(want, np.float64)
    got = np.asarray(got, np.float64)
    if not want.size:
        return 0.0
    return _worst(np.abs(got - want) / np.maximum(np.abs(want), 1e-30))


def _worst(gaps) -> float:
    """The largest gap; a NaN or an infinite one reads as the largest
    float, so that the result line stays valid JSON."""
    top = np.finfo(np.float64).max
    return float(np.max(np.where(np.isfinite(gaps), gaps, top)))

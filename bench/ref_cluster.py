"""Plain reference of the clustering service's sample and scores.

The semantics, after arXiv:1509.07445 §7: point x (key = its index) gets
the anchor upper-bound weight v_x = max_a (d(x, a) + eps)^mu / norm_a,
where the m anchors are a farthest-point traversal of the first fitted
chunk from its point 0, eps = 1e-3 x the mean point-anchor distance of
that chunk plus 1e-12, and norm_a the sum of (d + eps)^mu over it. The
sample is the bottom-k of r_x / v_x, with r_x = -ln(1 - u_x) and u_x the
keyed 24-bit hash of x; tau is the (k+1)-th smallest seed, the members
are the seeds below it, p_x = 1 - exp(-v_x tau). The cost of a centre
set C is the sum over members of min_{c in C} d(x, c)^mu / p_x.

Distances for the weights are taken as the configuration states them:
float32, |x|^2 + |a|^2 - 2 x.a contracted at HIGHEST precision (float32
products; the TPU's default rounds a float32 matmul's operands to
bfloat16). The scores are computed in float64 from the points'
differences. The points come from the benchmark's generator; nothing is
taken from the program. ``bf16=True`` is the control: the same reference
with every point and every centre rounded to bfloat16, the next
precision below the float32 the configuration states.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = 0x9E3779B9
_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def to_bf16(x):
    """float32 values rounded to the nearest bfloat16 (ties to even), as
    float32. Done on the bits: a compiler may drop a convert to bfloat16
    and back as excess precision."""
    b = jax.lax.bitcast_convert_type(jnp.asarray(x, jnp.float32), jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def mismatch(ref_keys, got_keys) -> int:
    """Keys in one sample and not in the other."""
    a = np.unique(np.asarray(ref_keys, np.int64))
    b = np.unique(np.asarray(got_keys, np.int64))
    return int(np.setxor1d(a, b, assume_unique=True).size)


def _sqd(x, y):
    dots = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    xn = jnp.sum(x * x, axis=1)[:, None]
    yn = jnp.sum(y * y, axis=1)[None, :]
    return jnp.maximum(xn + yn - 2.0 * dots, 0.0)


@partial(jax.jit, static_argnames=("m",))
def _anchors(x, *, m):
    idx = [jnp.int32(0)]
    d = jnp.sqrt(_sqd(x, x[:1]))[:, 0]
    for _ in range(1, m):
        nxt = jnp.argmax(d).astype(jnp.int32)
        idx.append(nxt)
        d = jnp.minimum(d, jnp.sqrt(_sqd(x, x[nxt][None]))[:, 0])
    return jnp.stack(idx)


@partial(jax.jit, static_argnames=("mu",))
def _normalizer(x, anchors, *, mu):
    d = jnp.sqrt(_sqd(x, anchors))
    eps = jnp.mean(d) * 1e-3 + 1e-12
    return eps, jnp.sum(jnp.power(d + eps, mu), axis=0)


@partial(jax.jit, static_argnames=("mu", "hash_seed"))
def _seeds(x, keys, anchors, eps, norm, *, mu, hash_seed):
    d = jnp.sqrt(_sqd(x, anchors))
    v = jnp.max(jnp.power(d + eps, mu) / norm[None, :], axis=1)
    s = jnp.uint32(hash_seed & 0xFFFFFFFF)
    h = keys.astype(jnp.uint32) + jnp.uint32(_GOLDEN) + s
    for _ in range(2):
        h = h ^ (h >> 16)
        h = h * jnp.uint32(_M1)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(_M2)
        h = h ^ (h >> 16)
        if _ == 0:
            h = h ^ (s * jnp.uint32(_M1) + jnp.uint32(1))
    u = ((h >> 8).astype(jnp.int32).astype(jnp.float32)
         * jnp.float32(1.0 / (1 << 24)) + jnp.float32(0.5 / (1 << 24)))
    r = -jnp.log1p(-u)
    return jnp.where(v > 0, r / jnp.maximum(v, 1e-30), jnp.inf), v


class ClusterSample:
    def __init__(self, keys, coords, probs, mu, bf16=False):
        self.keys = keys           # int64 [m], sorted
        self.coords = coords       # float64 [m, d]
        self.probs = probs         # float64 [m]
        self.mu = float(mu)
        self.bf16 = bf16           # centres are rounded as the points were

    def costs(self, center_sets) -> np.ndarray:
        """float64 costs of each [c, d] centre set."""
        out = np.zeros(len(center_sets))
        inv = 1.0 / self.probs
        for i, c in enumerate(center_sets):
            if self.bf16:
                c = to_bf16(c)
            c = np.asarray(c, np.float64)
            d2 = ((self.coords[:, None, :] - c[None, :, :]) ** 2).sum(-1)
            out[i] = float((np.power(d2.min(axis=1), 0.5 * self.mu)
                            * inv).sum())
        return out


def reference(points, chunk: int, k: int, mu: float, n_anchors: int,
              hash_seed: int, bf16: bool = False) -> ClusterSample:
    """The sample of ``points`` (a [n, d] device array), fitted in chunks
    of ``chunk`` points with the anchors frozen at the first; with
    ``bf16`` every point is first rounded to bfloat16 (the control)."""
    n = points.shape[0]
    if bf16:
        points = to_bf16(points)
    first = points[:min(chunk, n)]
    anchors = first[_anchors(first, m=min(n_anchors, first.shape[0]))]
    eps, norm = _normalizer(first, anchors, mu=float(mu))
    seeds, vs = [], []
    for s in range(0, n, chunk):
        x = points[s:s + chunk]
        keys = jnp.arange(s, s + x.shape[0], dtype=jnp.int32)
        sd, v = _seeds(x, keys, anchors, eps, norm, mu=float(mu),
                       hash_seed=int(hash_seed))
        seeds.append(np.asarray(sd, np.float64))
        vs.append(np.asarray(v, np.float64))
    seeds = np.concatenate(seeds)
    v = np.concatenate(vs)
    order = np.argsort(seeds, kind="stable")[:k + 1]
    tau = seeds[order[k]] if order.size > k else np.inf
    keys = np.sort(order[seeds[order] < tau])
    probs = -np.expm1(-v[keys] * tau)
    coords = np.asarray(points[jnp.asarray(keys)], np.float64)
    return ClusterSample(keys.astype(np.int64), coords, probs, mu, bf16)

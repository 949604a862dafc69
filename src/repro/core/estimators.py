"""Inverse-probability (Horvitz–Thompson) estimators for segment f-statistics.

Q^(g, H) = sum_{x in S ∩ H} g(w_x) / p_x     (paper Eq. 2 / Eq. 5)

Unbiased whenever g(w) > 0 => p > 0; nonnegative always. CV guarantees:
Thm 2.1 (single objective), Thm 3.1 (multi-objective), §5.1 (universal).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .funcs import StatFn


def estimate(f: StatFn, weights, probs, member, segment=None):
    """Q^(f, H). ``segment``: bool mask for H (None = whole key space)."""
    sel = member if segment is None else (member & segment)
    contrib = jnp.where(sel, f(weights) / jnp.maximum(probs, 1e-30), 0.0)
    return jnp.sum(contrib)


def estimate_segments(f: StatFn, weights, probs, member, segment_ids,
                      num_segments: int):
    """Q^(f, H_j) for a partition into ``num_segments`` segments at once."""
    contrib = jnp.where(member, f(weights) / jnp.maximum(probs, 1e-30), 0.0)
    return jax.ops.segment_sum(contrib, segment_ids,
                               num_segments=num_segments)


def estimate_many(fs, weights, probs, member, segments):
    """Q^(f_i, H_b) for |F| objectives x B (possibly overlapping) segments.

    fs: sequence of StatFn; segments: bool [B, n] (one mask row per segment,
    unlike ``estimate_segments``'s disjoint partition). Returns [|F|, B].
    One |F| x n contribution matrix and one matmul against the segment mask
    — the XLA mirror of the single-launch segquery kernel.
    """
    probs = jnp.asarray(probs, jnp.float32)
    ht = jnp.where(member, 1.0 / jnp.maximum(probs, 1e-30), 0.0)
    contrib = jnp.stack([f(weights) for f in fs]) * ht          # [F, n]
    # HIGHEST: the TPU's default f32 matmul rounds contrib to bf16
    return jnp.matmul(contrib, jnp.asarray(segments).astype(jnp.float32).T,
                      precision=jax.lax.Precision.HIGHEST)


def exact(f: StatFn, weights, active, segment=None):
    """Ground-truth Q(f, H) for validation."""
    sel = active if segment is None else (active & segment)
    return jnp.sum(jnp.where(sel, f(weights), 0.0))


def exact_segments(f: StatFn, weights, active, segment_ids, num_segments: int):
    contrib = jnp.where(active, f(weights), 0.0)
    return jax.ops.segment_sum(contrib, segment_ids,
                               num_segments=num_segments)


def cv_bound(q_rel: float, k: int, rho: float = 1.0) -> float:
    """Paper CV upper bound sqrt(rho / (q * (k-1))) (bottom-k variant)."""
    return float(jnp.sqrt(rho / (max(q_rel, 1e-30) * max(k - 1, 1))))

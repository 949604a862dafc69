"""High-level jit'd entry points composing the Pallas kernels into the
paper's sampling operations. ``interpret=None`` auto-detects the backend
(interpret mode on CPU, compiled Mosaic on TPU); pass an explicit bool to
override.

The multi-objective bottom-k chain (fused seeds, batched block select,
then the membership rule) is ``core.multi_sketch.multisketch_select``
with ``use_kernels=True``; ``statfn_of`` maps its seeds kernel's
(kind, param) objective encoding back to a StatFn.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.funcs import StatFn
from repro.core.hashing import rank_of, uniform01
from .rankcount import rank_counts

# objective encoding for the seeds kernel
SUM, COUNT, THRESH, CAP, MOMENT = 0, 1, 2, 3, 4

_KIND_NAMES = {0: "sum", 1: "count", 2: "thresh", 3: "cap", 4: "moment"}


def statfn_of(kind: int, param: float) -> StatFn:
    """The core StatFn equivalent of a (kind, param) kernel objective."""
    return StatFn(_KIND_NAMES[kind], float(param))


@partial(jax.jit, static_argnames=("k", "scheme", "seed", "interpret"))
def universal_capping_kernel(keys, weights, active, k: int, scheme="ppswor",
                             seed=0, interpret=None):
    """S^(C,k) membership via the blocked rank-count kernel (Lemma 6.3).

    Returns (member, hl) — membership exact; probabilities follow the
    candidate pass of core.capping (host side, |candidates| x |candidates|).
    """
    w = jnp.asarray(weights, jnp.float32)
    act = jnp.asarray(active, bool) & (w > 0)
    u = uniform01(keys, seed)
    r = rank_of(u, scheme)
    rw = jnp.where(act, r / jnp.maximum(w, 1e-30), jnp.float32(jnp.inf))
    # h uses u as the order statistic; l uses r/w  (DESIGN.md §3)
    h, l = rank_counts(jnp.where(act, w, 0.0), u, rw, act,
                       interpret=interpret)
    hl = h + l
    return act & (hl < k), jnp.minimum(hl, k + 1)

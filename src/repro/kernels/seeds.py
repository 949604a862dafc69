"""Fused f-seed computation kernel (paper §2.2 hot loop).

Computing a multi-objective sample applies |F| functions to every element
(paper §3.3: Omega(|F| n) lower bound). The reference path materializes
u_x, r_x and each f(w_x) in HBM separately; this kernel fuses
hash -> u -> r -> { r / f_j(w) } for all objectives into one VMEM-resident
pass: each (8x128-aligned) block of keys/weights is read once from HBM and
|F| seed rows are written once — the arithmetic-intensity fix for what is
otherwise a purely bandwidth-bound loop.

``fused_seeds_fvals`` additionally emits the f-values f_j(w_x) themselves
(already computed inside the kernel for the seed division), so the
downstream conditional-probability step of the batched multi-objective
pipeline needs no per-objective recomputation on the host.

Objectives are compiled in as (kind, param) pairs: kind 0=sum, 1=count,
2=thresh(T), 3=cap(T), 4=moment(p).

Inputs of any length are auto-padded to a BLOCK multiple with inactive
entries (seed = +inf, fval = 0) and the outputs sliced back to n.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels._util import pad_tail, resolve_interpret, round_up

_GOLDEN = np.uint32(0x9E3779B9)  # numpy scalars fold into the kernel
BLOCK = 1024  # 8 sublanes x 128 lanes


def _mix(h):
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _fval(kind: int, param: float, w):
    if kind == 0:
        return w
    if kind == 1:
        return (w > 0).astype(jnp.float32)
    if kind == 2:
        return (w >= param).astype(jnp.float32)
    if kind == 3:
        return jnp.minimum(w, param)
    return jnp.where(w > 0, jnp.power(jnp.maximum(w, 1e-30), param), 0.0)


def _seeds_kernel(keys_ref, w_ref, act_ref, *out_refs, objectives,
                  scheme: str, seed: int, want_fvals: bool):
    out_ref = out_refs[0]
    k = keys_ref[...].astype(jnp.uint32)
    w = w_ref[...].astype(jnp.float32)
    act = act_ref[...] != 0
    c1 = np.uint32((0x9E3779B9 + seed) & 0xFFFFFFFF)
    c2 = np.uint32((seed * 0x85EBCA6B + 1) & 0xFFFFFFFF)
    h = _mix(k + c1)
    h = _mix(h ^ c2)
    # Mosaic has no uint32 -> f32 cast; the 24-bit value is exact in int32
    u = ((h >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32)
         * np.float32(1.0 / (1 << 24)))
    u = u + np.float32(0.5 / (1 << 24))
    r = -jnp.log1p(-u) if scheme == "ppswor" else u
    for j, (kind, param) in enumerate(objectives):
        fv = _fval(kind, param, w)
        ok = act & (fv > 0)
        out_ref[j, :] = jnp.where(ok, r / jnp.maximum(fv, 1e-30),
                                  jnp.float32(jnp.inf))
        if want_fvals:
            out_refs[1][j, :] = jnp.where(act, fv, 0.0)


@partial(jax.jit, static_argnames=("objectives", "scheme", "seed",
                                   "interpret", "want_fvals"))
def _fused_seeds(keys, weights, active, objectives, scheme, seed,
                 interpret, want_fvals: bool):
    if scheme not in ("ppswor", "priority"):
        raise ValueError(
            f"unknown scheme {scheme!r} (want 'priority' or 'ppswor')")
    interpret = resolve_interpret(interpret)
    n = keys.shape[0]
    npad = round_up(n, BLOCK)
    keys = pad_tail(keys.astype(jnp.int32), npad, 0)
    weights = pad_tail(weights.astype(jnp.float32), npad, 0.0)
    act = pad_tail(active.astype(jnp.int32), npad, 0)
    nf = len(objectives)
    grid = (npad // BLOCK,)
    out_specs = [pl.BlockSpec((nf, BLOCK), lambda i: (0, i))]
    out_shape = [jax.ShapeDtypeStruct((nf, npad), jnp.float32)]
    if want_fvals:
        out_specs.append(pl.BlockSpec((nf, BLOCK), lambda i: (0, i)))
        out_shape.append(jax.ShapeDtypeStruct((nf, npad), jnp.float32))
    outs = pl.pallas_call(
        partial(_seeds_kernel, objectives=tuple(objectives), scheme=scheme,
                seed=seed, want_fvals=want_fvals),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
            pl.BlockSpec((BLOCK,), lambda i: (i,)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="fused_seeds",
    )(keys, weights, act)
    if want_fvals:
        return outs[0][:, :n], outs[1][:, :n]
    return outs[0][:, :n]


def fused_seeds(keys, weights, active, objectives, scheme="ppswor", seed=0,
                interpret=None):
    """keys,(weights,active): [n] -> seeds [|F|, n]; any n (auto-padded).

    objectives: tuple of (kind:int, param:float).
    """
    return _fused_seeds(keys, weights, active, tuple(objectives), scheme,
                        seed, interpret, False)


def fused_seeds_fvals(keys, weights, active, objectives, scheme="ppswor",
                      seed=0, interpret=None):
    """Like :func:`fused_seeds` but returns (seeds [|F|,n], fvals [|F|,n]).

    fvals[j] = f_j(w) masked to 0 on inactive keys — exactly the values the
    conditional-probability step (core.bottomk.conditional_prob) consumes,
    produced in the same single launch (one extra VMEM->HBM write, no extra
    read).
    """
    return _fused_seeds(keys, weights, active, tuple(objectives), scheme,
                        seed, interpret, True)

"""Sampled gradient exchange — the paper's technique attacking the
COLLECTIVE roofline term (DESIGN.md §2.1).

Standard multi-pod data parallelism all-reduces dense gradients across the
"pod" axis (cross-DCN: the slowest link). Here each DEVICE communicates a
FIXED-SIZE multi-objective bottom-k sample of ITS SHARD of the pod-local
gradient:

  keys    = (pod, device, coordinate) — distinct across pods/devices, so the
            union of per-shard samples is a valid weighted data set (§2.5
            composability — the merge is exact for the union's estimator);
  weights = |g_i| (normalized per shard);
  F       = {(sum, k), (cap_c, k), (count, k)} — one coordinated sample
            serves the gradient estimate (sum), heavy-hitter-robust mass
            (cap), and support statistics simultaneously (Thm 3.1);
  wire    = a fixed 3k-slot MultiSketch slab (core.multi_sketch wire
            format; keys/weights/probs/valid gathered, seeds/taus local)
            per device pair over DCN;
  merge   = own pod's shard stays EXACT; remote pods' contributions are HT
            estimates (Eq. 5) — unbiased for the pod-mean gradient with
            strictly less variance than sampling both sides.

Structure: two sibling shard_maps (sdy forbids pod collectives nested under
a pod-manual region):
  sm1  manual{pod}:             forward/backward with auto TP inside; the
                                returned grads are pod-VARYING (declared
                                replicated with check_vma=False — consumed
                                only by sm2).
  sm2  manual{pod,data,model}:  per-device-shard sampling, pod all_gather of
                                sketches, HT merge. Small leaves go dense
                                (pmean) — their bytes are negligible.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import cap, COUNT, SUM
from repro.core.multi_sketch import (MultiSketch, MultiSketchSpec,
                                     multisketch_select)


def _leaf_spec(k: int, cap_frac: float, scheme: str) -> MultiSketchSpec:
    """The coordinated objective set F of the gradient exchange."""
    return MultiSketchSpec(
        objectives=((SUM, k), (cap(cap_frac), k), (COUNT, k)),
        scheme=scheme, capacity=3 * k)


def _sample_leaf(g, k: int, seed, cap_frac: float,
                 scheme: str = "ppswor") -> MultiSketch:
    """Multi-objective bottom-k sample of one (shard of a) gradient leaf,
    as a fixed-capacity MultiSketch wire slab (3k slots, members first).

    Selection is core.multi_sketch.multisketch_select (pure-XLA path: this
    runs inside a fully-manual shard_map, and the per-(step, pod) reseed is
    traced). The sketch's ``weights`` slab carries the SIGNED gradient
    entries — probabilities were computed from the normalized |g| weights —
    so the HT merge reads contributions directly off the wire. Aux slots
    are dropped: pods hold disjoint key spaces, so the exchange never
    re-selects across pods (§2.5 composability keeps the union estimator
    exact); only members carry HT mass.
    """
    flat = g.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    w = jnp.abs(flat)
    wmax = jnp.maximum(jnp.max(w), 1e-30)
    wn = w / wmax                                   # weights in (0,1]
    spec = _leaf_spec(min(k, n), cap_frac, scheme)
    keys = jnp.arange(n, dtype=jnp.int32)
    member, prob, _aux, seeds, taus = multisketch_select(
        spec, keys, wn, (wn > 0), use_kernels=False, seed=seed)

    # compact members into 3k fixed slots (members first)
    slots = spec.cap
    order = jnp.argsort(~member)                    # members first
    take = order[:slots]
    valid = member[take]
    return MultiSketch(
        keys=jnp.where(valid, take, -1).astype(jnp.int32),
        weights=jnp.where(valid, flat[take], 0.0),  # signed payload
        probs=jnp.where(valid, prob[take], 1.0),
        seeds=jnp.where(valid[None, :], seeds[:, take], jnp.inf),
        member=valid,
        aux=jnp.zeros_like(valid),
        valid=valid,
        taus=taus)


def _merge_leaf(idx, val, prob, valid, n, npods):
    """HT-estimate the mean gradient from gathered per-pod sketch slabs
    (all-sampled variant; benchmarks use this single-pod)."""
    contrib = jnp.where(valid, val / jnp.maximum(prob, 1e-30), 0.0)
    dense = jnp.zeros((n,), jnp.float32)
    dense = dense.at[jnp.maximum(idx, 0).reshape(-1)].add(contrib.reshape(-1))
    return dense / npods


def compressed_grads_fn(compute_grads, mesh, *, axis: str = "pod",
                        k: int = 512, cap_frac: float = 0.01, seed: int = 17,
                        min_size: int = 65536):
    """Wrap (params, batch) -> (loss, metrics, grads) so the cross-POD
    gradient reduction is the paper's sampled exchange instead of a dense
    all-reduce. Returns None on single-pod meshes."""
    if axis not in mesh.axis_names:
        return None
    npods = mesh.shape[axis]
    all_axes = set(mesh.axis_names)

    def wrapped(params, batch, step, param_specs):
        # ---- sm1: pod-local grads (auto TP/DP inside) -------------------
        def grads_body(params, batch):
            loss, metrics, grads = compute_grads(params, batch)
            return (jax.lax.pmean(loss, axis),
                    jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics),
                    grads)  # pod-varying; consumed only by sm2

        bspec = jax.tree.map(lambda _: P(axis), batch)
        rep = jax.tree.map(lambda _: P(), params)
        loss, metrics, grads = jax.shard_map(
            grads_body, mesh=mesh,
            in_specs=(rep, bspec, ),
            out_specs=(P(), P(), rep),
            axis_names={axis}, check_vma=False)(params, batch)

        # ---- sm2: fully-manual sampled exchange -------------------------
        flat, treedef = jax.tree_util.tree_flatten(grads)
        flat_specs = jax.tree_util.tree_leaves(
            param_specs, is_leaf=lambda x: isinstance(x, P))

        def exchange(step_, *leaves):
            pod = jax.lax.axis_index(axis)
            out = []
            for j, g in enumerate(leaves):
                if g.size < min_size:
                    out.append(jax.lax.pmean(g, axis))
                    continue
                s = (jnp.uint32(seed) + jnp.uint32(j * 1_000_003)
                     + jnp.uint32(pod) * jnp.uint32(7919)
                     + step_.astype(jnp.uint32))
                flat_g = g.reshape(-1)
                n = flat_g.shape[0]
                sk = _sample_leaf(flat_g, k, s, cap_frac)
                # ship the sketch's HT slabs (keys/weights/probs/valid);
                # seeds/taus are recomputable and stay pod-local
                gi = jax.lax.all_gather(sk.keys, axis)
                gv = jax.lax.all_gather(sk.weights, axis)
                gp = jax.lax.all_gather(sk.probs, axis)
                gm = jax.lax.all_gather(sk.valid, axis)
                total = jnp.zeros((n,), jnp.float32)
                est_self = jnp.zeros((n,), jnp.float32)
                for p_ in range(npods):
                    contrib = jnp.where(
                        gm[p_], gv[p_] / jnp.maximum(gp[p_], 1e-30), 0.0)
                    est_p = jnp.zeros((n,), jnp.float32).at[
                        jnp.maximum(gi[p_], 0)].add(contrib)
                    total = total + est_p
                    est_self = est_self + jnp.where(pod == p_, est_p, 0.0)
                dense = (total - est_self
                         + flat_g.astype(jnp.float32)) / npods
                out.append(dense.reshape(g.shape).astype(g.dtype))
            return tuple(out)

        specs = tuple(flat_specs)
        new_flat = jax.shard_map(
            exchange, mesh=mesh,
            in_specs=(P(),) + specs, out_specs=specs,
            axis_names=all_axes, check_vma=False)(step, *flat)
        grads = jax.tree_util.tree_unflatten(treedef, new_flat)
        return loss, metrics, grads

    return wrapped

#!/usr/bin/env python3
"""Bring-up smoke of the sampling service on an accelerator chip.

Drives the served path once, through the entry points a user calls, at
deployment sizes, and checks every answer against two references:

  Phase A  a durable ``EnginePool`` stream (WAL + snapshots, 4 shards,
           ppswor over SUM / COUNT / thresh / cap / moment(2), k each)
           absorbs Zipf-keyed, Pareto-weighted chunks, then answers one
           coalesced batch of key-range and hash-fraction predicates
           through ``submit``/``pump`` plus a few single ``query`` calls.
           Reference 1: a twin stream on the XLA path (``use_kernels=
           False``) fed the same chunks — identical retained keys,
           estimates equal to 1e-5. Reference 2: exact numpy segment sums
           (a repeated key counts once, at its largest weight) — every
           estimate of a segment holding >= 1% of its objective's mass
           within 4x the Thm 3.1 CV bound.
  Phase B  ``ClusterEngine.fit`` over Gaussian-mixture points, then
           ``service_costs`` for Q candidate centre sets at mu = 1 and 2,
           against ``exact_service_costs`` within 4x the CV bound.
  --chips 4  only the multi-chip path: the sharded build over a 4-device
           mesh (``sharded_multisketch`` and
           ``SegmentQueryEngine.from_sharded``) against a one-shot build
           of the same data on one chip.

Each jitted fold and query program is compiled once more for the check
that its kernels are Mosaic custom calls, and the kernels found are
printed beside the ones the size plans predict. Wall seconds are of this
one run, not metrics.

The last line of standard output is one JSON object with ``"ok": true``
and the device as JAX reports it. It is printed only when every check
passed on a TPU with compiled (not interpreted) kernels; otherwise the
script exits non-zero. Without an accelerator it stops before any phase,
unless ``--cpu-rehearsal`` asks for the phases at small sizes (the run
then still exits non-zero).

    python chip_smoke.py                   # one chip, real sizes
    python chip_smoke.py --chips 4         # the four-chip sharded build
    JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal --chunks 4 \\
        --chunk-log2 12 --k 32 --points-log2 12 --queries 16 \\
        --centers 8 --dim 8 --cluster-k 64
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.core as C  # noqa: E402
from repro.core import multi_sketch as MS  # noqa: E402
from repro.kernels import default_interpret  # noqa: E402
from repro.kernels.blockselect import select_plan  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.pool import FRESH, EnginePool  # noqa: E402

_PERM_MULT = 0x9E3779B1          # odd: a bijection on [0, 2^m)
_CUSTOM_CALL = re.compile(
    r'%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"')


def log(*a):
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# data (made anew from --seed in every run)
# ---------------------------------------------------------------------------

def zipf_keys(rng, n: int, ids_log2: int, s: float) -> np.ndarray:
    """n keys, Zipf(s) by rank over 2^ids_log2 ids (continuous inverse-CDF
    approximation of the bounded law), ranks scattered over the id space
    by a multiplicative bijection."""
    N = float(1 << ids_log2)
    u = rng.random(n)
    x = (1.0 - u * (1.0 - N ** (1.0 - s))) ** (1.0 / (1.0 - s))
    rank = np.minimum(x.astype(np.int64), (1 << ids_log2)) - 1
    return ((rank * _PERM_MULT) & ((1 << ids_log2) - 1)).astype(np.int32)


def stream_chunk(seed: int, i: int, n: int, ids_log2: int):
    rng = np.random.default_rng([seed, i])
    keys = zipf_keys(rng, n, ids_log2, 1.1)
    weights = (rng.pareto(1.5, n) + 1.0).astype(np.float32)
    return keys, weights


def spec_of(k: int, seed: int):
    return C.MultiSketchSpec(
        objectives=((C.SUM, k), (C.COUNT, k), (C.thresh(2.0), k),
                    (C.cap(4.0), k), (C.moment(2.0), k)),
        scheme="ppswor", seed=seed)


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------

def check_serving(pool, streams, receipts, responses) -> list:
    """Problems the pool's fault tolerance would otherwise hide: a fold
    that did not apply, an answer that is not FRESH or carries an error,
    an open breaker, a fold backlog, a failed snapshot."""
    bad = []
    for i, r in enumerate(receipts):
        if not r.applied:
            bad.append(f"receipt {i} (seq {r.seq}) not applied")
    for label, r in responses:
        if r.status != FRESH or r.error is not None:
            bad.append(f"response {label}: {r.status} error={r.error}")
    for name in streams:
        st = pool.stats(name)
        if st["breaker_open"]:
            bad.append(f"stream {name}: breaker open")
        if st["pending"]:
            bad.append(f"stream {name}: {st['pending']} chunks pending")
        if st["snapshot_failures"]:
            bad.append(f"stream {name}: {st['snapshot_failures']} "
                       f"snapshot failures")
    return bad


def _sds(x):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)


def program_kernels(name, fn, args, kwargs, expect, failures):
    """Compile one jitted program as the run compiles it and list its
    Mosaic kernels; ``expect`` (a multiset of kernel names, empty for an
    XLA program) is what the size plans predict."""
    t0 = time.perf_counter()
    txt = fn.lower(*args, **kwargs).compile().as_text()
    secs = time.perf_counter() - t0
    found = collections.Counter(_CUSTOM_CALL.findall(txt))
    want = collections.Counter(expect)
    path = (", ".join(f"{k} x{v}" for k, v in sorted(found.items()))
            if found else "XLA path (no tpu_custom_call)")
    log(f"  program {name}: compile {secs:.2f}s; kernels: {path}")
    if found != want:
        failures.append(f"program {name}: kernels {dict(found)} != "
                        f"expected {dict(want)}")
    return secs


def fold_kernels(spec, n: int) -> list:
    """The kernels one kernel-path fold over n rows launches: fused seeds,
    the seed select and the compaction's priority pass, plus the block
    select of each select whose plan is 'block'."""
    out = ["fused_seeds", "retention_priority"]
    if select_plan(n, spec.kmax + 2) == "block":
        out.append("block_select")
    if select_plan(max(n, spec.cap + 1), spec.cap + 1) == "block":
        out.append("block_select")
    return out


def rel_close(a, b, rtol: float) -> bool:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return bool(np.all(np.abs(a - b) <= rtol * np.maximum(np.abs(a),
                                                          np.abs(b))
                       + 1e-30))


# ---------------------------------------------------------------------------
# exact references (numpy, independent of the code under test)
# ---------------------------------------------------------------------------

def _np_mix(h):
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h


def np_hash31(keys, salt: int) -> np.ndarray:
    """The 31-bit predicate hash (core.predicates.hash31), in numpy."""
    with np.errstate(over="ignore"):
        s = np.uint32(salt & 0xFFFFFFFF)
        h = _np_mix(keys.astype(np.uint32) + np.uint32(0x9E3779B9) + s)
        h = _np_mix(h ^ (s * np.uint32(0x85EBCA6B) + np.uint32(1)))
    return (h >> np.uint32(1)).astype(np.int64)


def np_fvals(w) -> np.ndarray:
    """f(w) of the five objectives of ``spec_of``, float64 [5, n]."""
    w = np.asarray(w, np.float64)
    return np.stack([w, (w > 0).astype(np.float64),
                     (w >= 2.0).astype(np.float64), np.minimum(w, 4.0),
                     np.where(w > 0, w * w, 0.0)])


def exact_segment_sums(keys, weights, preds) -> tuple:
    """(exact [5, B], totals [5]): each key once, at its largest weight."""
    order = np.lexsort((-weights, keys))
    sk = keys[order]
    first = np.ones(sk.shape[0], bool)
    first[1:] = sk[1:] != sk[:-1]
    uk = sk[first].astype(np.int64)
    fv = np_fvals(weights[order][first])                   # [5, u]
    totals = fv.sum(axis=1)
    csum = np.concatenate([np.zeros((5, 1)), np.cumsum(fv, axis=1)], axis=1)
    by_salt = {}
    out = np.zeros((5, len(preds)))
    for b, p in enumerate(preds):
        if p.on_hash:
            if p.salt not in by_salt:
                hv = np_hash31(uk, p.salt)
                o = np.argsort(hv, kind="stable")
                hs = np.concatenate([np.zeros((5, 1)),
                                     np.cumsum(fv[:, o], axis=1)], axis=1)
                by_salt[p.salt] = (hv[o], hs)
            vals, cs = by_salt[p.salt]
        else:
            vals, cs = uk, csum
        lo = np.searchsorted(vals, p.lo, side="left")
        hi = np.searchsorted(vals, p.hi, side="right")
        out[:, b] = cs[:, hi] - cs[:, lo]
    return out, totals


# ---------------------------------------------------------------------------
# phase A: the stream through EnginePool
# ---------------------------------------------------------------------------

def make_predicates(rng, n: int, ids_log2: int, hash_seed: int):
    """n/2 key ranges and n/2 coordinated hash fractions (4 salts). No
    salt equals the sketch's hash seed: that fraction would select keys by
    the sampling randomness itself (the keys with the smallest seeds), and
    its estimate would not be unbiased."""
    span = 1 << ids_log2
    preds = []
    for i in range(n):
        if i % 2 == 0:
            width = int(2 ** rng.uniform(ids_log2 - 10, ids_log2 - 1))
            lo = int(rng.integers(0, span - width))
            preds.append(C.key_range(lo, lo + width - 1))
        else:
            salt = hash_seed + 1 + int(rng.integers(0, 4))
            preds.append(C.hash_fraction(float(rng.uniform(0.02, 0.9)),
                                         salt=salt))
    return preds


def phase_a(args, state_root, failures):
    k, shards = args.k, args.shards
    n = 1 << args.chunk_log2
    spec = spec_of(k, args.seed)
    fs = tuple(f for f, _ in spec.objectives)
    log(f"phase A: EnginePool, {shards} shards, durable (WAL + snapshot "
        f"every {args.snapshot_every}); spec ppswor |F|={spec.nf} k={k} "
        f"cap={spec.cap}; {args.chunks} chunks x {n} events, keys "
        f"Zipf(1.1) over 2^{args.ids_log2} ids, weights Pareto(1.5); "
        f"{args.queries} predicates")
    # fold sizes: a shard fold (slab + chunk), the merged-slab fold of one
    # shard's slab, and the first read's full re-merge of every shard
    folds = {"shard_fold": spec.cap + n, "merged_fold": 2 * spec.cap,
             "full_remerge": (1 + shards) * spec.cap}
    log("  plans (seed select k+1=" f"{spec.kmax + 2} / compaction take "
        f"{spec.cap + 1}): " + "; ".join(
            f"{name} over {rows} rows: {select_plan(rows, spec.kmax + 2)} / "
            f"{select_plan(rows, spec.cap + 1)}"
            for name, rows in folds.items()))

    # -- every fold and query program the run dispatches ----------------
    leaves = _sds(MS.multisketch_empty(spec))
    chunk = (jax.ShapeDtypeStruct((n,), jnp.int32),
             jax.ShapeDtypeStruct((n,), jnp.float32),
             jax.ShapeDtypeStruct((n,), jnp.bool_))
    slab3 = (leaves.keys, leaves.weights, leaves.valid)
    stacked3 = tuple(jax.ShapeDtypeStruct((shards * spec.cap,), a.dtype)
                     for a in slab3)
    bq = 16                                   # the engines' b_quantum
    table_b = jax.ShapeDtypeStruct((-(-args.queries // bq) * bq, 6),
                                   jnp.int32)
    table_1 = jax.ShapeDtypeStruct((1, 6), jnp.int32)
    slab_q = (leaves.keys, leaves.weights, leaves.probs, leaves.member)
    compile_s = 0.0
    for uk in (True, False):
        tag = "kernels" if uk else "xla"
        compile_s += program_kernels(
            f"shard_fold[{tag}]", MS._absorb_jit, (leaves,) + chunk,
            dict(spec=spec, use_kernels=uk),
            fold_kernels(spec, folds["shard_fold"]) if uk else [], failures)
        for name, delta in (("merged_fold", slab3),
                            ("full_remerge", stacked3)):
            compile_s += program_kernels(
                f"{name}[{tag}]", MS._absorb_into_jit,
                tuple(leaves) + delta, dict(spec=spec, use_kernels=uk),
                fold_kernels(spec, folds[name]) if uk else [], failures)
        for qn, tb in (("batch", table_b), ("single", table_1)):
            compile_s += program_kernels(
                f"query_{qn}[{tag}]", MS._estimate_batch_jit,
                slab_q + (tb,),
                dict(fs=fs if qn == "batch" else fs[:1], use_kernels=uk),
                ["segment_query"] if uk else [], failures)
    compile_s += program_kernels(
        "finalize_probs", MS._finalize_probs_jit,
        (leaves.keys, leaves.weights, leaves.seeds, leaves.member,
         leaves.valid, leaves.taus), dict(spec=spec), [], failures)

    # -- the stream ------------------------------------------------------
    pool = EnginePool(durability_dir=tempfile.mkdtemp(prefix="pool-",
                                                      dir=state_root),
                      snapshot_every=args.snapshot_every)
    streams = ("kernels", "xla")
    engines = {"kernels": pool.create_stream("kernels", spec, shards=shards),
               "xla": pool.create_stream("xla", spec, shards=shards,
                                         use_kernels=False)}
    receipts, responses = [], []
    walls = {s: [] for s in streams}
    all_k, all_w = [], []
    t_gen = 0.0
    for i in range(args.chunks):
        t0 = time.perf_counter()
        keys, w = stream_chunk(args.seed, i, n, args.ids_log2)
        t_gen += time.perf_counter() - t0
        all_k.append(keys)
        all_w.append(w)
        for s in streams:
            t0 = time.perf_counter()
            receipts.append(pool.absorb(s, keys, w, shard=i % shards))
            walls[s].append(time.perf_counter() - t0)
            if i == 0:
                # a dashboard reads while it ingests: the first read builds
                # the merged slab (the full re-merge); from then on every
                # absorb maintains it (the merged-slab fold) and reads hit
                t0 = time.perf_counter()
                responses.append((f"{s}/first", pool.query(s, fs=fs[:1])))
                log(f"  first read[{s}] (one smoke run): "
                    f"{time.perf_counter() - t0:.3f}s")
    for s in streams:
        ws = walls[s]
        log(f"  absorb[{s}] (one smoke run): first chunk {ws[0]:.3f}s, "
            f"median of the rest "
            f"{np.median(ws[1:]) if len(ws) > 1 else float('nan'):.4f}s, "
            f"total {sum(ws):.2f}s")
    log(f"  data generation (set-up): {t_gen:.2f}s")

    rng = np.random.default_rng([args.seed, 1 << 20])
    preds = make_predicates(rng, args.queries, args.ids_log2, spec.seed)
    per_req = max(args.queries // 8, 1)
    got = {}
    for s in streams:
        t0 = time.perf_counter()
        futs = [pool.submit(s, predicates=preds[j:j + per_req])
                for j in range(0, len(preds), per_req)]
        pool.pump()
        rs = [f.result() for f in futs]
        log(f"  query batch[{s}] (one smoke run): {len(futs)} requests "
            f"coalesced in one pump, {time.perf_counter() - t0:.3f}s")
        responses += [(f"{s}/batch{j}", r) for j, r in enumerate(rs)]
        got[s] = np.concatenate([r.values for r in rs
                                 if r.values is not None], axis=1)
        singles = []
        for j in range(3):                   # SUM over three key ranges
            r = pool.query(s, fs=fs[:1], predicates=preds[2 * j])
            responses.append((f"{s}/single{j}", r))
            singles.append(None if r.values is None
                           else float(r.values[0, 0]))
        got[s + "/single"] = singles

    failures += check_serving(pool, streams, receipts, responses)
    if any(v.shape != (spec.nf, len(preds)) for k_, v in got.items()
           if not k_.endswith("/single")):
        failures.append("phase A: batch answers have the wrong shape")
        pool.close()
        return compile_s

    # reference 1: the XLA twin stream
    mk, mx = engines["kernels"].merged, engines["xla"].merged
    keys_k = np.sort(np.asarray(mk.keys)[np.asarray(mk.valid)])
    keys_x = np.sort(np.asarray(mx.keys)[np.asarray(mx.valid)])
    mem_k = np.sort(np.asarray(mk.keys)[np.asarray(mk.member)])
    mem_x = np.sort(np.asarray(mx.keys)[np.asarray(mx.member)])
    same_keys = (np.array_equal(keys_k, keys_x)
                 and np.array_equal(mem_k, mem_x))
    est_ok = (rel_close(got["kernels"], got["xla"], 1e-5)
              and rel_close(got["kernels/single"], got["xla/single"], 1e-5))
    log(f"  reference 1 (XLA twin stream): retained keys identical="
        f"{same_keys} ({keys_k.size} slots, {mem_k.size} members); "
        f"estimates within 1e-5 relative={est_ok}")
    if not same_keys:
        failures.append("phase A: kernel and XLA streams retain different "
                        "keys")
    if not est_ok:
        failures.append("phase A: kernel and XLA estimates differ > 1e-5")

    # reference 2: exact segment sums
    t0 = time.perf_counter()
    exact, totals = exact_segment_sums(np.concatenate(all_k),
                                       np.concatenate(all_w), preds)
    share = exact / totals[:, None]
    checked = share >= 0.01
    bound = np.array([[C.cv_bound(q, k) for q in row] for row in share])
    err = np.abs(got["kernels"] - exact) / np.maximum(exact, 1e-300)
    ratio = np.where(checked, err / bound, 0.0)
    singles_ok = all(
        v is not None and abs(v - exact[0, 2 * j])
        <= 4 * C.cv_bound(share[0, 2 * j], k) * exact[0, 2 * j]
        for j, v in enumerate(got["kernels/single"])
        if share[0, 2 * j] >= 0.01)
    log(f"  reference 2 (exact numpy sums, {time.perf_counter() - t0:.2f}s):"
        f" {int(checked.sum())} of {checked.size} estimates hold >= 1% of "
        f"their objective's mass; max |err| / CV bound = "
        f"{float(ratio.max()):.3f} (limit 4); single queries within "
        f"4x bound={singles_ok}")
    if float(ratio.max()) > 4.0 or not singles_ok:
        failures.append(f"phase A: estimate outside 4x the CV bound "
                        f"(max ratio {float(ratio.max()):.3f})")
    for s in streams:
        stt = pool.stats(s)
        log(f"  stream {s}: applied_seq={stt['applied_seq']} "
            f"quarantined={stt['quarantined']} merge_stats="
            f"{stt['merge_stats']}")
    pool.close()
    return compile_s


# ---------------------------------------------------------------------------
# phase B: clustering cost through ClusterEngine
# ---------------------------------------------------------------------------

def mixture_points(seed: int, n: int, dim: int, comps: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 2])
    means = rng.normal(0.0, 4.0, (comps, dim)).astype(np.float32)
    lab = rng.integers(0, comps, n)
    return means[lab] + rng.normal(0.0, 1.0, (n, dim)).astype(np.float32)


def exact_costs_chunked(X, table, rows: int = 8192) -> np.ndarray:
    """``exact_service_costs`` summed over point chunks (float64): the
    [Q*Cmax, n] distance matrix of the whole set would not fit."""
    total = np.zeros(np.shape(table.mu)[0], np.float64)
    tab = C.CostTable(*(jnp.asarray(x) for x in table))
    for s in range(0, X.shape[0], rows):
        total += np.asarray(C.exact_service_costs(X[s:s + rows], tab),
                            np.float64)
    return total


def phase_b(args, failures):
    from repro.kernels.servicecost import _service_cost_jit
    from repro.launch.cluster import ClusterEngine, _align_coords_delta
    n = 1 << args.points_log2
    log(f"phase B: ClusterEngine over {n} points in d={args.dim}, "
        f"{args.components}-component Gaussian mixture; sample k="
        f"{args.cluster_k}; Q={args.queries_b} sets of {args.centers} "
        f"centres at mu=1 and mu=2")
    t0 = time.perf_counter()
    X = mixture_points(args.seed, n, args.dim, args.components)
    log(f"  data generation (set-up): {time.perf_counter() - t0:.2f}s")

    spec = C.MultiSketchSpec(objectives=((C.SUM, args.cluster_k),),
                             scheme="ppswor", seed=args.seed)
    leaves = _sds(MS.multisketch_empty(spec))
    chunk = (jax.ShapeDtypeStruct((n,), jnp.int32),
             jax.ShapeDtypeStruct((n,), jnp.float32),
             jax.ShapeDtypeStruct((n,), jnp.bool_))
    cap, q, cm, d = spec.cap, args.queries_b, args.centers, args.dim
    f32 = jnp.float32
    table = C.CostTable(jax.ShapeDtypeStruct((q, cm, d), f32),
                        jax.ShapeDtypeStruct((q, cm), jnp.bool_),
                        jax.ShapeDtypeStruct((q,), f32),
                        jax.ShapeDtypeStruct((q,), f32),
                        jax.ShapeDtypeStruct((q,), jnp.int32))
    compile_s = program_kernels(
        "cluster_fold", MS._absorb_jit, (leaves,) + chunk,
        dict(spec=spec, use_kernels=True), fold_kernels(spec, cap + n),
        failures)
    compile_s += program_kernels(
        "align_coords", _align_coords_delta,
        (leaves.keys, leaves.keys, jax.ShapeDtypeStruct((cap, d), f32),
         chunk[0], jax.ShapeDtypeStruct((n, d), f32)), {}, [], failures)
    compile_s += program_kernels(
        "service_cost", _service_cost_jit,
        (jax.ShapeDtypeStruct((cap, d), f32), leaves.probs, leaves.member,
         table, None, None), {}, ["service_cost"], failures)

    t0 = time.perf_counter()
    eng = ClusterEngine.fit(X, k=args.cluster_k, mu=2.0, seed=args.seed)
    jax.block_until_ready(eng.sample())
    log(f"  fit (one smoke run): {time.perf_counter() - t0:.2f}s")
    rng = np.random.default_rng([args.seed, 3])
    sets = X[rng.integers(0, n, (q, cm))]
    Xd = jnp.asarray(X)
    for mu in (1.0, 2.0):
        tab = C.cost_table(sets, mu)
        t0 = time.perf_counter()
        est = np.asarray(eng.service_costs(tab), np.float64)
        t_q = time.perf_counter() - t0
        t0 = time.perf_counter()
        exact = exact_costs_chunked(Xd, tab)
        t_x = time.perf_counter() - t0
        ratio = float(np.max(np.abs(est - exact) / np.maximum(exact, 1e-300)
                             / C.cv_bound(1.0, args.cluster_k)))
        finite = bool(np.all(np.isfinite(est)))
        log(f"  mu={mu:g}: service_costs (one smoke run) {t_q:.3f}s; "
            f"exact reference {t_x:.2f}s; max |err| / CV bound = "
            f"{ratio:.3f} (limit 4); finite={finite}")
        if not finite or ratio > 4.0:
            failures.append(f"phase B: mu={mu:g} estimate outside 4x the "
                            f"CV bound (max ratio {ratio:.3f})")
    return compile_s


# ---------------------------------------------------------------------------
# --chips 4: the sharded build
# ---------------------------------------------------------------------------

def phase_sharded(args, failures):
    from repro.launch.mesh import make_mesh
    from repro.launch.query import SegmentQueryEngine
    from repro.launch.summary import (sharded_multisketch,
                                      sharded_multisketch_shards)
    devs = jax.devices()
    m = len(devs)
    per = 1 << args.shard_log2
    n = m * per
    spec = spec_of(args.k, args.seed)
    log(f"sharded build: {m} devices, {m} x {per} events (distinct keys, "
        f"Pareto(1.5) weights); spec ppswor |F|={spec.nf} k={args.k}")
    if m != 4:
        failures.append(f"--chips 4 needs 4 devices, found {m}")
    rng = np.random.default_rng([args.seed, 4])
    ids = np.arange(n, dtype=np.int64)
    keys = ((ids * _PERM_MULT) & ((1 << max(args.ids_log2,
                                             n.bit_length())) - 1))
    keys = keys.astype(np.int32)
    w = (rng.pareto(1.5, n) + 1.0).astype(np.float32)
    mesh = make_mesh((m,), ("data",))

    compile_s = program_kernels(
        "one_shot_build", MS._build_jit,
        (jax.ShapeDtypeStruct((n,), jnp.int32),
         jax.ShapeDtypeStruct((n,), jnp.float32),
         jax.ShapeDtypeStruct((n,), jnp.bool_)),
        dict(spec=spec, use_kernels=True),
        fold_kernels(spec, max(n, spec.kmax + 2)), failures)
    # the engine's first read re-merges the m adopted rows on one chip
    leaves = _sds(MS.multisketch_empty(spec))
    compile_s += program_kernels(
        "full_remerge", MS._absorb_into_jit,
        tuple(leaves) + tuple(jax.ShapeDtypeStruct((m * spec.cap,), a.dtype)
                              for a in (leaves.keys, leaves.weights,
                                        leaves.valid)),
        dict(spec=spec, use_kernels=True),
        fold_kernels(spec, (1 + m) * spec.cap), failures)

    t0 = time.perf_counter()
    stacked = sharded_multisketch_shards(spec, mesh, keys, w)
    jax.block_until_ready(stacked)
    rows = {s.device: int(s.index[0].start or 0)
            for s in stacked.keys.addressable_shards}
    log(f"  per-device rows (one smoke run {time.perf_counter() - t0:.2f}s):"
        f" {len(set(rows))} distinct devices hold rows "
        f"{sorted(rows.values())}")
    if len(set(rows)) != m or sorted(rows.values()) != list(range(m)):
        failures.append("sharded build rows are not one per device")
    t0 = time.perf_counter()
    eager = sharded_multisketch(spec, mesh, keys, w)
    eng = SegmentQueryEngine.from_sharded(spec, mesh, keys, w)
    lazy = eng.merged
    jax.block_until_ready((eager, lazy))
    log(f"  sharded_multisketch + from_sharded (one smoke run): "
        f"{time.perf_counter() - t0:.2f}s")

    # each sharded result against the one-chip build on its own path: the
    # eager build re-selects on XLA inside shard_map; the engine's first
    # read re-merges its rows through the fold kernels
    with jax.default_device(devs[0]):
        xla = C.multisketch_build(spec, keys, w, use_kernels=False)
        kern = C.multisketch_build(spec, keys, w)
    for label, sk, ref_sk, path in (
            ("sharded_multisketch", eager, xla, "XLA"),
            ("from_sharded merged", lazy, kern, "kernel")):
        same = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(sk, ref_sk))
        log(f"  {label} bit-identical to the one-chip {path} build: {same}")
        if not same:
            failures.append(f"{label} differs from the one-shot build")
    preds = make_predicates(np.random.default_rng([args.seed, 5]),
                            args.queries, max(args.ids_log2, n.bit_length()),
                            spec.seed)
    fs = tuple(f for f, _ in spec.objectives)
    est = eng.query_many(fs, preds)
    ref_k = C.multisketch_query_many(kern, fs, preds)
    ref = C.multisketch_query_many(xla, fs, preds, use_kernels=False)
    keys_same = np.array_equal(
        np.sort(np.asarray(kern.keys)[np.asarray(kern.valid)]),
        np.sort(np.asarray(xla.keys)[np.asarray(xla.valid)]))
    log(f"  estimates: engine == one-chip kernel build "
        f"{np.array_equal(est, ref_k)}; kernel and XLA builds retain the "
        f"same keys {keys_same}, estimates within 1e-5 "
        f"{rel_close(ref_k, ref, 1e-5)}")
    if not np.array_equal(est, ref_k):
        failures.append("sharded engine estimates differ from the one-shot "
                        "build")
    if not keys_same or not rel_close(ref_k, ref, 1e-5):
        failures.append("one-chip kernel build disagrees with the XLA build")
    return compile_s


# ---------------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip sharded build")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the phases even without an accelerator "
                         "(small sizes; the run still exits non-zero)")
    g = ap.add_argument_group("phase A")
    g.add_argument("--k", type=int, default=1024, help="k per objective")
    g.add_argument("--shards", type=int, default=4)
    g.add_argument("--chunks", type=int, default=32)
    g.add_argument("--chunk-log2", type=int, default=20)
    g.add_argument("--ids-log2", type=int, default=26)
    g.add_argument("--queries", type=int, default=256)
    g.add_argument("--snapshot-every", type=int, default=8)
    g = ap.add_argument_group("phase B")
    g.add_argument("--points-log2", type=int, default=20)
    g.add_argument("--dim", type=int, default=64)
    g.add_argument("--components", type=int, default=64)
    g.add_argument("--cluster-k", type=int, default=1024)
    g.add_argument("--queries-b", type=int, default=128)
    g.add_argument("--centers", type=int, default=64)
    g = ap.add_argument_group("--chips 4")
    g.add_argument("--shard-log2", type=int, default=22,
                   help="events per device of the sharded build")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"device: {device}; kernels interpreted: {default_interpret()}")
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"FAIL: no TPU found (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    failures = []
    state_root = os.path.join(HERE, ".smoke_state")
    os.makedirs(state_root, exist_ok=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            cs = phase_sharded(args, failures)
        else:
            cs = phase_a(args, state_root, failures)
            cs += phase_b(args, failures)
    finally:
        shutil.rmtree(state_root, ignore_errors=True)
    log(f"program checks compiled in {cs:.1f}s; whole run (one smoke run) "
        f"{time.perf_counter() - t0:.1f}s")
    if dev.platform != "tpu":
        failures.append(f"platform is {dev.platform!r}, not tpu")
    if default_interpret():
        failures.append("Pallas kernels ran in interpret mode")
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

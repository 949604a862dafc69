"""Benchmark harness — one function per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows (derived = the quantity the
paper's table/theorem is about) and mirrors every row into
``BENCH_results.json`` ({name: us_per_call} plus derived strings) so the
perf trajectory is machine-readable across PRs.
Run: PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import json
import math
import time
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

import repro.core as C
import repro.kernels as K

RESULTS: dict[str, float] = {}      # name -> us_per_call
DERIVED: dict[str, str] = {}        # name -> derived string


def _record(name: str, us: float, derived: str = ""):
    RESULTS[name] = round(float(us), 3)
    DERIVED[name] = derived
    print(f"{name},{us:.1f},{derived}")


def _timeit(fn, n=5):
    fn()  # warmup/compile
    t0 = time.perf_counter()
    out = None
    for _ in range(n):
        out = fn()
    if out is not None:
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


def bench_example_2_1_pps_table():
    """Paper Example 2.1: pps probabilities for sum/thresh/cap, k=3."""
    w = np.array([5, 100, 23, 7, 1, 5, 220, 19, 3, 2], np.float32)
    act = np.ones(10, bool)
    us = _timeit(lambda: [C.pps_probabilities(w, act, f, 3)[0]
                          for f in (C.SUM, C.thresh(10), C.cap(5))][0])
    p_sum, s = C.pps_probabilities(w, act, C.SUM, 3)
    _record("example_2_1_pps_table", us, f"total_sum={float(s):g}")


def bench_example_3_1_multiobjective_size():
    """Paper Example 3.1: E|S^(F)| vs naive union of dedicated samples."""
    w = np.array([5, 100, 23, 7, 1, 5, 220, 19, 3, 2], np.float32)
    act = np.ones(10, bool)
    objs = [(C.SUM, 3), (C.thresh(10), 3), (C.cap(5), 3)]

    def run():
        probs = [C.pps_probabilities(w, act, f, k)[0] for f, k in objs]
        return jnp.stack(probs).max(0).sum(), sum(p.sum() for p in probs)
    us = _timeit(lambda: run()[0])
    e_sf, naive = run()
    _record("example_3_1_multiobjective_size", us,
            f"E_SF={float(e_sf):.3f};naive={float(naive):.3f};paper=4.68/8.29")


def bench_thm_5_1_universal_size():
    """Thm 5.1: E|S^(M,k)| <= k ln n (+ Thm 5.2 lower bound shape)."""
    k = 16
    rows = []
    for n in (1_000, 10_000, 100_000):
        keys = np.arange(n, dtype=np.int32)
        w = np.random.default_rng(0).lognormal(0, 2, n).astype(np.float32)
        act = np.ones(n, bool)
        sizes = [int(C.universal_monotone_sample(keys, w, act, k,
                                                 seed=s).member.sum())
                 for s in range(8)]
        us = _timeit(lambda: C.universal_monotone_sample(keys, w, act, k,
                                                         seed=0).member)
        bound = k * math.log(n)
        lower = k * (math.log(n) - math.log(k))  # Thm 5.2 Omega(k ln n)
        rows.append((n, np.mean(sizes), bound, lower, us))
        _record(f"thm5_1_universal_size_n{n}", us,
                f"mean={np.mean(sizes):.1f};kln_n={bound:.1f};"
                f"lower={lower:.1f}")
    g1 = rows[1][1] / rows[0][1]
    g2 = rows[2][1] / rows[1][1]
    _record("thm5_1_log_growth", 0.0,
            f"size_ratio_per_10x={g1:.2f}/{g2:.2f}"
            f";expected_if_log={math.log(10_000)/math.log(1_000):.2f}")


def bench_thm_6_1_capping_size():
    """Thm 6.1: E|S^(C,k)| <= e k ln(w_max/w_min), independent of n."""
    k = 16
    rng = np.random.default_rng(1)
    for n in (1_000, 10_000, 100_000):
        keys = np.arange(n, dtype=np.int32)
        w = np.clip(rng.lognormal(0, 1.0, n), 0.1, 10.0).astype(np.float32)
        act = np.ones(n, bool)
        sizes = [int(C.universal_capping_sample(
            keys, w, act, k, m_cap=4096, seed=s).member.sum())
            for s in range(5)]
        us = _timeit(lambda: C.universal_capping_sample(
            keys, w, act, k, m_cap=4096, seed=0).member)
        bound = C.capping_size_bound(k, 10.0, 0.1)
        _record(f"thm6_1_capping_size_n{n}", us,
                f"mean={np.mean(sizes):.1f};bound={bound:.1f}")


def bench_thm_3_1_estimation_cv():
    """Thm 3.1/§5.1: empirical CV vs gold-standard bound per f (segment)."""
    n, k, trials = 2_000, 24, 200
    rng = np.random.default_rng(2)
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    act = np.ones(n, bool)
    seg = (np.arange(n) % 4 == 0)
    for f in [C.SUM, C.COUNT, C.thresh(3.0), C.cap(2.0), C.moment(1.5)]:
        t0 = time.perf_counter()
        ests = [float(C.estimate(f, w, s.prob, s.member, seg))
                for s in (C.universal_monotone_sample(keys, w, act, k, seed=i)
                          for i in range(trials))]
        us = (time.perf_counter() - t0) * 1e6 / trials
        ex = float(C.exact(f, w, act, seg))
        q = ex / float(C.exact(f, w, act))
        cv = float(np.std(ests) / ex)
        bound = C.cv_bound(q, k)
        _record(f"thm3_1_cv_{f.name}", us,
                f"cv={cv:.3f};bound={bound:.3f};ok={cv <= bound}")


def _kernel_select(objs, k: int):
    """The jitted kernel chain (member, prob) for (kind, param) objectives,
    each at budget k."""
    spec = C.MultiSketchSpec(objectives=tuple(
        (K.ops.statfn_of(kind, param), k) for kind, param in objs))
    return jax.jit(lambda keys, w, act: C.multisketch_select(
        spec, keys, w, act, use_kernels=True)[:2])


def bench_sampling_throughput():
    """Production sort+scan vs fused kernels (keys/s)."""
    n, k = 65_536, 64
    rng = np.random.default_rng(3)
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    act = np.ones(n, bool)
    us_prod = _timeit(lambda: C.universal_monotone_sample(
        keys, w, act, k, seed=0).member)
    _record("throughput_universal_sortscan", us_prod,
            f"keys_per_s={n/us_prod*1e6:.3g};seed_recorded=3.18e5;"
            f"speedup_vs_seed={n/us_prod*1e6/3.18e5:.2f}x")
    objs = ((0, 0.0), (3, 2.0), (1, 0.0))
    select = _kernel_select(objs, k)
    us_k = _timeit(lambda: select(
        jnp.asarray(keys), jnp.asarray(w), jnp.asarray(act))[0])
    _record("throughput_multiobj_kernel", us_k,
            f"keys_per_s={n/us_k*1e6:.3g};note=interpret_mode_cpu")


def bench_merge_throughput():
    """Composability cost: sketch merge (paper §5.2) at fixed capacity.

    Satellite fix: merge_sketches is now jit-cached per (k, capacity, seed)
    with an opt-in both-inputs-donated variant; the un-jitted op-by-op
    dispatch path (the seed's behavior, 131.8 ms/call recorded pre-fix) is
    timed alongside as the before/after record.
    """
    from repro.core.merge import _rebuild
    n, k = 16_384, 32
    rng = np.random.default_rng(4)
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    act = np.ones(n, bool)
    cap_sz = C.sketch_capacity(n, k)
    a = C.build_sketch(keys[:n // 2], w[:n // 2], act[:n // 2], k, cap_sz, 0)
    b = C.build_sketch(keys[n // 2:], w[n // 2:], act[n // 2:], k, cap_sz, 0)

    def merge_nojit():
        return _rebuild(jnp.concatenate([a.keys, b.keys]),
                        jnp.concatenate([a.weights, b.weights]),
                        jnp.concatenate([a.valid, b.valid]),
                        k, cap_sz, 0).member

    us_nojit = _timeit(merge_nojit)
    us = _timeit(lambda: C.merge_sketches(a, b).member)
    _record("merge_sketches", us,
            f"capacity={cap_sz};nojit_us={us_nojit:.0f};"
            f"seed_recorded_us=131789;jit_speedup={us_nojit/us:.1f}x")
    # donated fold: state <- merge(state, fresh) with both slabs consumed
    fresh = lambda s: s._replace(
        keys=jnp.array(s.keys), weights=jnp.array(s.weights),
        probs=jnp.array(s.probs), member=jnp.array(s.member),
        valid=jnp.array(s.valid))
    pool = [(fresh(a), fresh(b)) for _ in range(7)]
    it = iter(pool)
    import warnings
    with warnings.catch_warnings():
        # int32 keys can't alias across the concat; donation of the float
        # slabs still holds — silence the partial-donation notice
        warnings.filterwarnings("ignore", message=".*donated buffers.*")
        us_don = _timeit(
            lambda: C.merge_sketches(*next(it), donate=True).member, n=5)
    _record("merge_sketches_donated", us_don, f"capacity={cap_sz}")


def bench_universal_scan(smoke: bool = False):
    """Satellite: the blocked buffer scan (rank pass + inserted-subsequence
    replay) vs the sequential one-element-per-step reference scan. Runs at
    full n even in --smoke: the blocked win is the large-n regime (the
    inserted-subsequence bound grows ~k ln n while n grows linearly)."""
    from repro.core.universal import _buffer_scan, _buffer_scan_ref
    n, k1 = 65_536, 65
    rng = np.random.default_rng(8)
    v = jnp.asarray(rng.exponential(1.0, n).astype(np.float32))
    idx = jnp.arange(n, dtype=jnp.int32)
    ref = jax.jit(partial(_buffer_scan_ref, k_plus_1=k1))
    us_blk = _timeit(lambda: _buffer_scan(v, idx, k1)[1])
    us_ref = _timeit(lambda: ref(v, idx)[1])
    _record("universal_scan_blocked", us_blk,
            f"keys_per_s={n/us_blk*1e6:.3g};"
            f"speedup_vs_ref={us_ref/us_blk:.2f}x")
    _record("universal_scan_ref", us_ref, f"keys_per_s={n/us_ref*1e6:.3g}")


def bench_query_engine(smoke: bool = False):
    """Tentpole claim: batched segment queries (ONE fused launch for
    B predicates x |F| objectives, kernels.segquery) vs the one-query-at-
    a-time loop (one launch per (f, H) pair — the pre-PR serving path),
    against a resident merged slab. queries/s, B x |F| grid."""
    from repro.launch.query import SegmentQueryEngine
    pool = ((C.SUM, 64), (C.COUNT, 64), (C.thresh(2.0), 64),
            (C.cap(1.5), 64), (C.moment(1.5), 64), (C.thresh(0.5), 64),
            (C.cap(4.0), 64), (C.moment(0.5), 64))
    n = 16_384 if smoke else 65_536
    rng = np.random.default_rng(9)
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    grid = (((1, 1), (16, 3), (128, 8)) if smoke
            else ((1, 1), (1, 3), (1, 8), (16, 1), (16, 3), (16, 8),
                  (128, 1), (128, 3), (128, 8)))
    span = n // 128

    for b, nf in grid:
        spec = C.MultiSketchSpec(objectives=pool[:nf], seed=0)
        eng = SegmentQueryEngine(spec, shards=4)
        for i in range(4):
            eng.absorb(keys[i::4], w[i::4], shard=i)
        preds = [C.key_range(j * span, (j + 1) * span - 1) for j in range(b)]
        fs = tuple(f for f, _ in spec.objectives)
        sk = eng.merged

        us_batch = _timeit(lambda: eng.query_many(fs, preds), n=3)
        qps_batch = b * nf / us_batch * 1e6

        def loop_all():
            out = None
            for f in fs:
                for p in preds:
                    # a per-query serving loop delivers each answer to the
                    # host before the next request (same sync discipline
                    # query_many's numpy return pays once per batch)
                    out = np.asarray(C.multisketch_estimate_batch(sk, (f,),
                                                                  (p,)))
            return out
        us_loop = _timeit(loop_all, n=3)
        qps_loop = b * nf / us_loop * 1e6
        _record(f"bench_query_engine_B{b}_F{nf}", us_batch,
                f"qps={qps_batch:.3g};loop_qps={qps_loop:.3g};"
                f"batched_speedup={us_loop/us_batch:.1f}x")


def bench_cluster_engine(smoke: bool = False):
    """PR 4 tentpole claim: batched service-cost scoring (ONE fused launch
    for Q candidate center sets x the resident sample slab,
    kernels.servicecost) vs the one-set-at-a-time loop (one launch per
    candidate — the host-loop scoring a swap search would otherwise pay),
    over a Q x |C| grid."""
    from repro.core.costs import cost_table
    from repro.launch.cluster import ClusterEngine

    n, dim = (4096 if smoke else 16384), 8
    rng = np.random.default_rng(10)
    ctrs = rng.normal(0, 6, (8, dim))
    X = (ctrs[rng.integers(0, 8, n)]
         + rng.normal(0, 0.7, (n, dim))).astype(np.float32)
    eng = ClusterEngine.fit(X, k=64, mu=2.0, seed=0)
    grid = (((16, 8), (128, 8), (128, 64)) if smoke
            else ((1, 8), (16, 8), (16, 64), (128, 8), (128, 64)))
    for q, cm in grid:
        sets = X[rng.integers(0, n, (q, cm))]
        table = cost_table(sets, 2.0)
        us_batch = _timeit(lambda: eng.service_costs(table), n=3)
        rows = [cost_table(sets[i:i + 1], 2.0) for i in range(q)]

        def loop_all():
            out = None
            for r in rows:
                out = eng.service_costs(r)
            return out
        us_loop = _timeit(loop_all, n=3)
        _record(f"bench_cluster_engine_Q{q}_C{cm}", us_batch,
                f"sets_per_s={q/us_batch*1e6:.3g};"
                f"loop_sets_per_s={q/us_loop*1e6:.3g};"
                f"batched_speedup={us_loop/us_batch:.1f}x")


def bench_engine_tail_latency(smoke: bool = False):
    """PR 7 tentpole: query-engine tail latency under interleaved
    absorb/query. With absorb-time maintenance (the default) the merged
    slab is folded forward DURING the absorb, so the churn-phase query
    path dispatches ZERO merge work — asserted by the dispatch spy
    (query_time_folds must be 0) — and churn_tax_p50 collapses to ~1x.
    p50/p95/max per-query microseconds."""
    from repro.launch.query import SegmentQueryEngine
    from tests.dispatch_spy import spy_merge_dispatch
    spec = C.MultiSketchSpec(objectives=((C.SUM, 64), (C.COUNT, 64),
                                         (C.thresh(2.0), 64)), seed=0)
    n = 8192 if smoke else 32768
    iters = 16 if smoke else 32
    rng = np.random.default_rng(11)
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    preds = [C.key_range(j * (n // 16), (j + 1) * (n // 16) - 1)
             for j in range(16)]
    fs = tuple(f for f, _ in spec.objectives)

    eng = SegmentQueryEngine(spec, shards=2)
    eng.absorb(keys[::2], w[::2], shard=0)
    eng.absorb(keys[1::2], w[1::2], shard=1)
    # warm every executable in the chain (the bootstrap full merge, the
    # absorb-time fold, the fused query launch)
    eng.query_many(fs, preds)
    eng.absorb(keys[:1], w[:1], shard=0)
    eng.query_many(fs, preds)

    # churn and steady samples INTERLEAVED in one loop: each epoch's
    # first query (right after the absorb) is the churn sample, and an
    # immediate second query — a pure cache hit on the identical state —
    # is the steady baseline. Pairing them under the same machine
    # conditions is what makes the ratio a property of the engine, not
    # of CPU-frequency / scheduler drift between two separate phases.
    churn, steady = [], []
    folds = {"full": 0, "inc": 0}
    stats0 = dict(eng.merge_stats)
    for i in range(iters):
        eng.absorb(keys[i::iters], w[i::iters], shard=i % 2)
        # drain the absorb epoch (shard fold + merged-slab maintenance +
        # probs finalize are async-dispatched): maintenance cost is
        # charged to absorb time, where it now runs — the query timer
        # below must measure the query launch, not the previous epoch's
        # device backlog (a serving pump drains folds between requests
        # the same way)
        eng.drain()
        with spy_merge_dispatch() as counts:
            t0 = time.perf_counter()
            eng.query_many(fs, preds)
            churn.append((time.perf_counter() - t0) * 1e6)
            t0 = time.perf_counter()
            eng.query_many(fs, preds)
            steady.append((time.perf_counter() - t0) * 1e6)
        folds["full"] += counts["full"]
        folds["inc"] += counts["inc"]
    churn, steady = np.asarray(churn), np.asarray(steady)
    at = eng.merge_stats["absorb_time"] - stats0["absorb_time"]
    query_time_folds = folds["full"] + folds["inc"]
    _record("engine_tail_latency_churn", float(np.percentile(churn, 95)),
            f"p50={np.percentile(churn, 50):.0f};"
            f"p95={np.percentile(churn, 95):.0f};max={churn.max():.0f};"
            f"steady_p50={np.percentile(steady, 50):.0f};"
            f"steady_p95={np.percentile(steady, 95):.0f};"
            f"query_time_folds={query_time_folds};absorb_time_folds={at};"
            f"churn_tax_p50={np.percentile(churn, 50)/max(np.percentile(steady, 50), 1e-9):.2f}x")


def bench_incremental_merge(smoke: bool = False):
    """PR 5 tentpole: epoch maintenance cost when ONE shard absorbed — the
    delta fold into the cached merged slab (multisketch_absorb_into,
    donated buffers, (1 + dirty) x capacity re-selection) vs the full
    stacked re-merge over all S shards. The gap widens with S: the full
    path stacks and rebuilds S x capacity slots every epoch."""
    from repro.launch.query import SegmentQueryEngine
    spec = C.MultiSketchSpec(objectives=((C.SUM, 64), (C.COUNT, 64),
                                         (C.thresh(2.0), 64)), seed=0)
    n = 8192 if smoke else 32768
    rng = np.random.default_rng(12)
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    for shards in ((2, 8) if smoke else (2, 4, 8)):
        # lazy twins isolate the PR 5 ladder; the third engine runs the
        # PR 7 absorb-time maintenance (same fold, paid inside absorb)
        engs = {"incremental": SegmentQueryEngine(spec, shards=shards,
                                                  absorb_time=False),
                "full": SegmentQueryEngine(spec, shards=shards,
                                           absorb_time=False, max_delta=0),
                "absorb_time": SegmentQueryEngine(spec, shards=shards)}
        for eng in engs.values():
            for i in range(shards):
                eng.absorb(keys[i::shards], w[i::shards], shard=i)
            eng._materialize_merged()
        us = {}
        for name, eng in engs.items():
            def epoch(i=[0], eng=eng):
                i[0] += 1
                eng.absorb(keys[i[0] % 7::7], w[i[0] % 7::7],
                           shard=i[0] % shards)
                return eng._materialize_merged().member
            epoch()  # warm the per-path executables
            us[name] = _timeit(epoch, n=5)
        _record(f"incremental_merge_S{shards}", us["incremental"],
                f"full_us={us['full']:.0f};"
                f"absorb_time_us={us['absorb_time']:.0f};"
                f"speedup={us['full']/us['incremental']:.1f}x")


def bench_shard_gc(smoke: bool = False):
    """PR 7 shard lifecycle: long-run churn under the auto GC water-mark.
    Reports the GC merge cost, the live-shard plateau and the resident-
    bytes bound — the O(capacity)-memory claim for long-running streams
    (CI asserts the plateau fields exist and live <= water-mark)."""
    from repro.launch.query import SegmentQueryEngine
    spec = C.MultiSketchSpec(objectives=((C.SUM, 64), (C.COUNT, 64),
                                         (C.thresh(2.0), 64)), seed=0)
    epochs = 24 if smoke else 64
    shards, water = 8, 3
    chunk = 2048 if smoke else 8192
    rng = np.random.default_rng(13)
    eng = SegmentQueryEngine(spec, shards=shards, gc_max_live=water)
    gc_us, live_track, bytes_track = [], [], []
    for i in range(epochs):
        k = rng.integers(0, 1 << 20, chunk).astype(np.int32)
        w = rng.lognormal(0, 1.5, chunk).astype(np.float32)
        gc0 = eng.merge_stats["gc_merges"]
        t0 = time.perf_counter()
        eng.absorb(k, w, shard=int(rng.integers(0, shards)))
        us = (time.perf_counter() - t0) * 1e6
        if eng.merge_stats["gc_merges"] > gc0:
            gc_us.append(us)
        live_track.append(eng.merge_stats["live_shards"])
        bytes_track.append(eng.merge_stats["bytes_resident"])
    jax.block_until_ready(eng.merged.keys)
    half = epochs // 2
    _record("bench_shard_gc",
            float(np.mean(gc_us)) if gc_us else 0.0,
            f"gc_merges={eng.merge_stats['gc_merges']};"
            f"live_max={max(live_track)};live_plateau={max(live_track[half:])};"
            f"water_mark={water};"
            f"bytes_plateau={max(bytes_track[half:])};"
            f"bytes_peak={max(bytes_track)};"
            f"plateau_bounded={int(max(bytes_track[half:]) <= max(bytes_track[:half]))}")


def bench_absorb_throughput(smoke: bool = False):
    """Tentpole claim: the jit'd device-resident MultiSketch fold vs the
    seed's host-side per-batch rebuild-and-merge absorption loop
    (build_sketch + merge_sketches per chunk), capacity >= 1024."""
    k, capacity = 64, 1024
    chunk = 1024 if smoke else 4096
    iters = 4 if smoke else 12
    rng = np.random.default_rng(7)
    ws = [rng.lognormal(0, 1, chunk).astype(np.float32)
          for _ in range(iters)]
    ks = [(i * chunk + np.arange(chunk)).astype(np.int32)
          for i in range(iters)]
    act = np.ones(chunk, bool)

    spec = C.MultiSketchSpec(objectives=((C.SUM, k), (C.COUNT, k)),
                             seed=0, capacity=capacity)

    def fold_all():
        st = C.multisketch_empty(spec)
        for i in range(iters):
            st = C.multisketch_absorb(st, ks[i], ws[i], spec=spec,
                                      use_kernels=False)
        return st.member

    def host_rebuild_all():
        sk = None
        for i in range(iters):
            new = C.build_sketch(ks[i], ws[i], act, k, capacity, 0)
            sk = new if sk is None else C.merge_sketches(sk, new)
        return sk.member

    us_fold = _timeit(fold_all, n=3) / iters
    us_host = _timeit(host_rebuild_all, n=3) / iters
    _record("absorb_fold_device", us_fold,
            f"keys_per_s={chunk/us_fold*1e6:.3g};capacity={capacity}")
    _record("absorb_host_rebuild", us_host,
            f"keys_per_s={chunk/us_host*1e6:.3g};"
            f"fold_speedup={us_host/us_fold:.2f}x")


def bench_gradient_compression():
    """distopt: wire bytes vs dense, and estimate quality."""
    from repro.distopt.compression import _sample_leaf, _merge_leaf
    n, k = 262_144, 512
    rng = np.random.default_rng(5)
    g = (rng.standard_normal(n) * (rng.random(n) < 0.3)).astype(np.float32)
    us = _timeit(lambda: _sample_leaf(jnp.asarray(g), k, 7, 0.01).keys)
    sk = _sample_leaf(jnp.asarray(g), k, 7, 0.01)
    wire = int(sk.keys.size) * (4 + 4 + 4)
    dense = n * 4
    est = _merge_leaf(sk.keys[None], sk.weights[None], sk.probs[None],
                      sk.valid[None], n, 1)
    rel = float(jnp.linalg.norm(est - g) / jnp.linalg.norm(g))
    dots = float(jnp.dot(est, g) / jnp.dot(g, g))
    _record("grad_compression", us,
            f"ratio={dense/wire:.1f}x;l2rel={rel:.3f};proj={dots:.3f}")


_SCALING_POOL = ((0, 0.0), (3, 2.0), (1, 0.0), (2, 5.0),
                 (4, 1.5), (3, 0.5), (2, 1.0), (4, 0.8))


@partial(jax.jit, static_argnames=("objectives", "k"))
def _per_objective_loop(keys, weights, active, objectives, k):
    """The seed's multi-objective path: |F| separate block-select launches
    plus a per-objective StatFn/prob pass — the flat-vs-linear baseline."""
    from repro.core.bottomk import bottomk_members, conditional_prob
    n = keys.shape[0]
    seeds = K.fused_seeds(keys, weights, active, objectives)
    member = jnp.zeros((n,), bool)
    prob = jnp.zeros((n,), jnp.float32)
    for j, (kind, param) in enumerate(objectives):
        vals, idx, _ = K.bottomk_select(seeds[j], k + 1)
        m, tau, _ = bottomk_members(seeds[j][None], keys, (k,),
                                    (vals[None], idx[None]))
        m, tau = m[0], tau[0]
        fv = jnp.where(active,
                       K.ops.statfn_of(kind, param)(
                           jnp.asarray(weights, jnp.float32)), 0.0)
        p = jnp.where(m, conditional_prob(fv, tau, "ppswor"), 0.0)
        member = member | m
        prob = jnp.maximum(prob, p)
    return member, prob


def bench_multiobj_scaling():
    """Launch-cost scaling in |F|: fused single-launch chain vs the
    per-objective loop. The fused path should grow sublinearly (bandwidth
    term only); the loop pays |F| launches + 2|F| scans."""
    n, k = 65_536, 64
    rng = np.random.default_rng(6)
    keys = jnp.arange(n, dtype=jnp.int32)
    w = jnp.asarray(rng.lognormal(0, 1.5, n).astype(np.float32))
    act = jnp.ones(n, bool)
    base_fused = base_loop = None
    for nf in (1, 2, 4, 8):
        objs = _SCALING_POOL[:nf]
        select = _kernel_select(objs, k)
        us_f = _timeit(lambda: select(keys, w, act)[0])
        us_l = _timeit(lambda: _per_objective_loop(keys, w, act, objs, k)[0])
        if base_fused is None:
            base_fused, base_loop = us_f, us_l
        _record(f"multiobj_scaling_F{nf}", us_f,
                f"fused_x={us_f/base_fused:.2f};loop_us={us_l:.1f};"
                f"loop_x={us_l/base_loop:.2f}")


def bench_serving_chaos(smoke: bool = False):
    """Robustness PR tentpole: the multi-tenant ``EnginePool`` under
    open-loop Poisson load WHILE a seeded fault schedule fires on the
    fold and query paths (device faults -> retry -> breaker -> last-good
    stale serving) and occasional producers ship NaN rows (quarantine).
    Latency is measured from the SCHEDULED arrival (queueing under
    overload is charged to the server). Reports p50/p95/p99 ms and
    availability = (FRESH + STALE) / total — the acceptance gate asserts
    availability >= 0.99 with every degraded answer labeled."""
    from repro.launch.pool import (FRESH, REJECTED, STALE, EnginePool,
                                   RejectedError)
    from tests.faults import FaultInjector, poisson_arrivals

    n_req = 100 if smoke else 400
    # smoke runs on CPU interpret-mode kernels: keep the offered load
    # below saturation so the percentiles measure the pool, not an
    # unpayable backlog
    rate_hz = 20.0 if smoke else 150.0
    rng = np.random.default_rng(20)
    # retries=0: each injected fault costs one op, so breakers actually
    # open under the 0.25 schedule (3 consecutive) and the bench walks
    # the whole ladder, not just the retry rung
    pool = EnginePool(queue_depth=256, retries=0, breaker_threshold=3,
                      breaker_reset=0.02, sleep=lambda s: None)
    # small per-objective k: the bench measures the POOL (admission,
    # ladder, breaker, quarantine), not kernel throughput — the query/
    # absorb benches above own that axis
    kk = 16 if smoke else 64
    spec = C.MultiSketchSpec(objectives=((C.SUM, kk), (C.COUNT, kk),
                                         (C.thresh(2.0), kk)), seed=0)
    fs = tuple(f for f, _ in spec.objectives)
    tenants = ("tenant_a", "tenant_b", "tenant_c")
    warm_n = 256 if smoke else 2048
    for i, name in enumerate(tenants):
        pool.create_stream(name, spec)
        keys = (i * 100_000 + np.arange(warm_n)).astype(np.int32)
        pool.absorb(name, keys,
                    rng.lognormal(0, 1.5, warm_n).astype(np.float32))
        pool.query(name, fs)            # warm the per-tenant executables

    arrivals = poisson_arrivals(rate_hz, n_req, rng)
    statuses = {FRESH: 0, STALE: 0, REJECTED: 0}
    lat_ms = []
    quarantined = 0
    t0 = time.perf_counter()
    with FaultInjector(seed=21) as inj:
        inj.fail_prob("query_merge", 0.25)
        inj.fail_prob("absorb_fold", 0.25)
        for i in range(n_req):
            sched = t0 + float(arrivals[i])
            while True:                 # open-loop: hold to the schedule
                gap = sched - time.perf_counter()
                if gap <= 0:
                    break
                time.sleep(min(gap, 1e-3))
            name = tenants[int(rng.integers(0, len(tenants)))]
            if i % 8 == 7:              # interleaved ingest under load
                keys = (500_000 + i * 64 + np.arange(64)).astype(np.int32)
                w = rng.lognormal(0, 1, 64).astype(np.float32)
                if i % 16 == 15:
                    w[::11] = np.nan    # corrupt producer rows
                try:
                    quarantined += pool.absorb(name, keys, w).quarantined
                except RejectedError:
                    pass
            try:
                fut = pool.submit(name, fs, timeout=2.0)
            except RejectedError:       # admission shed counts against us
                statuses[REJECTED] += 1
                continue
            pool.pump()
            resp = fut.result(5.0)
            statuses[resp.status] += 1
            lat_ms.append((time.perf_counter() - sched) * 1e3)
    lat = np.asarray(lat_ms)
    opens = sum(pool.stats(t)["breaker_opens"] for t in tenants)
    avail = (statuses[FRESH] + statuses[STALE]) / n_req
    _record("serving_chaos", float(np.percentile(lat, 95)) * 1e3,
            f"availability={avail:.4f};p50_ms={np.percentile(lat, 50):.2f};"
            f"p95_ms={np.percentile(lat, 95):.2f};"
            f"p99_ms={np.percentile(lat, 99):.2f};fresh={statuses[FRESH]};"
            f"stale={statuses[STALE]};rejected={statuses[REJECTED]};"
            f"quarantined={quarantined};breaker_opens={opens};"
            f"rate_hz={rate_hz:g};n={n_req}")


def bench_pool_scaleout(smoke: bool = False):
    """Scale-out PR tentpole: the ``ShardedEnginePool`` (consistent-hash
    placement over a host group, absorb fan-out, cross-host re-selection
    reads, replicated last-good slabs) under open-loop load WHILE a
    seeded schedule kills an owner host mid-stream, followed by a
    rebalance (checkpoint + WAL rebuild of the dead host's shards).
    Reports availability = (FRESH + STALE) / reads — the CI scaleout
    gate asserts >= 0.99 — and ``bitsame``: post-rebalance answers must
    be BIT-IDENTICAL to a never-failed single-host union engine (=1)."""
    import tempfile

    from repro.launch.pool import (FRESH, REJECTED, STALE, RejectedError,
                                   ShardedEnginePool)
    from repro.launch.query import SegmentQueryEngine
    from tests.faults import FaultInjector, poisson_arrivals

    n_ops = 60 if smoke else 240
    rate_hz = 20.0 if smoke else 100.0
    shards, rows = 16, 128 if smoke else 512
    kk = 16 if smoke else 64
    rng = np.random.default_rng(31)
    spec = C.MultiSketchSpec(objectives=((C.SUM, kk), (C.COUNT, kk)),
                             seed=0, capacity=4 * kk)
    with tempfile.TemporaryDirectory() as dur:
        pool = ShardedEnginePool(hosts=(0, 1, 2, 3), durability_dir=dur,
                                 pending_limit=1024, sleep=lambda s: None)
        placement = pool.create_stream("t", spec, shards=shards)
        twin = SegmentQueryEngine(spec, shards=shards)
        statuses = {FRESH: 0, STALE: 0, REJECTED: 0}
        unlabeled = shed = 0
        lat_ms = []
        arrivals = poisson_arrivals(rate_hz, n_ops, rng)
        t0 = time.perf_counter()
        with FaultInjector(seed=32) as inj:
            inj.kill_host(pool, placement[0], at=n_ops // 2)
            for i in range(n_ops):
                sched = t0 + float(arrivals[i])
                while True:             # open-loop: hold to the schedule
                    gap = sched - time.perf_counter()
                    if gap <= 0:
                        break
                    time.sleep(min(gap, 1e-3))
                sh = int(rng.integers(0, shards))
                keys = (i * rows + np.arange(rows)).astype(np.int32)
                w = rng.lognormal(0, 1.5, rows).astype(np.float32)
                try:
                    pool.absorb("t", keys, w, shard=sh)
                except RejectedError:
                    shed += 1
                    continue
                twin.absorb(keys, w, shard=sh)
                r = pool.query("t", timeout=2.0)
                statuses[r.status] += 1
                lat_ms.append((time.perf_counter() - sched) * 1e3)
                if r.status == FRESH:
                    if (r.epoch_lag != 0 or not np.array_equal(
                            r.values, twin.query_many())):
                        unlabeled += 1  # FRESH must be the exact truth
                elif r.status == STALE:
                    if r.values is None or (r.epoch_lag == 0
                                            and r.error is None):
                        unlabeled += 1  # degraded must be labeled
        # recovery: re-partition around the dead host, answers exact again
        reb_t0 = time.perf_counter()
        out = pool.rebalance("t")["t"]
        reb_ms = (time.perf_counter() - reb_t0) * 1e3
        r = pool.query("t")
        bitsame = int(r.status == FRESH and out["error"] is None
                      and np.array_equal(r.values, twin.query_many()))
        pool.close()
    reads = sum(statuses.values())
    avail = (statuses[FRESH] + statuses[STALE]) / max(reads, 1)
    lat = np.asarray(lat_ms)
    _record("pool_scaleout", float(np.percentile(lat, 95)) * 1e3,
            f"availability={avail:.4f};bitsame={bitsame};"
            f"unlabeled={unlabeled};fresh={statuses[FRESH]};"
            f"stale={statuses[STALE]};rejected={statuses[REJECTED]};"
            f"shed={shed};moved={len(out['moved'])};"
            f"rebalance_ms={reb_ms:.1f};"
            f"p50_ms={np.percentile(lat, 50):.2f};"
            f"p95_ms={np.percentile(lat, 95):.2f};"
            f"hosts=4;shards={shards};rate_hz={rate_hz:g};n={n_ops}")


def bench_dryrun_roofline_summary():
    """Ties to EXPERIMENTS.md §Roofline: summarize dry-run artifacts."""
    import glob
    import json
    for mesh in ("sp", "mp"):
        cells = ok = 0
        for f in glob.glob(f"experiments/dryrun/*__{mesh}.json"):
            r = json.load(open(f))
            cells += 1
            ok += r.get("status") in ("ok", "skipped")
        _record(f"dryrun_cells_{mesh}", 0.0, f"total={cells};ok_or_skipped={ok}")


def bench_roofline_fold_model(smoke: bool = False):
    """Satellite: the idle roofline generator, wired into the registry —
    the absorb/fold bytes-moved model (benchmarks.roofline) for the
    serving engine's maintenance paths, plus the dry-run table row count
    when artifacts exist. ``--only roofline`` runs it standalone."""
    from benchmarks.roofline import TARGET_KIND, fold_bytes_moved, peaks
    spec = C.MultiSketchSpec(objectives=((C.SUM, 64), (C.COUNT, 64),
                                         (C.thresh(2.0), 64)), seed=0)
    b = C.multisketch_slab_bytes(spec)
    for absorb_time in (True, False):
        mode = "absorb_time" if absorb_time else "lazy"
        m = fold_bytes_moved(b, chunk_rows=8192, num_shards=8,
                             absorb_time=absorb_time)
        _record(f"roofline_fold_{mode}", m["min_epoch_s"] * 1e6,
                f"slab_bytes={b};epoch_bytes={m['epoch_bytes']};"
                f"shard_fold_bytes={m['shard_fold_bytes']};"
                f"maintain_bytes={m['maintain_bytes']};"
                f"lazy_remerge_bytes={m['lazy_remerge_bytes']};"
                f"hbm_bw={peaks(TARGET_KIND)['hbm_bw']:g}")


def _registry(smoke: bool):
    """Bench registry: (name, thunk, runs_in_smoke). ``--only <name>``
    selects one entry (running it even when the smoke subset skips it)."""
    s = dict(smoke=smoke)
    return (
        ("example_2_1_pps_table", bench_example_2_1_pps_table, True),
        ("example_3_1_multiobjective_size",
         bench_example_3_1_multiobjective_size, True),
        ("thm_5_1_universal_size", bench_thm_5_1_universal_size, False),
        ("thm_6_1_capping_size", bench_thm_6_1_capping_size, False),
        ("thm_3_1_estimation_cv", bench_thm_3_1_estimation_cv, False),
        ("sampling_throughput", bench_sampling_throughput, False),
        ("merge_throughput", bench_merge_throughput, True),
        ("incremental_merge", partial(bench_incremental_merge, **s), True),
        ("absorb_throughput", partial(bench_absorb_throughput, **s), True),
        ("universal_scan", partial(bench_universal_scan, **s), True),
        ("query_engine", partial(bench_query_engine, **s), True),
        ("cluster_engine", partial(bench_cluster_engine, **s), True),
        ("engine_tail_latency",
         partial(bench_engine_tail_latency, **s), True),
        ("shard_gc", partial(bench_shard_gc, **s), True),
        ("roofline", bench_roofline_fold_model, True),
        ("serving_chaos", partial(bench_serving_chaos, **s), True),
        ("pool_scaleout", partial(bench_pool_scaleout, **s), True),
        ("gradient_compression", bench_gradient_compression, True),
        ("multiobj_scaling", bench_multiobj_scaling, False),
        ("dryrun_roofline_summary", bench_dryrun_roofline_summary, True),
    )


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced fast subset (CI): skips the scaling "
                         "sweeps, shrinks the absorb bench")
    ap.add_argument("--only", default=None,
                    help="run a single bench by registry name "
                         "(e.g. serving_chaos)")
    ap.add_argument("--out", default="BENCH_results.json",
                    help="JSON results path")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    registry = _registry(args.smoke)
    if args.only is not None:
        names = {n for n, _, _ in registry}
        if args.only not in names:
            raise SystemExit(f"unknown bench {args.only!r}; "
                             f"choose from {sorted(names)}")
    print("name,us_per_call,derived")
    for name, fn, in_smoke in registry:
        if args.only is not None:
            if name != args.only:
                continue
        elif args.smoke and not in_smoke:
            continue
        fn()
    with open(args.out, "w") as fh:
        json.dump({"us_per_call": RESULTS, "derived": DERIVED}, fh,
                  indent=1, sort_keys=True)
    print(f"# wrote {args.out} ({len(RESULTS)} entries)")


if __name__ == "__main__":
    main()

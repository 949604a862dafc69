"""Segment-query engine: batched query_many vs the single-estimate oracle
across schemes x |F| x B, single-launch flatness in both B and |F|, lazy
merge-on-demand == eager sharded build, absorb-epoch cache invalidation;
plus the PR's satellites (blocked buffer scan bit-identity, jit-cached /
donated merge_sketches, collector query routing)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as C
from repro.core.predicates import PRED_COLS, never_row, pad_table
from repro.kernels import ref as R
from repro.kernels.segquery import segment_query_slab
from repro.launch.query import SegmentQueryEngine
from tests.test_batched_multiobj import _count_pallas_calls


def _objectives(nf):
    pool = [(C.SUM, 16), (C.COUNT, 8), (C.thresh(2.0), 12), (C.cap(1.5), 8),
            (C.moment(1.5), 8), (C.thresh(0.5), 8), (C.cap(4.0), 8),
            (C.moment(0.5), 8)]
    return tuple(pool[:nf])


def _predicates(b, lo=0, hi=10_000):
    """b deterministic predicates cycling through every wire family."""
    span = max((hi - lo) // max(b, 1), 1)
    pool = []
    for i in range(b):
        fam = i % 4
        if fam == 0:
            pool.append(C.key_range(lo + i * span, lo + (i + 1) * span - 1))
        elif fam == 1:
            pool.append(C.key_mask(3, i % 4))
        elif fam == 2:
            pool.append(C.hash_fraction(0.1 + 0.8 * (i / max(b, 1)), salt=i))
        else:
            pool.append(C.EVERYTHING)
    return pool


def _data(n=2500, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.permutation(np.arange(5, 5 + n)).astype(np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    return keys, w


# ------------------------------------------------ batched query vs oracles
@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("nf", [1, 3, 8])
@pytest.mark.parametrize("b", [1, 16, 128])
def test_query_many_matches_single_estimates(scheme, nf, b):
    keys, w = _data()
    objs = _objectives(nf)
    spec = C.MultiSketchSpec(objectives=objs, scheme=scheme, seed=11)
    sk = C.multisketch_build(spec, keys, w)
    preds = _predicates(b)
    fs = tuple(f for f, _ in objs)
    got = C.multisketch_estimate_batch(sk, fs, preds)
    assert got.shape == (nf, b)
    for i, f in enumerate(fs):
        for j, p in enumerate(preds):
            want = float(C.multisketch_estimate(sk, f, segment_fn=p))
            assert abs(float(got[i, j]) - want) <= 1e-3 * max(1.0, abs(want))


def test_kernel_and_xla_paths_identical():
    keys, w = _data(seed=3)
    objs = _objectives(3)
    spec = C.MultiSketchSpec(objectives=objs, seed=7)
    sk = C.multisketch_build(spec, keys, w)
    preds = _predicates(16)
    fs = tuple(f for f, _ in objs)
    a = C.multisketch_estimate_batch(sk, fs, preds, use_kernels=True)
    x = C.multisketch_estimate_batch(sk, fs, preds, use_kernels=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(x), rtol=1e-5)
    r = R.segment_query_ref(sk.keys, sk.weights, sk.probs, sk.member,
                            C.encode_predicates(preds),
                            ((0, 0.0), (1, 0.0), (2, 2.0)))
    np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5)


def test_estimate_many_matches_estimate():
    rng = np.random.default_rng(2)
    n = 800
    w = rng.lognormal(0, 1, n).astype(np.float32)
    probs = np.clip(rng.random(n), 0.05, 1).astype(np.float32)
    member = rng.random(n) > 0.4
    segs = rng.random((5, n)) > 0.5
    fs = (C.SUM, C.COUNT, C.cap(1.5))
    got = C.estimate_many(fs, w, probs, member, segs)
    for i, f in enumerate(fs):
        for j in range(5):
            want = float(C.estimate(f, w, probs, member, segs[j]))
            np.testing.assert_allclose(float(got[i, j]), want, rtol=1e-5)
    # disjoint segment rows agree with the partition estimator
    ids = rng.integers(0, 4, n)
    part = np.stack([ids == j for j in range(4)])
    got_p = C.estimate_many((C.SUM,), w, probs, member, part)[0]
    want_p = C.estimate_segments(C.SUM, w, probs, member, ids, 4)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want_p),
                               rtol=1e-5)


def test_predicate_wire_semantics():
    keys = np.arange(-2, 1000, dtype=np.int32)
    m = C.predicate_matrix(keys, C.encode_predicates(
        [C.key_range(10, 20), C.key_mask(1, 1), C.EVERYTHING,
         C.SegmentPredicate(lo=1, hi=0)]))
    m = np.asarray(m)
    np.testing.assert_array_equal(m[0], (keys >= 10) & (keys <= 20))
    np.testing.assert_array_equal(m[1], (keys % 2 == 1) & (keys >= 0))
    np.testing.assert_array_equal(m[2], keys >= 0)
    assert not m[3].any()
    # hashed fraction selects ~q of keys, coordinated (same salt -> same set)
    frac = C.hash_fraction(0.25, salt=9)
    sel = np.asarray(frac(np.arange(20_000)))
    assert abs(sel.mean() - 0.25) < 0.02
    np.testing.assert_array_equal(sel, np.asarray(frac(np.arange(20_000))))
    assert pad_table(C.encode_predicates([C.EVERYTHING]), 4).shape == \
        (4, PRED_COLS)
    assert (never_row()[0] > never_row()[1])


# ------------------------------------------------ single-launch flatness
@pytest.mark.parametrize("nf", [1, 3, 8])
@pytest.mark.parametrize("b", [1, 16, 128])
def test_query_launch_count_flat_in_B_and_F(nf, b):
    """ONE pallas launch per query_many, for every (B, |F|) combination."""
    objs = _objectives(nf)
    spec = C.MultiSketchSpec(objectives=objs, seed=1)
    sk = C.multisketch_build(spec, np.arange(500), np.ones(500, np.float32))
    enc = spec.kernel_objectives()
    table = jnp.asarray(C.encode_predicates(_predicates(b)))
    jx = jax.make_jaxpr(
        lambda k, w, p, m, t: segment_query_slab(k, w, p, m, t, enc))(
            sk.keys, sk.weights, sk.probs, sk.member, table)
    assert _count_pallas_calls(jx.jaxpr) == 1


# ------------------------------------------------ engine: lazy merge, cache
def test_engine_lazy_merge_matches_one_shot_and_invalidates():
    keys, w = _data(n=3000, seed=5)
    objs = _objectives(3)
    spec = C.MultiSketchSpec(objectives=objs, seed=2)
    eng = SegmentQueryEngine(spec, shards=4)
    for i in range(4):
        eng.absorb(keys[i::4], w[i::4], shard=i)
    one = C.multisketch_build(spec, keys, w)
    m = eng.merged
    np.testing.assert_array_equal(np.asarray(m.keys), np.asarray(one.keys))
    np.testing.assert_array_equal(np.asarray(m.probs), np.asarray(one.probs))
    np.testing.assert_array_equal(np.asarray(m.taus), np.asarray(one.taus))
    # memoized: same epoch -> same object, no re-merge
    assert eng.merged is m
    # absorb invalidates; the next query reflects the union
    e0 = eng.epoch
    extra_k = np.arange(90_000, 90_064)
    extra_w = np.full(64, 2.0, np.float32)
    eng.absorb(extra_k, extra_w, shard=2)
    assert eng.epoch > e0 and eng.merged is not m
    want = C.multisketch_merge(spec, one,
                               C.multisketch_build(spec, extra_k, extra_w))
    got = eng.query(C.SUM)
    ref = float(C.multisketch_estimate(want, C.SUM))
    assert abs(got - ref) <= 1e-3 * max(1.0, abs(ref))


def test_engine_merged_handle_survives_absorb():
    """Single-shard fast path: a handed-out merged slab must stay readable
    after the next (donated) absorb invalidates the engine's state."""
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=4)
    eng = SegmentQueryEngine(spec)
    eng.absorb(np.arange(300), np.ones(300, np.float32))
    held = eng.merged
    before = int(jnp.sum(held.member))
    eng.absorb(np.arange(1000, 1300), np.ones(300, np.float32))
    assert int(jnp.sum(held.member)) == before   # not donated away
    assert eng.merged is not held


def test_engine_set_shard_copies_installed_slab():
    """A slab installed via set_shard must remain the CALLER's — the next
    absorb donates the resident buffers, never the installed handle."""
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=6)
    installed = C.multisketch_build(spec, np.arange(200),
                                    np.ones(200, np.float32))
    before = int(jnp.sum(installed.member))
    eng = SegmentQueryEngine(spec)
    eng.set_shard(0, installed)
    eng.absorb(np.arange(5000, 5200), np.ones(200, np.float32))
    assert int(jnp.sum(installed.member)) == before
    assert eng.query(C.COUNT) > 0


def test_engine_query_many_shapes_and_bucketing():
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=3)
    eng = SegmentQueryEngine(spec)
    eng.absorb(np.arange(300), np.ones(300, np.float32))
    out = eng.query_many(predicates=_predicates(5))   # padded to b_quantum
    assert out.shape == (2, 5)
    single = eng.query(C.SUM, C.key_range(0, 149))
    batch = eng.query_many((C.SUM,), (C.key_range(0, 149),))
    assert abs(single - float(batch[0, 0])) < 1e-5 * max(1.0, abs(single))


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax
    import repro.core as C
    from repro.launch.mesh import make_mesh
    from repro.launch.summary import sharded_multisketch
    from repro.launch.query import SegmentQueryEngine

    rng = np.random.default_rng(4)
    n = 4096
    keys = rng.permutation(np.arange(n)).astype(np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    mesh = make_mesh((4,), ("data",))
    spec = C.MultiSketchSpec(objectives=((C.SUM, 16), (C.COUNT, 8),
                                         (C.thresh(2.0), 12)), seed=13)
    eager = sharded_multisketch(spec, mesh, keys, w)
    eng = SegmentQueryEngine.from_sharded(spec, mesh, keys, w)
    lazy = eng.merged
    same = all(bool(np.array_equal(np.asarray(a), np.asarray(b)))
               for a, b in zip(lazy, eager))
    est = eng.query_many(predicates=[C.EVERYTHING,
                                     C.key_range(0, n // 2 - 1)])
    ok_est = abs(est[0, 0] / w.sum() - 1) < 0.5
    # the engine is resident on ONE device: its folds' Pallas kernels
    # cannot be partitioned over the mesh the rows were built on
    one_device = len(lazy.keys.devices()) == 1
    print("RESULT " + json.dumps({"same": bool(same),
                                  "est_ok": bool(ok_est),
                                  "one_device": one_device}))
""")


def test_engine_from_sharded_matches_eager_multidevice():
    """Lazy merge-on-demand over real per-device shards is bit-identical
    to the eager replicated sharded_multisketch re-selection, and runs on
    one device."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    assert json.loads(line[len("RESULT "):]) == {"same": True,
                                                 "est_ok": True,
                                                 "one_device": True}


# ------------------------------------------------ satellites
@pytest.mark.parametrize("n,k1,quantize", [
    (1000, 17, False), (700, 65, True), (256, 5, True), (50, 65, False),
    (513, 8, True), (2048, 129, True), (1, 3, False)])
def test_blocked_buffer_scan_bit_identical(n, k1, quantize):
    from repro.core.universal import _buffer_scan, _buffer_scan_ref
    rng = np.random.default_rng(n + k1)
    v = rng.exponential(1.0, n).astype(np.float32)
    if quantize:
        v = np.round(v * 8) / 8            # heavy value ties
    v[rng.random(n) > 0.9] = np.inf        # inactive sentinels mid-stream
    idx = rng.permutation(n).astype(np.int32)
    got = _buffer_scan(jnp.asarray(v), jnp.asarray(idx), k1)
    want = _buffer_scan_ref(jnp.asarray(v), jnp.asarray(idx), k1)
    for name, g, r in zip(("rank", "tail_v", "tail_i"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=name)


def test_blocked_buffer_scan_overflow_fallback():
    """Descending input saturates the inserted-subsequence bound; the
    lax.cond fallback must keep the result exact."""
    from repro.core.universal import _buffer_scan, _buffer_scan_ref
    rng = np.random.default_rng(0)
    v = np.sort(rng.exponential(1.0, 4096).astype(np.float32))[::-1].copy()
    idx = np.arange(4096, dtype=np.int32)
    got = _buffer_scan(jnp.asarray(v), jnp.asarray(idx), 9)
    want = _buffer_scan_ref(jnp.asarray(v), jnp.asarray(idx), 9)
    for g, r in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_blocked_buffer_scan_tie_eviction_at_capacity():
    """Ties AT the capacity boundary: an incoming value equal to the
    buffer tail still inserts (rank counts strictly smaller only) and
    evicts the old tail, so tail_v repeats while tail_i changes. The
    blocked scan must reproduce the reference's evict-last choice
    bit-exactly, including which index the emitted tail carries."""
    from repro.core.universal import _buffer_scan, _buffer_scan_ref
    rng = np.random.default_rng(7)
    # a 4-value alphabet over 1500 draws: the tail is almost always tied
    v = rng.choice(np.array([1.0, 2.0, 3.0, 4.0], np.float32), 1500)
    idx = np.arange(1500, dtype=np.int32)
    for k1 in (3, 17, 64):
        got = _buffer_scan(jnp.asarray(v), jnp.asarray(idx), k1)
        want = _buffer_scan_ref(jnp.asarray(v), jnp.asarray(idx), k1)
        for name, g, r in zip(("rank", "tail_v", "tail_i"), got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                          err_msg=f"k1={k1} {name}")
    # the emitted tail index must actually churn under ties (the evict
    # path runs), not just repeat the first tail forever
    ti = np.asarray(_buffer_scan(jnp.asarray(v), jnp.asarray(idx), 3)[2])
    assert len(set(ti[np.asarray(v) == 4.0].tolist())) > 1


def test_blocked_buffer_scan_all_equal_forces_full_replay():
    """All-equal values: every rank is 0, the whole stream 'inserts', the
    inserted-subsequence bound overflows and the lax.cond falls back to
    the full sequential replay — which must stay exact under total ties."""
    from repro.core.universal import (_buffer_scan, _buffer_scan_ref,
                                      _insert_bound)
    n, k1 = 4096, 9
    assert _insert_bound(n, k1) < n     # the compressed path CAN'T hold it
    v = np.full(n, 2.5, np.float32)
    idx = np.arange(n, dtype=np.int32)
    got = _buffer_scan(jnp.asarray(v), jnp.asarray(idx), k1)
    want = _buffer_scan_ref(jnp.asarray(v), jnp.asarray(idx), k1)
    for name, g, r in zip(("rank", "tail_v", "tail_i"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=name)


def test_merge_sketches_jit_cached_and_donatable():
    from repro.core.merge import _merge_jit
    rng = np.random.default_rng(1)
    n, k = 2000, 32
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1, n).astype(np.float32)
    act = np.ones(n, bool)
    cap = C.sketch_capacity(n, k)
    a = C.build_sketch(keys[:n // 2], w[:n // 2], act[:n // 2], k, cap, 0)
    b = C.build_sketch(keys[n // 2:], w[n // 2:], act[n // 2:], k, cap, 0)
    m0 = C.merge_sketches(a, b)
    misses = _merge_jit._cache_size()
    for _ in range(3):
        m1 = C.merge_sketches(a, b)
    assert _merge_jit._cache_size() == misses, "merge retraced per call"
    assert isinstance(m1.k, int) and m1.k == k   # static fields stay static
    # donated variant: same result from fresh (device-committed) copies
    fresh = lambda s: s._replace(
        keys=jnp.array(s.keys), weights=jnp.array(s.weights),
        probs=jnp.array(s.probs), member=jnp.array(s.member),
        valid=jnp.array(s.valid))
    m2 = C.merge_sketches(fresh(a), fresh(b), donate=True)
    np.testing.assert_array_equal(np.asarray(m0.keys), np.asarray(m2.keys))
    np.testing.assert_array_equal(np.asarray(m0.probs), np.asarray(m2.probs))


def test_query_many_B1_single_query_fast_path(monkeypatch):
    """Satellite regression pin (bench_query_engine_B1_F1 showed 0.5x):
    a B=1 query must run a ONE-row table — the same work unit as the
    one-query-at-a-time loop, sharing its jit-cached executable — while
    B in (1, b_quantum] still pads to the bucket."""
    import repro.core.multi_sketch as MS
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=15)
    eng = SegmentQueryEngine(spec)
    eng.absorb(np.arange(400), np.ones(400, np.float32))
    widths = []
    real = MS.multisketch_estimate_batch

    def spy(sk, fs, table, use_kernels=None):
        widths.append(np.asarray(table).shape[0])
        return real(sk, fs, table, use_kernels=use_kernels)

    monkeypatch.setattr(MS, "multisketch_estimate_batch", spy)
    single = eng.query(C.SUM, C.key_range(0, 199))
    assert widths[-1] == 1, "B=1 padded to a wider bucket"
    out5 = eng.query_many((C.SUM,), _predicates(5))
    assert widths[-1] == eng.b_quantum, "B in (1, quantum] must bucket"
    # same executable as the loop path's 1-predicate estimate: no retrace
    misses = MS._estimate_batch_jit._cache_size()
    loop = float(np.asarray(real(eng.merged, (C.SUM,),
                                 (C.key_range(0, 199),)))[0, 0])
    assert MS._estimate_batch_jit._cache_size() == misses
    assert abs(single - loop) <= 1e-5 * max(1.0, abs(loop))
    assert out5.shape == (1, 5)
    # B=0 (pre-encoded empty table) still buckets and returns empty
    out0 = eng.query_many((C.SUM,), np.zeros((0, PRED_COLS), np.int32))
    assert out0.shape == (1, 0)
    assert widths[-1] == eng.b_quantum


def test_collector_routes_queries_through_batched_path():
    from repro.telemetry.stats import StatsCollector, TelemetryConfig
    rng = np.random.default_rng(0)
    tel = StatsCollector(TelemetryConfig(k=48, capacity=512, seed=9))
    w = rng.lognormal(0, 1, 700).astype(np.float32)
    tel.absorb(np.arange(700), w)
    # predicate and callable segment paths agree
    q_pred = tel.query(C.SUM, C.key_range(0, 349))
    q_call = tel.query(C.SUM, segment_fn=lambda k: k < 350)
    assert abs(q_pred - q_call) <= 1e-3 * max(1.0, abs(q_call))
    qm = tel.query_many((C.SUM, C.COUNT),
                        (C.EVERYTHING, C.key_range(0, 349)))
    assert qm.shape == (2, 2)
    assert abs(qm[0, 0] - tel.query(C.SUM)) <= 1e-3 * abs(qm[0, 0])
    # repeated single queries reuse one executable (batched jit path)
    from repro.core.multi_sketch import _estimate_batch_jit
    misses = _estimate_batch_jit._cache_size()
    for _ in range(4):
        tel.query(C.SUM)
    assert _estimate_batch_jit._cache_size() == misses

"""MultiSketch subsystem tests: streaming-fold / merge / sharded-build
equivalence with the one-shot sample (exactness acceptance criteria), the
Pallas compaction kernel, and collector segment-query accuracy."""
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
from repro.telemetry.stats import StatsCollector, TelemetryConfig


def _objectives(nf):
    pool = [(C.SUM, 16), (C.COUNT, 8), (C.thresh(2.0), 12), (C.cap(1.5), 8),
            (C.moment(1.5), 8), (C.thresh(0.5), 8), (C.cap(4.0), 8),
            (C.moment(0.5), 8)]
    return tuple(pool[:nf])


def _data(n=2500, seed=0):
    rng = np.random.default_rng(seed)
    keys = rng.permutation(np.arange(5, 5 + n)).astype(np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    return keys, w


def _members(sk):
    m = np.asarray(sk.member)
    return dict(zip(np.asarray(sk.keys)[m].tolist(),
                    np.asarray(sk.probs)[m].tolist()))


def _reference(keys, w, objs, scheme, seed):
    ref = C.multi_bottomk_sample(keys, w, np.ones(len(keys), bool), objs,
                                 scheme=scheme, seed=seed)
    m = np.asarray(ref.member)
    return (dict(zip(keys[m].tolist(), np.asarray(ref.prob)[m].tolist())),
            np.asarray(ref.taus))


def _assert_same_sample(got: dict, want: dict):
    assert set(got) == set(want), sorted(set(got) ^ set(want))[:5]
    for k in want:
        assert abs(got[k] - want[k]) < 1e-5, (k, got[k], want[k])


@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("nf", [1, 3, 8])
def test_streaming_fold_matches_one_shot(scheme, nf):
    """Absorbing any chunking in any order == one-shot sample (member set,
    probs AND taus) — the §3.3 mergeability acceptance criterion."""
    keys, w = _data()
    objs = _objectives(nf)
    spec = C.MultiSketchSpec(objectives=objs, scheme=scheme, seed=11)
    want, want_taus = _reference(keys, w, objs, scheme, 11)

    rng = np.random.default_rng(1)
    for m, order_seed in ((3, 0), (7, 1)):
        perm = np.random.default_rng(order_seed).permutation(len(keys))
        st = C.multisketch_empty(spec)
        for ch in np.array_split(perm, m):
            st = C.multisketch_absorb(st, keys[ch], w[ch], spec=spec)
        _assert_same_sample(_members(st), want)
        np.testing.assert_allclose(np.asarray(st.taus), want_taus, rtol=1e-6)


def test_merge_and_merge_stacked_match_one_shot():
    keys, w = _data(n=3000, seed=3)
    objs = _objectives(3)
    spec = C.MultiSketchSpec(objectives=objs, seed=2)
    want, want_taus = _reference(keys, w, objs, "ppswor", 2)

    halves = np.array_split(np.arange(len(keys)), 2)
    a = C.multisketch_build(spec, keys[halves[0]], w[halves[0]])
    b = C.multisketch_build(spec, keys[halves[1]], w[halves[1]])
    m = C.multisketch_merge(spec, a, b)
    _assert_same_sample(_members(m), want)

    parts = [C.multisketch_build(spec, keys[i::4], w[i::4])
             for i in range(4)]
    stacked = C.MultiSketch(*jax.tree.map(lambda *xs: jnp.stack(xs), *parts))
    ms = C.multisketch_merge_stacked(spec, stacked)
    _assert_same_sample(_members(ms), want)
    np.testing.assert_allclose(np.asarray(ms.taus), want_taus, rtol=1e-6)


def test_merge_dedups_by_max_weight():
    """A key seen by two parts keeps max w (paper's merged-weight rule)."""
    spec = C.MultiSketchSpec(objectives=((C.SUM, 4),), seed=0)
    a = C.multisketch_build(spec, np.arange(6), np.full(6, 2.0, np.float32))
    b = C.multisketch_build(spec, np.arange(6),
                            np.array([9., 1., 1., 1., 1., 1.], np.float32))
    m = C.multisketch_merge(spec, a, b)
    got_w = {int(k): float(v) for k, v, ok in
             zip(np.asarray(m.keys), np.asarray(m.weights),
                 np.asarray(m.valid)) if ok}
    assert got_w[0] == 9.0
    assert all(v == 2.0 for k, v in got_w.items() if k != 0)


def test_inactive_duplicate_does_not_shadow_observation():
    """Regression: an INVALID higher-weight occurrence of a key in the same
    fold must not knock out the valid observation via the dedup mask."""
    spec = C.MultiSketchSpec(objectives=((C.SUM, 4),), seed=0)
    st = C.multisketch_empty(spec)
    st = C.multisketch_absorb(st, np.array([7, 7]),
                              np.array([5.0, 3.0], np.float32),
                              np.array([False, True]), spec=spec)
    m = np.asarray(st.member)
    assert int(m.sum()) == 1
    assert int(np.asarray(st.keys)[m][0]) == 7
    assert float(np.asarray(st.weights)[m][0]) == 3.0


@pytest.mark.parametrize("use_kernels", [True, False])
def test_negative_zero_weight_does_not_shadow_observation(use_kernels):
    """Regression: a valid -0.0 observation of a key (quarantine admits
    it: -0.0 < 0 is False) must not sort ahead of the key's positive
    observation in the dedup order and drop its weight."""
    spec = C.MultiSketchSpec(objectives=((C.SUM, 4),), seed=0)
    st = C.multisketch_empty(spec)
    st = C.multisketch_absorb(st, np.array([7, 7, 9]),
                              np.array([-0.0, 3.0, 1.0], np.float32),
                              spec=spec, use_kernels=use_kernels)
    got_w = {int(k): float(v) for k, v, ok in
             zip(np.asarray(st.keys), np.asarray(st.weights),
                 np.asarray(st.member)) if ok}
    assert got_w == {7: 3.0, 9: 1.0}


def test_xla_and_kernel_paths_identical():
    keys, w = _data(n=2048, seed=5)
    objs = _objectives(3)
    spec = C.MultiSketchSpec(objectives=objs, seed=7)
    a = C.multisketch_build(spec, keys, w, use_kernels=True)
    b = C.multisketch_build(spec, keys, w, use_kernels=False)
    np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
    np.testing.assert_array_equal(np.asarray(a.probs), np.asarray(b.probs))
    np.testing.assert_array_equal(np.asarray(a.taus), np.asarray(b.taus))


def test_compact_kernel_priority_and_dedup():
    """kernels.compact: members (weight desc) first, aux next, dups/invalid
    dropped — against a plain-numpy oracle."""
    from repro.kernels.compact import compact_take
    keys = jnp.asarray([-1, 2, 2, 3, 5, 5, 7, 9], jnp.int32)  # key-sorted
    w = jnp.asarray([9., 5., 4., 1., 7., 2., 3., 6.], jnp.float32)
    member = jnp.asarray([1, 0, 0, 1, 1, 0, 0, 0], bool)
    keep = jnp.asarray([1, 1, 1, 1, 1, 1, 0, 1], bool)
    take, valid = compact_take(keys, w, member, keep, 6)
    # retained: members {3(w1), 5(w7)} (slot0 invalid key, dup 5 dropped),
    # then aux {2(w5), 9(w6)}; dup-2, non-keep-7 dropped
    assert np.asarray(valid).tolist() == [True] * 4 + [False] * 2
    assert np.asarray(take)[:4].tolist() == [4, 3, 7, 1]


def test_stats_collector_streaming_and_segments():
    """Device-fold collector: chunked absorb accuracy on whole-set and
    segment queries vs exact sums (satellite acceptance)."""
    tel = StatsCollector(TelemetryConfig(k=48, capacity=512, seed=9))
    rng = np.random.default_rng(0)
    all_k, all_w = [], []
    for step in range(12):
        m = int(rng.integers(40, 160))           # ragged chunks
        w = rng.lognormal(0, 1, m).astype(np.float32)
        keys = step * 1000 + np.arange(m)
        tel.absorb(keys, w)
        all_k.append(keys)
        all_w.append(w)
    keys = np.concatenate(all_k)
    w = np.concatenate(all_w)
    slack = 4 / np.sqrt(47)                      # ~4 sigma at k=48
    assert abs(tel.query(C.SUM) / w.sum() - 1) < slack
    assert abs(tel.query(C.COUNT) / len(w) - 1) < slack
    # segment query: keys from steps >= 6, routed via sketch_estimate
    seg = lambda k: k >= 6000
    exact = w[keys >= 6000].sum()
    est = tel.query(C.SUM, segment_fn=seg)
    assert abs(est / exact - 1) < 2 * slack

    # merge_from: two collectors over disjoint streams == their union
    t2 = StatsCollector(TelemetryConfig(k=48, capacity=512, seed=9))
    t2.absorb(np.arange(50) + 500_000, np.ones(50, np.float32))
    tel.merge_from(t2)
    assert abs(tel.query(C.SUM) / (w.sum() + 50) - 1) < slack


def test_stats_collector_warns_once_on_overflow():
    """Satellite: a saturated pool (S ∪ Z possibly truncated) must raise
    a RuntimeWarning at query time — exactly once per collector — and
    expose the flag via ``.overflow``."""
    import warnings
    tel = StatsCollector(TelemetryConfig(k=48, capacity=64, chunk=64))
    # skewed weights: the SUM and COUNT bottom-k samples diverge, so
    # |S ∪ Z| wants ~2k slots and the 64-slot pool saturates
    w = np.random.default_rng(0).lognormal(0, 2, 512).astype(np.float32)
    tel.absorb(np.arange(512), w)
    assert tel.overflow
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        tel.query(C.SUM)
        tel.query(C.COUNT)               # second query: no second warning
    hits = [w for w in rec if "overflowed" in str(w.message)]
    assert len(hits) == 1 and issubclass(hits[0].category, RuntimeWarning)

    ok = StatsCollector(TelemetryConfig(k=8, capacity=512))
    ok.absorb(np.arange(64), np.ones(64, np.float32))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        ok.query(C.SUM)
    assert not ok.overflow
    assert not [w for w in rec if "overflowed" in str(w.message)]


def test_absorb_is_jit_cached_and_donated():
    """The fold reuses one compiled executable across same-shape chunks."""
    spec = C.MultiSketchSpec(objectives=((C.SUM, 8), (C.COUNT, 8)), seed=1)
    st = C.multisketch_empty(spec)
    from repro.core.multi_sketch import _absorb_jit
    misses0 = _absorb_jit._cache_size()
    for i in range(4):
        st = C.multisketch_absorb(st, np.arange(i * 64, (i + 1) * 64),
                                  np.ones(64, np.float32), spec=spec)
    assert _absorb_jit._cache_size() == misses0 + 1


@pytest.mark.parametrize("use_kernels", [False, True])
def test_build_dedups_repeated_key_by_max_weight(use_kernels):
    """A key repeated in one build batch is one key of its largest weight,
    and the build equals folding the batch into an empty sketch."""
    spec = C.MultiSketchSpec(objectives=((C.SUM, 2), (C.COUNT, 2)), seed=3)
    keys = np.array([7, 4, 7, 9, 4, 12], np.int32)
    w = np.array([3.0, 1.0, 5.0, 2.0, 0.5, 4.0], np.float32)
    sk = C.multisketch_build(spec, keys, w, use_kernels=use_kernels)
    valid = np.asarray(sk.valid)
    got = dict(zip(np.asarray(sk.keys)[valid].tolist(),
                   np.asarray(sk.weights)[valid].tolist()))
    assert got == {7: 5.0, 4: 1.0, 9: 2.0, 12: 4.0}
    folded = C.multisketch_absorb(C.multisketch_empty(spec), keys, w,
                                  spec=spec, use_kernels=use_kernels)
    for name, x, y in zip(C.MultiSketch._fields, sk, folded):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=name)


def _tie_keys():
    """215 keys whose COUNT seeds tie at the 16th and 17th places: the
    first two ids of [0, 2^20) with equal 24-bit u under hash seed 7, the
    15 ids just below them in u order and the 198 just above."""
    ids = np.arange(1 << 20)
    u24 = np.asarray(C.hash_u32(ids, 7)) >> 8
    order = np.lexsort((ids, u24))
    i = int(np.flatnonzero(np.diff(u24[order]) == 0)[0])
    keys = order[i - 15:i + 2 + 198].astype(np.int32)
    return np.random.default_rng(0).permutation(keys)


def _tie_samples(case, spec, keys, w):
    """(member keys, their p, taus, slab or None) of one sampler."""
    act = np.ones(len(keys), bool)
    if case in ("bottomk_sample", "multi_bottomk_sample"):
        s = (C.bottomk_sample(keys, w, act, C.COUNT, 16, seed=7)
             if case == "bottomk_sample" else
             C.multi_bottomk_sample(keys, w, act, ((C.COUNT, 16),), seed=7))
        m = np.asarray(s.member)
        taus = s.tau if case == "bottomk_sample" else s.taus
        return keys[m], np.asarray(s.prob)[m], np.atleast_1d(taus), None
    if case.startswith("build"):
        sk = C.multisketch_build(spec, keys, w,
                                 use_kernels=case == "build_kernels")
    elif case == "absorb":
        sk = C.multisketch_empty(spec)
        for part in np.array_split(np.arange(len(keys)), 3):
            sk = C.multisketch_absorb(sk, keys[part], w[part], spec=spec)
    else:
        a, b = np.array_split(np.arange(len(keys)), 2)
        sk = C.multisketch_merge(spec,
                                 C.multisketch_build(spec, keys[a], w[a]),
                                 C.multisketch_build(spec, keys[b], w[b]))
    m = np.asarray(sk.member)
    return np.asarray(sk.keys)[m], np.asarray(sk.probs)[m], sk.taus, sk


@pytest.mark.parametrize("case", ["build_xla", "build_kernels", "absorb",
                                  "merge", "bottomk_sample",
                                  "multi_bottomk_sample"])
def test_seed_tie_at_kth_place(case):
    """Two keys with equal seeds at the k-th and (k+1)-th places: every
    sampler keeps exactly k members, all with p > 0, the first k by
    (seed, key), and estimates COUNT as that reference sample does."""
    keys = _tie_keys()
    w = np.ones(len(keys), np.float32)
    spec = C.MultiSketchSpec(objectives=((C.COUNT, 16),), seed=7)
    seeds = np.asarray(C.f_seed(w, np.ones(len(keys), bool), C.COUNT,
                                C.uniform01(keys, 7), "ppswor"))
    order = np.lexsort((keys, seeds))
    assert seeds[order[15]] == seeds[order[16]]      # the tie is at k
    want_keys, want_tau = np.sort(keys[order[:16]]), seeds[order[16]]
    want_est = 16 / -np.expm1(-np.float64(want_tau))

    got_keys, got_p, taus, sk = _tie_samples(case, spec, keys, w)
    assert len(got_keys) == 16
    assert np.all(got_p > 0)
    np.testing.assert_array_equal(np.sort(got_keys), want_keys)
    assert np.asarray(taus).tolist() == [want_tau]
    est = float(np.sum(1.0 / got_p.astype(np.float64)))
    assert np.isfinite(est) and abs(est / want_est - 1) < 1e-6
    if sk is not None:
        assert abs(float(C.multisketch_estimate(sk, C.COUNT)) / want_est
                   - 1) < 1e-6
        base = C.multisketch_build(spec, keys, w, use_kernels=False)
        for name, x, y in zip(C.MultiSketch._fields, sk, base):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)
        # the tau the finalizer's rule recomputes from the slab
        from repro.core.bottomk import bottomk_members_any_order
        _, slab_taus, _ = bottomk_members_any_order(
            jnp.where(sk.valid[None, :], sk.seeds, jnp.inf), sk.keys, (16,))
        np.testing.assert_array_equal(np.asarray(slab_taus),
                                      np.asarray(sk.taus))


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import numpy as np
    import jax
    import repro.core as C
    from repro.launch.mesh import make_mesh
    from repro.launch.summary import sharded_multisketch

    rng = np.random.default_rng(4)
    n = 4096
    keys = rng.permutation(np.arange(n)).astype(np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    mesh = make_mesh((4,), ("data",))
    out = {}
    for nf, objs in (
            (1, ((C.SUM, 16),)),
            (3, ((C.SUM, 16), (C.COUNT, 8), (C.thresh(2.0), 12))),
            (8, ((C.SUM, 8), (C.COUNT, 8), (C.thresh(2.0), 8),
                 (C.cap(1.5), 8), (C.moment(1.5), 8), (C.thresh(0.5), 8),
                 (C.cap(4.0), 8), (C.moment(0.5), 8)))):
        spec = C.MultiSketchSpec(objectives=objs, seed=13)
        sk = sharded_multisketch(spec, mesh, keys, w)
        ref = C.multi_bottomk_sample(keys, w, np.ones(n, bool), objs,
                                     scheme="ppswor", seed=13)
        m = np.asarray(sk.member)
        got = dict(zip(np.asarray(sk.keys)[m].tolist(),
                       np.asarray(sk.probs)[m].tolist()))
        rm = np.asarray(ref.member)
        want = dict(zip(keys[rm].tolist(),
                        np.asarray(ref.prob)[rm].tolist()))
        ok = (set(got) == set(want)
              and all(abs(got[k] - want[k]) < 1e-5 for k in want)
              and np.allclose(np.asarray(sk.taus), np.asarray(ref.taus),
                              rtol=1e-6))
        out[str(nf)] = bool(ok)
    print("RESULT " + json.dumps(out))
""")


def test_sharded_build_matches_one_shot_multidevice():
    """shard_map local-build -> all_gather -> one re-selection equals the
    one-shot sample on a real 4-device (host) mesh, |F| in {1, 3, 8}."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out == {"1": True, "3": True, "8": True}

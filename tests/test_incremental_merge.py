"""Incremental + absorb-time merged-slab maintenance.

The lazy ladder (PR 5): the delta fold (``multisketch_absorb_into`` —
dirty shards folded into the cached merged slab, donated buffers) must be
BIT-IDENTICAL to the full stacked re-merge for any absorb history, across
schemes and |F|; an incremental epoch must dispatch the delta-fold
launches ONLY (no full ``merge_stacked``), the full path must stay
unchanged, and non-monotone mutations (set_shard / load_stacked) must
force the full path. Lazy-ladder tests pin ``absorb_time=False``.

Absorb-time maintenance (PR 7 default): every query under churn is a pure
cache hit — ZERO merge dispatches on the query path (asserted via
``tests.dispatch_spy``) — and the maintained slab is bit-identical to the
lazy full re-merge oracle. Plus the ClusterEngine twin: delta-aware
coords realignment bit-identical to the full candidate lookup.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as C
from repro.core.multi_sketch import MultiSketch, multisketch_absorb_into
from repro.launch import query as Q
from repro.launch.query import SegmentQueryEngine
from tests.dispatch_spy import spy_merge_dispatch
from tests.test_batched_multiobj import _count_pallas_calls


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


def _assert_bitsame(a: MultiSketch, b: MultiSketch, msg=""):
    for name, x, y in zip(MultiSketch._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{msg}{name}")


def _twin_engines(spec, shards, keys, w):
    """(incremental-enabled, forced-full) LAZY engines over the same
    absorbs — absorb-time maintenance off, so the query-time ladder
    (hit / delta fold / full re-merge) is what's under test."""
    inc = SegmentQueryEngine(spec, shards=shards, absorb_time=False)
    full = SegmentQueryEngine(spec, shards=shards, max_delta=0,
                              absorb_time=False)
    for i in range(shards):
        for e in (inc, full):
            e.absorb(keys[i::shards], w[i::shards], shard=i)
    return inc, full


# ------------------------------------------------- bit-identity, all specs
@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("nf", [1, 3, 8])
def test_incremental_bitidentical_to_full(scheme, nf):
    keys, w = _data()
    spec = C.MultiSketchSpec(objectives=_objectives(nf), scheme=scheme,
                             seed=11)
    inc, full = _twin_engines(spec, 3, keys, w)
    _assert_bitsame(inc._materialize_merged(), full._materialize_merged())
    # churn epochs: single-dirty-shard absorbs, re-merged incrementally
    rng = np.random.default_rng(nf)
    for it in range(3):
        ek = np.arange(90_000 + 500 * it, 90_000 + 500 * it + 300)
        ew = rng.lognormal(0, 1, 300).astype(np.float32)
        inc.absorb(ek, ew, shard=it % 3)
        full.absorb(ek, ew, shard=it % 3)
        _assert_bitsame(inc._materialize_merged(),
                        full._materialize_merged(), msg=f"epoch {it}: ")
    assert inc.merge_stats["incremental"] == 3
    assert inc.merge_stats["full"] == 1            # only the initial merge
    assert full.merge_stats["incremental"] == 0


def test_multi_dirty_delta_stacked_and_padded():
    """2 and 3 dirty shards of 4 between queries: the stacked (power-of-two
    padded) delta fold still matches the full re-merge bit-for-bit."""
    keys, w = _data(n=3000, seed=7)
    spec = C.MultiSketchSpec(objectives=_objectives(3), seed=3)
    inc, full = _twin_engines(spec, 4, keys, w)
    _assert_bitsame(inc._materialize_merged(), full._materialize_merged())
    rng = np.random.default_rng(1)
    for ndirty in (2, 3):
        for j in range(ndirty):
            ek = np.arange(50_000 + 1000 * ndirty + 100 * j,
                           50_000 + 1000 * ndirty + 100 * j + 80)
            ew = rng.lognormal(0, 1, 80).astype(np.float32)
            inc.absorb(ek, ew, shard=j)
            full.absorb(ek, ew, shard=j)
        _assert_bitsame(inc._materialize_merged(),
                        full._materialize_merged(), msg=f"{ndirty} dirty: ")
    assert inc.merge_stats["incremental"] == 2


def test_add_shard_rides_delta_path():
    """Cross-job fan-in only ADDS data -> the new slab is the delta."""
    keys, w = _data(n=2000, seed=2)
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=9)
    inc, full = _twin_engines(spec, 2, keys, w)
    inc._materialize_merged(), full._materialize_merged()
    other = C.multisketch_build(spec, np.arange(70_000, 70_500),
                                np.ones(500, np.float32))
    inc.add_shard(other)
    full.add_shard(other)
    _assert_bitsame(inc._materialize_merged(), full._materialize_merged())
    assert inc.merge_stats["incremental"] == 1
    # and both equal the one-shot union build
    union = C.multisketch_merge(spec, C.multisketch_build(spec, keys, w),
                                other)
    _assert_bitsame(inc._materialize_merged(), union, msg="vs union: ")


def test_set_shard_and_load_stacked_force_full():
    """Non-monotone mutations (shard content replaced) void the delta
    fold's containment premise — the engine must take the full path."""
    keys, w = _data(n=1500, seed=4)
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=5)
    eng = SegmentQueryEngine(spec, shards=2)
    eng.absorb(keys[::2], w[::2], shard=0)
    eng.absorb(keys[1::2], w[1::2], shard=1)
    eng._materialize_merged()
    n_full = eng.merge_stats["full"]
    replacement = C.multisketch_build(spec, np.arange(40_000, 40_300),
                                      np.ones(300, np.float32))
    eng.set_shard(1, replacement)
    eng._materialize_merged()
    assert eng.merge_stats["full"] == n_full + 1
    assert eng.merge_stats["incremental"] == 0
    # result reflects the REPLACED union, exactly
    want = C.multisketch_merge(
        spec, C.multisketch_build(spec, keys[::2], w[::2]), replacement)
    _assert_bitsame(eng._materialize_merged(), want)
    # load_stacked likewise drops the cache
    stacked = MultiSketch(*jax.tree.map(
        lambda *xs: jnp.stack(xs), *[replacement, replacement]))
    eng.load_stacked(stacked)
    eng._materialize_merged()
    assert eng.merge_stats["incremental"] == 0


def test_truncating_capacity_skips_incremental():
    """A capacity below the hard |S ∪ Z| bound may truncate, where delta
    and full paths can legitimately diverge — incremental must not run."""
    objs = _objectives(2)
    spec = C.MultiSketchSpec(objectives=objs, seed=1, capacity=8)
    keys, w = _data(n=800, seed=6)
    eng = SegmentQueryEngine(spec, shards=2)
    eng.absorb(keys[::2], w[::2], shard=0)
    eng.absorb(keys[1::2], w[1::2], shard=1)
    eng._materialize_merged()
    eng.absorb(np.arange(60_000, 60_100), np.ones(100, np.float32), shard=0)
    eng._materialize_merged()
    assert eng.merge_stats["incremental"] == 0
    assert eng.merge_stats["full"] == 2


# ------------------------------------------------- launch / dispatch counts
def test_incremental_epoch_dispatches_delta_fold_only(monkeypatch):
    """Incremental epoch = the delta fold ONLY (no full merge_stacked
    dispatch); full-path epochs and cache hits stay unchanged."""
    keys, w = _data(n=1200, seed=8)
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=2)
    eng = SegmentQueryEngine(spec, shards=2, absorb_time=False)
    eng.absorb(keys[::2], w[::2], shard=0)
    eng.absorb(keys[1::2], w[1::2], shard=1)
    eng._materialize_merged()                      # initial full merge
    with spy_merge_dispatch() as calls:
        eng.absorb(np.arange(30_000, 30_200), np.ones(200, np.float32),
                   shard=1)
        eng.query_many()                           # incremental epoch
        assert calls == {"full": 0, "inc": 1}
        eng.query_many()                           # cache hit: no dispatch
        assert calls == {"full": 0, "inc": 1}
        assert eng.merge_stats["hit"] >= 1
        # forced-full twin: merge_stacked only, never the delta fold
        eng.max_delta = 0
        eng.absorb(np.arange(31_000, 31_200), np.ones(200, np.float32),
                   shard=0)
        eng.query_many()
        assert calls == {"full": 1, "inc": 1}


@pytest.mark.parametrize("m", [1, 2, 4])
def test_delta_fold_launch_count_flat_in_dirty_shards(m):
    """The kernel-path delta fold is a fixed 4-launch chain (fused seeds,
    block-select, retention-priority, compacting block-select) regardless
    of how many dirty slabs ride in the delta. The capacity puts every
    delta size in the block plan of both selects
    (``blockselect.select_plan``); smaller folds swap a select for one
    XLA top_k, which ``test_select_plan_by_size`` covers."""
    spec = C.MultiSketchSpec(objectives=_objectives(3), seed=0,
                             capacity=256)
    keys, w = _data(n=900, seed=9)
    base = C.multisketch_build(spec, keys, w)
    parts = [C.multisketch_build(spec, np.arange(10_000 * (i + 1),
                                                 10_000 * (i + 1) + 200),
                                 np.ones(200, np.float32))
             for i in range(m)]
    delta = (parts[0] if m == 1 else
             MultiSketch(*jax.tree.map(lambda *xs: jnp.stack(xs), *parts)))
    dk = delta.keys.reshape(-1)
    dw = delta.weights.reshape(-1)
    dv = delta.valid.reshape(-1)
    from repro.core.multi_sketch import _rebuild

    def fold(sk, sw, sv, dk, dw, dv):
        return _rebuild(spec, jnp.concatenate([sk, dk]),
                        jnp.concatenate([sw, dw]),
                        jnp.concatenate([sv, dv]), use_kernels=True)
    jx = jax.make_jaxpr(fold)(base.keys, base.weights, base.valid,
                              dk, dw, dv)
    assert _count_pallas_calls(jx.jaxpr) == 4


def test_absorb_into_matches_merge_and_donates_state():
    """Direct core-level check: absorb_into == multisketch_merge, and the
    state argument's buffers are consumed (donated) on backends that
    support it while the delta slab stays usable."""
    spec = C.MultiSketchSpec(objectives=_objectives(3), seed=6)
    keys, w = _data(n=1000, seed=10)
    a = C.multisketch_build(spec, keys[:500], w[:500])
    b = C.multisketch_build(spec, keys[500:], w[500:])
    want = C.multisketch_merge(spec, a, b)
    state = jax.tree.map(jnp.copy, a)
    got = multisketch_absorb_into(state, b, spec=spec)
    _assert_bitsame(got, want)
    # the delta (a resident shard slab) must NOT be donated
    assert int(jnp.sum(b.valid)) > 0
    # kernel and XLA delta folds agree bit-for-bit
    state2 = jax.tree.map(jnp.copy, a)
    got_k = multisketch_absorb_into(state2, b, spec=spec, use_kernels=True)
    _assert_bitsame(got_k, want)


def test_merged_handle_survives_incremental_fold():
    """A handed-out merged slab must stay readable after the next epoch's
    delta fold (which donates only engine-owned buffers)."""
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=8)
    keys, w = _data(n=1000, seed=12)
    eng = SegmentQueryEngine(spec, shards=2, absorb_time=False)
    eng.absorb(keys[::2], w[::2], shard=0)
    eng.absorb(keys[1::2], w[1::2], shard=1)
    held = eng.merged                              # public handout
    snap = np.asarray(held.keys).copy()
    before = int(jnp.sum(held.member))
    eng.absorb(np.arange(20_000, 20_100), np.ones(100, np.float32), shard=0)
    assert eng._materialize_merged() is not held
    assert eng.merge_stats["incremental"] == 1
    assert int(jnp.sum(held.member)) == before     # not donated away
    np.testing.assert_array_equal(np.asarray(held.keys), snap)


# ------------------------------------------------- absorb-time maintenance
@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("nf", [1, 3])
def test_absorb_time_bitidentical_to_lazy_oracle(scheme, nf):
    """Absorb-time maintenance == the lazy full re-merge oracle, bit for
    bit, at every churn epoch — and the query path dispatches NOTHING."""
    keys, w = _data(n=2000, seed=13)
    spec = C.MultiSketchSpec(objectives=_objectives(nf), scheme=scheme,
                             seed=21)
    zm = SegmentQueryEngine(spec, shards=3)               # the default
    oracle = SegmentQueryEngine(spec, shards=3, max_delta=0,
                                absorb_time=False)
    for i in range(3):
        for e in (zm, oracle):
            e.absorb(keys[i::3], w[i::3], shard=i)
    # first query warms the cache (cold start takes the lazy ladder once)
    _assert_bitsame(zm._materialize_merged(), oracle._materialize_merged())
    rng = np.random.default_rng(nf)
    for it in range(4):
        ek = np.arange(80_000 + 400 * it, 80_000 + 400 * it + 250)
        ew = rng.lognormal(0, 1, 250).astype(np.float32)
        zm.absorb(ek, ew, shard=it % 3)
        oracle.absorb(ek, ew, shard=it % 3)
        with spy_merge_dispatch() as calls:
            got = zm._materialize_merged()
        assert calls == {"full": 0, "inc": 0}, f"epoch {it} dispatched"
        _assert_bitsame(got, oracle._materialize_merged(),
                        msg=f"epoch {it}: ")
    assert zm.merge_stats["absorb_time"] == 4
    assert zm.merge_stats["hit"] >= 4


def test_absorb_time_single_shard_realias_no_double_fold():
    """Single-shard engine: the maintained cache ALIASES the shard, so an
    absorb epoch folds the chunk ONCE (the shard fold is the merged-slab
    fold) and the next query is still a dispatch-free hit."""
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=14)
    keys, w = _data(n=900, seed=14)
    eng = SegmentQueryEngine(spec)                        # 1 shard
    eng.absorb(keys, w)
    eng._materialize_merged()                             # warm (alias)
    held = eng.merged                                     # public handout
    snap = np.asarray(held.keys).copy()
    eng.absorb(np.arange(70_000, 70_150), np.ones(150, np.float32))
    with spy_merge_dispatch() as calls:
        got = eng._materialize_merged()
    assert calls == {"full": 0, "inc": 0}
    assert got is eng._shards[0]                          # re-aliased
    np.testing.assert_array_equal(np.asarray(held.keys), snap)  # survived
    # oracle: one-shot build over the union
    want = C.multisketch_build(
        spec, np.concatenate([keys, np.arange(70_000, 70_150)]),
        np.concatenate([w, np.ones(150, np.float32)]))
    _assert_bitsame(got, want, msg="vs one-shot: ")


def test_absorb_time_add_shard_keeps_cache_current():
    """add_shard under a current cache folds the new slab at absorb time
    — next query hits, bit-identical to the eager union."""
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=15)
    keys, w = _data(n=1100, seed=15)
    eng = SegmentQueryEngine(spec, shards=2)
    eng.absorb(keys[::2], w[::2], shard=0)
    eng.absorb(keys[1::2], w[1::2], shard=1)
    eng._materialize_merged()
    other = C.multisketch_build(spec, np.arange(75_000, 75_400),
                                np.ones(400, np.float32))
    eng.add_shard(other)
    with spy_merge_dispatch() as calls:
        got = eng._materialize_merged()
    assert calls == {"full": 0, "inc": 0}
    union = C.multisketch_merge(spec, C.multisketch_build(spec, keys, w),
                                other)
    _assert_bitsame(got, union, msg="vs union: ")


def test_absorb_time_nonmonotone_falls_back_then_reseeds():
    """set_shard drops the cache (maintenance can't ride a replaced
    shard); the next query re-merges fully, and maintenance resumes from
    the re-seeded cache on the following absorb."""
    spec = C.MultiSketchSpec(objectives=_objectives(2), seed=16)
    keys, w = _data(n=800, seed=16)
    eng = SegmentQueryEngine(spec, shards=2)
    eng.absorb(keys[::2], w[::2], shard=0)
    eng.absorb(keys[1::2], w[1::2], shard=1)
    eng._materialize_merged()
    repl = C.multisketch_build(spec, np.arange(42_000, 42_200),
                               np.ones(200, np.float32))
    eng.set_shard(1, repl)
    n_at = eng.merge_stats["absorb_time"]
    eng._materialize_merged()                             # full re-merge
    assert eng.merge_stats["full"] >= 2
    eng.absorb(np.arange(43_000, 43_100), np.ones(100, np.float32), shard=0)
    assert eng.merge_stats["absorb_time"] == n_at + 1     # resumed
    with spy_merge_dispatch() as calls:
        got = eng._materialize_merged()
    assert calls == {"full": 0, "inc": 0}
    want = C.multisketch_merge(
        spec,
        C.multisketch_merge(spec,
                            C.multisketch_build(spec, keys[::2], w[::2]),
                            repl),
        C.multisketch_build(spec, np.arange(43_000, 43_100),
                            np.ones(100, np.float32)))
    _assert_bitsame(got, want, msg="vs union: ")


# ------------------------------------------------- cluster coords twin
def test_align_coords_delta_bit_identical():
    from repro.launch.cluster import _align_coords, _align_coords_delta
    rng = np.random.default_rng(3)
    cap, dim, chunk = 96, 4, 40
    pts = rng.normal(0, 2, (400, dim)).astype(np.float32)
    old_keys = np.full(cap, -1, np.int32)
    occ = rng.permutation(cap)[:60]
    old_keys[occ] = rng.choice(300, 60, replace=False)
    old_coords = np.where(old_keys[:, None] >= 0, pts[old_keys], 0.0)
    # chunk: half re-presented old keys (same coords), half new
    ck = np.concatenate([old_keys[occ[:20]],
                         np.arange(300, 300 + chunk - 20)]).astype(np.int32)
    cc = pts[ck].astype(np.float32)
    # new slab: a shuffle of old ∪ chunk keys plus empty slots
    new_keys = np.full(cap, -1, np.int32)
    pool = np.concatenate([old_keys[old_keys >= 0], ck])
    pick = rng.choice(pool, 80, replace=False)
    new_keys[rng.permutation(cap)[:80]] = pick
    want = _align_coords(jnp.asarray(new_keys),
                         jnp.concatenate([jnp.asarray(old_keys),
                                          jnp.asarray(ck)]),
                         jnp.concatenate([jnp.asarray(old_coords),
                                          jnp.asarray(cc)]))
    got = _align_coords_delta(jnp.asarray(new_keys), jnp.asarray(old_keys),
                              jnp.asarray(old_coords), jnp.asarray(ck),
                              jnp.asarray(cc))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_cluster_engine_streaming_alignment_after_delta_path():
    """End-to-end: streamed absorbs keep every slab slot's coords equal to
    its key's true point under the delta realignment."""
    from repro.launch.cluster import ClusterEngine
    rng = np.random.default_rng(5)
    n, dim = 600, 3
    X = rng.normal(0, 3, (n, dim)).astype(np.float32)
    eng = ClusterEngine(dim=dim, k=24, seed=0, chunk=128)
    for s in range(0, n, 150):
        eng.absorb(X[s:s + 150])
    ks = np.asarray(eng._sketch.keys)
    vv = np.asarray(eng._sketch.valid)
    cs = np.asarray(eng._coords)
    sel = vv & (ks >= 0)
    np.testing.assert_allclose(cs[sel], X[ks[sel]], rtol=0, atol=0)

"""Scale-out tier acceptance: multi-host pool vs the single-host oracle.

The contract under test (ISSUE 10 / core.merge failure-semantics):

  * the cross-host merged answer is BIT-IDENTICAL to a never-failed
    single-host union engine over the same records (threshold closure
    through per-host merged slabs, one shared fold family);
  * a host loss degrades reads to the replicated last-good slab at STALE
    (labeled, never wrong), absorbs to a durable pending backlog;
  * rebalance rebuilds a dead host's shards bit-exactly from checkpoint +
    WAL tail and logs the re-partition as a REBALANCE marker;
  * recovery replays data + GC + REBALANCE markers in seq order to the
    identical post-move layout; a LOST marker recovers the pre-move
    placement with bit-identical merged answers.
"""
import os

import jax
import numpy as np
import pytest

import repro.core as C
from repro.launch.cluster import ClusterEngine
from repro.launch.pool import (FRESH, REJECTED, STALE, HostDownError,
                               RejectedError, ShardedEnginePool,
                               compute_placement, rendezvous_owner)
from repro.launch.query import SegmentQueryEngine
from repro.launch.wal import REBALANCE_SHARD, WriteAheadLog
from repro.telemetry.stats import collect_host_gauges

from tests.faults import FaultInjector, tear_wal

HOSTS = (0, 1, 2, 3)
SHARDS = 16


@pytest.fixture(scope="module", autouse=True)
def _release_executables():
    # This module compiles a large family of per-host fold/merge/query
    # executables (4 full-width engines x many epochs) on top of an
    # already-long tier-1 run; on a small CI box the accumulated native
    # code arenas can crash a LATER module's compile. Drop them at
    # module teardown — later modules recompile what they need.
    yield
    jax.clear_caches()


def _spec(seed=0):
    return C.MultiSketchSpec(objectives=((C.SUM, 16), (C.COUNT, 8)),
                             seed=seed, capacity=128)


def _chunks(n_chunks=18, n=60, seed=3, shards=SHARDS):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_chunks):
        keys = (i * n + np.arange(n)).astype(np.int32)
        w = rng.lognormal(0, 1.5, n).astype(np.float32)
        out.append((int(rng.integers(0, shards)), keys, w))
    return out


def _fast_pool(**kw):
    kw.setdefault("hosts", HOSTS)
    kw.setdefault("sleep", lambda s: None)
    kw.setdefault("backoff_base", 1e-4)
    return ShardedEnginePool(**kw)


def _twin(chunks, spec=None, shards=SHARDS):
    """The never-failed single-host union oracle."""
    eng = SegmentQueryEngine(spec or _spec(), shards=shards)
    for sh, k, w in chunks:
        eng.absorb(k, w, shard=sh)
    return eng


def _assert_slabs_equal(a, b):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"leaf {name} diverged")


def _feed(pool, chunks, name="t"):
    for sh, k, w in chunks:
        pool.absorb(name, k, w, shard=sh)


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------

def test_rendezvous_placement_is_deterministic_and_total():
    p1 = compute_placement(SHARDS, HOSTS)
    p2 = compute_placement(SHARDS, list(reversed(HOSTS)))
    assert p1 == p2                        # order-free
    assert set(p1) <= set(HOSTS)
    # every host owns something at this shard:host ratio
    assert set(p1) == set(HOSTS)
    assert rendezvous_owner(0, (5,)) == 5
    with pytest.raises(ValueError):
        rendezvous_owner(0, ())


def test_rendezvous_movement_is_minimal_under_membership_change():
    base = compute_placement(64, HOSTS)
    # removing a host moves ONLY its shards
    down = compute_placement(64, (0, 1, 3))
    moved = [s for s in range(64) if base[s] != down[s]]
    assert moved and all(base[s] == 2 for s in moved)
    # adding a host only PULLS shards onto it
    up = compute_placement(64, HOSTS + (4,))
    moved = [s for s in range(64) if base[s] != up[s]]
    assert moved and all(up[s] == 4 for s in moved)


def test_absorb_fans_out_to_owner_hosts_only():
    pool = _fast_pool()
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks(10)
    _feed(pool, chunks)
    touched = {sh for sh, _, _ in chunks}
    for hid in HOSTS:
        eng = pool._hosts[hid].engines.get("t")
        owned = {s for s in touched if placement[s] == hid}
        if eng is None:
            assert not owned
            continue
        for s in range(SHARDS):
            assert eng.shard_live(s) == (s in owned)


# ---------------------------------------------------------------------------
# cross-host reads: exactness + caching
# ---------------------------------------------------------------------------

def test_query_bit_identical_to_single_host_union_engine():
    pool = _fast_pool()
    pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks()
    _feed(pool, chunks)
    twin = _twin(chunks)
    r = pool.query("t")
    assert r.status == FRESH and r.epoch_lag == 0
    np.testing.assert_array_equal(r.values, twin.query_many())
    preds = [C.key_range(0, 300), C.key_range(301, 10**6)]
    r2 = pool.query("t", predicates=preds)
    np.testing.assert_array_equal(r2.values,
                                  twin.query_many(predicates=preds))


def test_cross_host_merge_is_memoized_per_epoch():
    pool = _fast_pool()
    pool.create_stream("t", _spec(), shards=SHARDS)
    _feed(pool, _chunks(6))
    pool.query("t")
    st = pool._stream("t")
    merges = st.cross_merges
    assert merges >= 1
    for _ in range(5):
        assert pool.query("t").status == FRESH
    assert st.cross_merges == merges       # steady-state reads: zero merges
    sh, k, w = _chunks(1, seed=99)[0]
    pool.absorb("t", k, w, shard=sh)
    pool.query("t")
    assert st.cross_merges == merges + 1   # one re-selection per new epoch


def test_query_timeout_zero_is_rejected():
    t = [5.0]
    pool = _fast_pool(clock=lambda: t[0])
    pool.create_stream("t", _spec(), shards=4)
    r = pool.query("t", timeout=0)
    assert r.status == REJECTED and r.error == "deadline"
    assert pool.query("t", timeout=10.0).status == FRESH


# ---------------------------------------------------------------------------
# host loss: replica reads, pending backlog, follower promotion
# ---------------------------------------------------------------------------

def test_host_kill_serves_stale_from_replica_with_exact_lag():
    pool = _fast_pool()
    pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks()
    _feed(pool, chunks)
    good = pool.query("t")
    assert good.status == FRESH
    pool.kill_host(HOSTS[0])
    r = pool.query("t")
    assert r.status == STALE and r.error is not None
    np.testing.assert_array_equal(r.values, good.values)
    # lag counts chunks accepted after the replica was captured
    extra = _chunks(3, seed=11)
    for sh, k, w in extra:
        rec = pool.absorb("t", k, w, shard=sh)
        assert rec.seq > 0
    r2 = pool.query("t")
    assert r2.status == STALE and r2.epoch_lag >= len(extra)


def test_follower_promotion_survives_primary_replica_host_loss():
    pool = _fast_pool()
    pool.create_stream("t", _spec(), shards=SHARDS)
    _feed(pool, _chunks())
    good = pool.query("t")
    st = pool._stream("t")
    primary, follower = pool._replica_hosts(st)
    pool.kill_host(primary)               # replica + owned shards gone
    r = pool.query("t")
    assert r.status == STALE
    np.testing.assert_array_equal(r.values, good.values)
    assert st.name in pool._hosts[follower].replicas
    # losing the follower too wipes every replica -> REJECTED, labeled
    pool.kill_host(follower)
    r2 = pool.query("t")
    assert r2.status == REJECTED and r2.values is None
    assert r2.error is not None


def test_dead_owner_absorbs_stay_pending_durable_and_shed_at_limit(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path), pending_limit=4)
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    _feed(pool, _chunks(4))
    victim = placement[0]
    pool.kill_host(victim)
    dead_shard = placement.index(victim)
    k, w = np.arange(50, dtype=np.int32) + 10**6, np.ones(50, np.float32)
    for i in range(4):
        rec = pool.absorb("t", k + i * 50, w, shard=dead_shard)
        assert rec.durable and not rec.applied
    with pytest.raises(RejectedError):
        pool.absorb("t", k + 999, w, shard=dead_shard)
    s = pool.stats("t")
    assert s["pending"] == 4 and s["epoch_lag"] == 4
    assert not s["owners_alive"]


def test_fault_injector_kill_schedule_fires_at_exact_op_index():
    pool = _fast_pool()
    pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks(8)
    with FaultInjector() as inj:
        inj.kill_host(pool, HOSTS[1], at=5)
        for i, (sh, k, w) in enumerate(chunks):
            pool.absorb("t", k, w, shard=sh)
            if inj.calls.get("host_op", 0) <= 5:
                assert pool._hosts[HOSTS[1]].alive
        assert inj.fired["host_op"] == 1
    assert not pool._hosts[HOSTS[1]].alive


# ---------------------------------------------------------------------------
# rebalance: hand-off, dead-host rebuild, REBALANCE marker
# ---------------------------------------------------------------------------

def test_rebalance_after_kill_rebuilds_bit_identically(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path))
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks()
    _feed(pool, chunks)
    victim = placement[0]
    pool.kill_host(victim)
    extra = _chunks(4, seed=21)           # some land pending on the dead host
    for sh, k, w in extra:
        pool.absorb("t", k, w, shard=sh)
    out = pool.rebalance("t")["t"]
    assert out["error"] is None and out["moved"]
    assert all(o == victim for s, (o, n) in out["moved"].items())
    assert victim not in out["placement"]
    r = pool.query("t")
    twin = _twin(chunks + extra)
    assert r.status == FRESH and r.epoch_lag == 0
    np.testing.assert_array_equal(r.values, twin.query_many())
    # the re-partition was WAL-marked with the full placement
    recs = [rec for rec in pool._stream("t").wal.replay()
            if rec.shard == REBALANCE_SHARD]
    assert len(recs) == 1
    assert tuple(int(x) for x in recs[0].keys) == out["placement"]


def test_live_handoff_on_join_and_leave_is_bit_identical(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path))
    pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks()
    _feed(pool, chunks)
    twin = _twin(chunks)
    pool.host_join(9)
    out = pool.rebalance("t")["t"]
    assert out["moved"] and all(n == 9 for s, (o, n) in out["moved"].items())
    r = pool.query("t")
    assert r.status == FRESH
    np.testing.assert_array_equal(r.values, twin.query_many())
    # graceful decommission hands every shard back off the host
    pool.host_leave(9)
    assert 9 not in pool.hosts
    assert 9 not in pool.placement("t")
    r2 = pool.query("t")
    assert r2.status == FRESH
    np.testing.assert_array_equal(r2.values, twin.query_many())


def test_recovery_replays_rebalance_marker_to_identical_layout(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path))
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks()
    _feed(pool, chunks)
    pool.kill_host(placement[0])
    out = pool.rebalance("t")["t"]
    after = _chunks(3, seed=31)           # post-move records in the WAL
    for sh, k, w in after:
        pool.absorb("t", k, w, shard=sh)
    pool.close()
    pool2 = ShardedEnginePool.open(str(tmp_path), sleep=lambda s: None)
    assert pool2.placement("t") == out["placement"]
    twin = _twin(chunks + after)
    r = pool2.query("t")
    assert r.status == FRESH
    np.testing.assert_array_equal(r.values, twin.query_many())
    # per-host slabs landed on the replayed owners, bit-exactly
    st = pool2._stream("t")
    for s in range(SHARDS):
        hid = st.placement[s]
        eng = pool2._hosts[hid].engines.get("t")
        if eng is not None and eng.shard_live(s):
            _assert_slabs_equal(eng.shard_slab(s), twin._shards[s])
    pool2.close()


def test_lost_rebalance_marker_recovers_pre_move_placement(tmp_path):
    """The PR 7 lost-GC-marker contract, for REBALANCE: a marker that
    never became durable recovers the PRE-move placement — same union,
    bit-identical answers."""
    pool = _fast_pool(durability_dir=str(tmp_path))
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks()
    _feed(pool, chunks)
    twin = _twin(chunks)
    with FaultInjector() as inj:
        inj.fail_next("wal_append", 1)
        out = pool.rebalance("t", exclude=(placement[0],))["t"]
    assert out["moved"]
    assert out["error"] and "marker" in out["error"]
    pool.close()
    pool2 = ShardedEnginePool.open(str(tmp_path), sleep=lambda s: None)
    assert pool2.placement("t") == tuple(placement)   # pre-move layout
    r = pool2.query("t")
    assert r.status == FRESH
    np.testing.assert_array_equal(r.values, twin.query_many())
    pool2.close()


def test_torn_rebalance_marker_recovers_pre_move_placement(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path))
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks()
    _feed(pool, chunks)
    twin = _twin(chunks)
    out = pool.rebalance("t", exclude=(placement[0],))["t"]
    assert out["moved"] and out["error"] is None
    pool.close()
    # crash tore the marker frame mid-write
    tear_wal(str(tmp_path / "t" / "wal.log"), drop_bytes=7)
    pool2 = ShardedEnginePool.open(str(tmp_path), sleep=lambda s: None)
    assert pool2.placement("t") == tuple(placement)
    r = pool2.query("t")
    assert r.status == FRESH
    np.testing.assert_array_equal(r.values, twin.query_many())
    pool2.close()


def test_snapshot_plus_wal_tail_recovery_is_bit_identical(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path), snapshot_every=5,
                      keep_snapshots=2)
    pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks(17)
    _feed(pool, chunks)
    assert pool._stream("t").snapshot_seqs          # snapshots happened
    pool.close()
    pool2 = ShardedEnginePool.open(str(tmp_path), sleep=lambda s: None)
    r = pool2.query("t")
    assert r.status == FRESH
    np.testing.assert_array_equal(r.values, _twin(chunks).query_many())
    pool2.close()


def test_snapshot_refuses_while_an_owner_is_down(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path))
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    _feed(pool, _chunks(4))
    pool.kill_host(placement[0])
    with pytest.raises(HostDownError):
        pool.snapshot("t")


# ---------------------------------------------------------------------------
# availability smoke: host loss mid-stream, then rebalance
# ---------------------------------------------------------------------------

def test_availability_smoke_host_kill_mid_stream(tmp_path):
    pool = _fast_pool(durability_dir=str(tmp_path), pending_limit=256)
    placement = pool.create_stream("t", _spec(), shards=SHARDS)
    chunks = _chunks(40, seed=7)
    twin = SegmentQueryEngine(_spec(), shards=SHARDS)
    statuses = {FRESH: 0, STALE: 0, REJECTED: 0}
    unlabeled = 0
    with FaultInjector() as inj:
        inj.kill_host(pool, placement[0], at=20)
        for sh, k, w in chunks:
            try:
                pool.absorb("t", k, w, shard=sh)
            except RejectedError:
                continue                   # shed ingest is not a read miss
            twin.absorb(k, w, shard=sh)
            r = pool.query("t")
            statuses[r.status] += 1
            if r.status == FRESH:
                # an unlabeled answer = FRESH that is not the exact truth
                if (r.epoch_lag != 0
                        or not np.array_equal(r.values, twin.query_many())):
                    unlabeled += 1
            elif r.status == STALE:
                if r.values is None or (r.epoch_lag == 0
                                        and r.error is None):
                    unlabeled += 1
    total = sum(statuses.values())
    availability = (statuses[FRESH] + statuses[STALE]) / total
    assert availability >= 0.99, statuses
    assert unlabeled == 0
    # post-recovery: rebalance, then answers match the never-failed twin
    assert pool.rebalance("t")["t"]["error"] is None
    r = pool.query("t")
    assert r.status == FRESH
    np.testing.assert_array_equal(r.values, twin.query_many())
    pool.close()


# ---------------------------------------------------------------------------
# per-host gauges + cluster-tier replica hand-off
# ---------------------------------------------------------------------------

def test_host_stats_and_telemetry_gauges():
    pool = _fast_pool()
    pool.create_stream("t", _spec(), shards=SHARDS)
    _feed(pool, _chunks(8))
    pool.query("t")
    g = collect_host_gauges(pool)
    assert set(g["hosts"]) == set(HOSTS)
    assert g["totals"]["hosts_alive"] == len(HOSTS)
    assert g["totals"]["owned_shards"] == SHARDS
    assert g["totals"]["live_shards"] >= 1
    assert g["totals"]["bytes_resident"] > 0
    assert g["totals"]["replica_streams"] == 2   # primary + follower
    pool.kill_host(HOSTS[0])
    g2 = collect_host_gauges(pool)
    assert g2["totals"]["hosts_alive"] == len(HOSTS) - 1
    assert not g2["hosts"][HOSTS[0]]["alive"]
    assert g2["hosts"][HOSTS[0]]["live_shards"] == 0


def test_cluster_engine_handoff_promotes_bit_identical_follower():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(400, 3)).astype(np.float32)
    src = ClusterEngine(dim=3, k=32, seed=5, chunk=64)
    src.absorb(X[:250])
    follower = ClusterEngine.from_handoff(src.handoff())
    from repro.core.costs import cost_query
    q = cost_query(X[:4], 2.0)
    np.testing.assert_array_equal(src.service_costs(q),
                                  follower.service_costs(q))
    # the frozen normalizers rode along: continued absorbs on both sides
    # stay sample-coordinated, bit for bit
    src.absorb(X[250:])
    follower.absorb(X[250:])
    np.testing.assert_array_equal(src.service_costs(q),
                                  follower.service_costs(q))
    _assert_slabs_equal(src._sketch, follower._sketch)

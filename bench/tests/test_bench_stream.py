"""The key-value stream cells (``kv_dashboard.ingest``, ``.read``) at
small sizes on the CPU: each cell's path end to end, the control and the
faults that the comparison must fail, and the yardstick's own parts (the
reference's tie rule, the predicate and hash semantics, the kernels' byte
counts, the staged ``BENCHMARK.json`` entries)."""
import os
import tempfile

import numpy as np
import pytest

import stream_tiny
import harness
import ref_stream
import stream_cost as sc
import stream_gen

DEVICE_METRICS = ("idle_share", "roofline", "fold_device")
LAYER = {"kv_dashboard.ingest": {"wal_append_ms", "snapshot_ms"},
         "kv_dashboard.read": {"pump_ms", "coalesced_rows"}}
E2E = {"kv_dashboard.ingest": {"ingest_events_per_s", "setup_s"},
       "kv_dashboard.read": {"query_p50_ms", "query_p99_ms", "setup_s"}}


@pytest.fixture
def root(tmp_path):
    return stream_tiny.stage(str(tmp_path))


def _failed(checks):
    return [k for k, c in checks.items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", stream_tiny.CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_stream_cell_runs_end_to_end_and_is_correct(cell, trace, root):
    r = stream_tiny.run(cell, root, trace=trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"member_diff", "prob_gap", "answer_gap",
                                "recovered_diff", "guarantee_breaks"}
    names = set(r["metrics"])
    assert names == (LAYER[cell] if trace else E2E[cell])
    assert not any(d in n for n in names for d in DEVICE_METRICS)
    for m in r["metrics"].values():
        assert np.isfinite(m["value"]) and m["value"] > 0


@pytest.mark.parametrize("cell", stream_tiny.CELLS)
def test_stream_control_is_not_correct(cell, root):
    r = stream_tiny.run(cell, root, control=True)
    assert not r["correct"], r["checks"]
    assert _failed(r["checks"])


def _faults(monkeypatch, fault):
    import repro.launch.query as Q
    real_absorb = Q.SegmentQueryEngine.absorb
    if fault == "dropped":
        calls = {"n": 0}

        def absorb(self, keys, weights, active=None, shard=0):
            calls["n"] += 1
            if calls["n"] == 2:            # acknowledged, never folded
                return None
            return real_absorb(self, keys, weights, active, shard)
        monkeypatch.setattr(Q.SegmentQueryEngine, "absorb", absorb)
    elif fault == "half":
        def absorb(self, keys, weights, active=None, shard=0):
            act = np.ones(np.shape(keys), bool)
            act[act.shape[0] // 2:] = False
            return real_absorb(self, keys, weights, act, shard)
        monkeypatch.setattr(Q.SegmentQueryEngine, "absorb", absorb)
    elif fault == "flipped":
        real = Q.SegmentQueryEngine._materialize_merged

        def merged(self):
            sk = real(self)
            return sk._replace(member=sk.member.at[0].set(~sk.member[0]))
        monkeypatch.setattr(Q.SegmentQueryEngine, "_materialize_merged",
                            merged)
    else:
        real_q = Q.multisketch_query_many

        def query_many(*a, **kw):
            return np.asarray(real_q(*a, **kw)) * 1.01
        monkeypatch.setattr(Q, "multisketch_query_many", query_many)


@pytest.mark.parametrize("fault,cell", [
    ("dropped", "kv_dashboard.ingest"), ("half", "kv_dashboard.ingest"),
    ("flipped", "kv_dashboard.ingest"), ("altered", "kv_dashboard.ingest"),
    ("dropped", "kv_dashboard.read"), ("flipped", "kv_dashboard.read"),
    ("altered", "kv_dashboard.read")])
def test_stream_broken_timed_path_is_not_correct(fault, cell, root,
                                                  monkeypatch):
    _faults(monkeypatch, fault)
    r = stream_tiny.run(cell, root)
    assert not r["correct"], r["checks"]
    assert _failed(r["checks"])


def _tied_pair(seed: int):
    """Two ids whose 24-bit hashes are equal under ``seed``."""
    ids = np.arange(1 << 16)
    h = ref_stream.hash_u32(ids, seed) >> np.uint32(8)
    order = np.argsort(h, kind="stable")
    same = np.flatnonzero(h[order][1:] == h[order][:-1])[0]
    return sorted(int(x) for x in order[same:same + 2])


@pytest.mark.parametrize("k", [1, 2])
def test_reference_breaks_seed_ties_by_key(k):
    # COUNT seeds depend on the hash alone: a tied pair at the k-th place
    a, b = _tied_pair(7)
    u = ref_stream.uniform01(np.array([a, b]), 7)
    assert u[0] == u[1]
    ids = np.arange(1 << 16)
    uu = ref_stream.uniform01(ids, 7)
    below = [int(i) for i in ids[uu < u[0]][:k - 1]]
    above = [int(i) for i in ids[uu > u[0]][:3]]
    wmax = np.zeros(1 << 16, np.float32)
    wmax[below + [a, b] + above] = 1.5
    s = ref_stream.sample(wmax, [["count", 0, k]], 7)
    assert s.keys.tolist() == sorted(below + [a])   # exactly k members
    assert np.all(s.probs > 0)
    assert s.taus[0] == pytest.approx(-np.log1p(-float(u[0])), rel=1e-6)
    # every key a member when k covers them all: p = 1, answers exact
    s = ref_stream.sample(wmax, [["count", 0, 16]], 7)
    assert np.all(s.probs == 1.0)
    every = np.array([[0, (1 << 16) - 1, 0, 0, 0, 0]], np.int32)
    assert s.answers(every)[0, 0] == len(below) + 2 + len(above)


def test_reference_hash_and_predicates_match_the_wire_format():
    import jax.numpy as jnp
    from repro.core.hashing import hash_u32
    from repro.core.predicates import predicate_matrix
    keys = np.concatenate([np.arange(0, 4096, 7), [-1, 2**31 - 1]])
    assert np.array_equal(ref_stream.hash_u32(keys, 150907445),
                          np.asarray(hash_u32(jnp.asarray(keys, jnp.int32),
                                              150907445)))
    rng = stream_gen.rng_of(2**40 + 3, 5)
    table = np.concatenate([stream_gen.predicates(rng, 12, 12, 11),
                            stream_gen.NEVER_TABLE,
                            [[0, 4095, 0xF, 3, 0, 0]]]).astype(np.int32)
    assert np.array_equal(ref_stream.predicate_match(keys, table),
                          np.asarray(predicate_matrix(keys, table)))


def test_generators_are_deterministic_by_seed():
    ev = {"chunk_log2": 10, "ids_log2": 16, "zipf_s": 0.99,
          "pareto_alpha": 1.5}
    big = 2**33 + 12345
    k1, w1 = stream_gen.stream_chunk(big, 3, ev)
    k2, w2 = stream_gen.stream_chunk(big, 3, ev)
    k3, _ = stream_gen.stream_chunk(big + 1, 3, ev)
    assert k1.dtype == np.int32 and w1.dtype == np.float32
    assert np.array_equal(k1, k2) and np.array_equal(w1, w2)
    assert not np.array_equal(k1, k3)
    assert k1.min() >= 0 and k1.max() < 1 << 16 and w1.min() >= 1.0


def test_stream_kernel_bytes_by_hand():
    assert sc.fused_seeds(1024, 5) == 1024 * (4 + 4 + 1) + 2 * 5 * 1024 * 4
    assert sc.block_select(5, 2048, 1025) == 5 * 2048 * 4 + 5 * 1025 * 8
    assert sc.retention_priority(640) == 640 * (4 + 4 + 1 + 1 + 4)
    assert sc.segment_query(5126, 16, 5) == (5126 * (4 + 4 + 4 + 1)
                                             + 16 * 6 * 4 + 5 * 16 * 4)
    peak = {"hbm_bytes_per_s": 819e9}
    assert sc.least_seconds(819e9, peak) == pytest.approx(1.0)


def test_pending_entries_are_whole():
    add = stream_tiny.ENTRIES
    cells = {w["name"] for w in add["workloads"]}
    assert cells == set(stream_tiny.CELLS)
    e2e = {m["name"]: m for m in add["end_to_end"]}
    for m in add["end_to_end"]:
        assert set(m["workloads"]) <= cells
    for m in add["per_layer"]:
        assert os.path.isfile(os.path.join(stream_tiny.BENCH, "metrics",
                                           m["name"] + ".py"))
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        assert m["source"] in ("host_clock", "device_trace")
    bench = stream_tiny.merged(harness.benchmark())
    for w in add["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] == 1
        kind = stream_tiny.TRAFFIC[w["name"]]["kind"]
        assert os.path.isfile(os.path.join(stream_tiny.BENCH, "kinds",
                                           kind + ".py"))
        cell = harness.find_cell(w["name"], root=stream_tiny.stage(
            tempfile.mkdtemp(prefix="kv-stage-")), bench=bench)
        assert cell.config["reduced"] == []
        assert {m["name"] for m in cell.per_layer} == {
            m["name"] for m in add["per_layer"]
            if w["name"] in m["workloads"]}


@pytest.mark.parametrize("cell", stream_tiny.CELLS)
def test_stream_limits_lie_between_program_and_control_readings(cell, root):
    # the staged limits of the two gaps: above what the program reads at
    # these sizes, below what the control reads, on three seeds
    limits = stream_tiny.CONFIG["limits"]
    for seed in (2**33 + 11, 2**31 + 5, 977):
        r = stream_tiny.run(cell, root, seed=seed, readings=True)
        assert r["correct"] and not r["control_correct"]
        for k in ("prob_gap", "answer_gap"):
            assert r["checks"][k]["value"] * 10 <= limits[k]
        assert _failed(r["control_checks"])

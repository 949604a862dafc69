"""Program spans and counters (``repro.telemetry.trace``): recorded only
inside a profiler session, nested as the search and the engine nest them,
counting what the padded tables hold and each jit cache miss once, and
written into the profiler's own trace on its clock."""
import collections
import glob
import os

import jax
import numpy as np
import pytest

from repro.core.costs import cost_query, cost_table
from repro.launch.cluster import ClusterEngine, local_search
from repro.telemetry import trace


def _points(n=500, dim=5, seed=0):
    rng = np.random.default_rng(seed)
    ctrs = rng.normal(0, 5.0, (4, dim))
    return (ctrs[rng.integers(0, 4, n)]
            + rng.normal(0, 0.7, (n, dim))).astype(np.float32)


@pytest.fixture(scope="module")
def engine():
    eng = ClusterEngine.fit(_points(), k=40, mu=2.0, seed=5, q_quantum=16)
    local_search(eng, 3, rounds=1, n_cand=8, tol=-1.0)    # warm the shapes
    return eng


@pytest.fixture(autouse=True)
def _fresh():
    trace.reset()
    yield
    trace.reset()


def test_nothing_recorded_outside_a_profiler_session(engine):
    local_search(engine, 3, rounds=2, n_cand=8, tol=-1.0)
    engine.service_costs(cost_query(_points()[:2]))
    jax.jit(lambda x: x * 3 + 1)(np.ones((7, 3), np.float32))  # a new trace
    trace.count("n", lambda: 1 / 0)          # a lazy count is not taken
    snap = trace.snapshot()
    assert snap.spans == [] and snap.counters == {}
    # off, a span is the same shared null context whatever its name
    assert trace.span("a") is trace.span("b")


def test_search_span_tree_and_counters(engine, tmp_path):
    shapes = []

    def scorer(table):
        shapes.append(np.shape(table.centers))
        return engine.service_costs(table)

    with jax.profiler.trace(str(tmp_path)):
        res = local_search(engine, 3, rounds=2, n_cand=8, tol=-1.0,
                           scorer=scorer)
    assert res.rounds == 2
    snap = trace.snapshot()
    pairs = collections.Counter((n, p) for n, _, _, p in snap.spans)
    assert pairs == collections.Counter({
        ("cluster.search", None): 1,
        ("cluster.search.seed", "cluster.search"): 1,
        ("cluster.search.round", "cluster.search"): 2,
        ("cluster.search.build", "cluster.search.round"): 2,
        ("cluster.search.encode", "cluster.search.round"): 2,
        ("cluster.score", "cluster.search.seed"): 1,
        ("cluster.score", "cluster.search.round"): 2,
        ("cluster.score.prep", "cluster.score"): 3,
        ("cluster.score.dispatch", "cluster.score"): 3,
        ("cluster.score.wait", "cluster.score"): 3})
    # each span lies inside a span of its parent's name
    for name, t0, t1, parent in snap.spans:
        assert t0 <= t1
        if parent is not None:
            assert any(n == parent and p0 <= t0 and t1 <= p1
                       for n, p0, p1, _ in snap.spans), name

    # the bytes are those of the tables padded to the 16-set quantum
    q = [s[0] for s in shapes]
    qpad = [-(-x // 16) * 16 for x in q]
    row = [s[1] * s[2] * 4 + s[1] + 12 for s in shapes]  # f32, bool, 3 x 4 B
    assert q == [1, 25, 25] and qpad == [16, 32, 32]
    # only the seed's one-set table is on the host: the rounds' tables are
    # built on the device and never uploaded
    assert snap.counters == {
        "cluster.score.sets": sum(q),
        "cluster.score.table_bytes": sum(p * r for p, r in zip(qpad, row)),
        "cluster.score.upload_bytes": qpad[0] * row[0]}


def test_jax_traces_counts_a_new_q_bucket_once(engine, tmp_path):
    sets = np.broadcast_to(_points()[:3], (16 * 23 - 5, 3, 5))
    table = cost_table(sets, 2.0)
    with jax.profiler.trace(str(tmp_path)):
        engine.service_costs(table)
        first = trace.snapshot().counters
        engine.service_costs(table)
        second = trace.snapshot().counters
    assert first["jax.traces"] == 1
    assert second["jax.traces"] == 1


def test_a_search_warmed_as_the_benchmark_warms_it_traces_nothing(tmp_path):
    """Set-up as the benchmark's: a search of no rounds, then one host
    table of the round's shape; the rounds that follow (``swap_centers``,
    device pad, kernel) trace nothing."""
    eng = ClusterEngine.fit(_points(dim=6, seed=4), k=40, mu=2.0, seed=5,
                            q_quantum=16)
    local_search(eng, 4, rounds=0, n_cand=8)
    eng.service_costs(cost_table(np.broadcast_to(_points(dim=6)[:4],
                                                 (1 + 4 * 8, 4, 6)), 2.0))
    with jax.profiler.trace(str(tmp_path)):
        res = local_search(eng, 4, rounds=2, n_cand=8, tol=-1.0)
    assert res.rounds == 2
    assert trace.snapshot().counters.get("jax.traces", 0) == 0


@pytest.mark.parametrize("uk", [True, False])
def test_a_search_of_no_rounds_warms_the_rounds(uk, tmp_path):
    """A search of no rounds leaves nothing for the rounds of a search at
    the same centre count to trace: the round's one program for its
    device table, its centers and the read of a set's rows."""
    eng = ClusterEngine.fit(_points(dim=6, seed=4), k=40, mu=2.0, seed=5,
                            q_quantum=16, use_kernels=uk)
    local_search(eng, 4, rounds=0, n_cand=8)
    with jax.profiler.trace(str(tmp_path)):
        res = local_search(eng, 4, rounds=2, n_cand=8, tol=-1.0,
                           scorer=lambda t: (t.centers[0], t.cvalid[0],
                                             eng.service_costs(t))[-1])
    assert res.rounds == 2
    assert trace.snapshot().counters.get("jax.traces", 0) == 0


def test_spans_are_written_into_the_profilers_trace(engine, tmp_path):
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("test.outer"):
            local_search(engine, 3, rounds=1, n_cand=8, tol=-1.0)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events[ev.name].append(
                    (line.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    (line, o0, o1), = events["test.outer"]
    for name in ("cluster.search", "cluster.search.seed",
                 "cluster.search.round", "cluster.search.build",
                 "cluster.search.encode", "cluster.score",
                 "cluster.score.prep", "cluster.score.dispatch",
                 "cluster.score.wait"):
        assert events[name], name
        for ln, s, e in events[name]:
            assert ln == line and o0 <= s <= e <= o1, name
    assert len(events["cluster.score.wait"]) == 2


def test_records_past_the_cap_are_counted_as_dropped(monkeypatch, tmp_path):
    monkeypatch.setattr(trace, "MAX_SPANS", 2)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(5):
            with trace.span("x"):
                pass
        trace.count("n", 3)
        trace.count("n")
        trace.count("n", lambda: 2)
    snap = trace.snapshot()
    assert [r[0] for r in snap.spans] == ["x", "x"]
    assert snap.counters == {"trace.dropped": 3, "n": 6}

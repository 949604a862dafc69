"""Metric-space clustering subsystem: fused service-cost kernel vs the XLA
oracle across mu x Q x schemes, single-launch flatness in Q and |C|,
ball-density edge cases, the coords-aligned streaming ClusterEngine, and
the sample-based optimizer vs its exact-cost twin on small instances."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as C
from repro.core.costs import (CostTable, ball_query, cost_query, cost_table,
                              encode_cost_queries, estimate_bucketed,
                              pad_cost_table, swap_cost_table)
from repro.kernels import ref as R
from repro.kernels.servicecost import service_cost_slab
from repro.launch.cluster import (ClusterEngine, exact_scorer, kcenter,
                                  local_search)
from tests.test_batched_multiobj import _count_pallas_calls


def _points(n=400, dim=3, seed=0, spread=6.0):
    rng = np.random.default_rng(seed)
    ctrs = rng.normal(0, spread, (4, dim))
    return (ctrs[rng.integers(0, 4, n)]
            + rng.normal(0, 0.7, (n, dim))).astype(np.float32)


_ENGINES = {}


def _engine(scheme):
    if scheme not in _ENGINES:
        _ENGINES[scheme] = ClusterEngine.fit(_points(), k=48, mu=2.0,
                                             scheme=scheme, seed=3)
    return _ENGINES[scheme]


def _queries(q, mu, dim=3, seed=1):
    """q queries cycling through ragged cost sets and ball rows."""
    rng = np.random.default_rng(seed)
    X = _points(seed=0)
    out = []
    for i in range(q):
        m = int(rng.integers(1, 6))
        ctr = X[rng.integers(0, X.shape[0], m)] + rng.normal(0, 0.1, (m, dim))
        if i % 4 == 3:
            out.append(ball_query(ctr, radius=float(rng.random() * 5)))
        else:
            out.append(cost_query(ctr, mu=mu))
    return encode_cost_queries(out)


# ------------------------------------------------ kernel vs oracles
@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("q", [1, 16, 128])
@pytest.mark.parametrize("mu", [1.0, 2.0])
def test_service_cost_kernel_vs_oracle(scheme, q, mu):
    eng = _engine(scheme)
    pts, probs, member = eng.sample()
    table = _queries(q, mu)
    got = np.asarray(service_cost_slab(pts, probs, member, table))
    xla = np.asarray(C.estimate_service_costs(pts, probs, member, table,
                                              use_kernels=False))
    ref = np.asarray(R.service_cost_ref(pts, probs, member, table))
    assert got.shape == (q,)
    np.testing.assert_allclose(got, xla, rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-3)
    assert np.all(np.isfinite(got)) and np.all(got >= 0)


def test_service_cost_weighted_points():
    eng = _engine("ppswor")
    pts, probs, member = eng.sample()
    pw = np.random.default_rng(5).random(pts.shape[0]).astype(np.float32)
    table = _queries(8, 2.0)
    got = np.asarray(service_cost_slab(pts, probs, member, table,
                                       point_weights=pw))
    xla = np.asarray(C.estimate_service_costs(pts, probs, member, table,
                                              point_weights=pw,
                                              use_kernels=False))
    np.testing.assert_allclose(got, xla, rtol=2e-4, atol=1e-3)


def test_pad_rows_estimate_exactly_zero():
    eng = _engine("ppswor")
    pts, probs, member = eng.sample()
    table = pad_cost_table(_queries(5, 1.0), 16)
    for uk in (True, False):
        got = np.asarray(C.estimate_service_costs(pts, probs, member, table,
                                                  use_kernels=uk))
        assert got.shape == (16,)
        np.testing.assert_array_equal(got[5:], np.zeros(11, np.float32))


def test_encode_cost_queries_validation():
    with pytest.raises(ValueError):
        encode_cost_queries([])
    with pytest.raises(ValueError):
        encode_cost_queries([cost_query(np.zeros((2, 3))),
                             cost_query(np.zeros((2, 4)))])
    with pytest.raises(ValueError):
        encode_cost_queries([cost_query(np.zeros((4, 3)))], cmax=2)
    t = encode_cost_queries([cost_query(np.zeros((1, 3))),
                             cost_query(np.zeros((4, 3)))])
    assert t.centers.shape == (2, 4, 3)
    assert t.cvalid.sum() == 5


# ------------------------------------------------ single-launch flatness
@pytest.mark.parametrize("q,cm", [(1, 2), (16, 8), (128, 8), (16, 64)])
def test_service_cost_launch_count_flat_in_Q_and_C(q, cm):
    """ONE pallas launch per batch, for every (Q, |C|) combination."""
    rng = np.random.default_rng(0)
    pts = rng.normal(0, 1, (300, 3)).astype(np.float32)
    probs = np.clip(rng.random(300), 0.1, 1).astype(np.float32)
    member = rng.random(300) > 0.5
    table = cost_table(rng.normal(0, 1, (q, cm, 3)).astype(np.float32), 2.0)
    jx = jax.make_jaxpr(
        lambda p, pr, m, t: service_cost_slab(p, pr, m, t))(
            jnp.asarray(pts), jnp.asarray(probs), jnp.asarray(member),
            C.CostTable(*(jnp.asarray(x) for x in table)))
    assert _count_pallas_calls(jx.jaxpr) == 1


# ------------------------------------------------ ball-density edges
def test_ball_density_edge_cases():
    X = _points(seed=2)
    eng = ClusterEngine.fit(X, k=48, mu=1.0, seed=7)
    pts, probs, member = eng.sample()
    diam = float(np.max(np.linalg.norm(X[None] - X[:, None], axis=-1)))

    # r >= diameter: every point covered -> the estimate IS the HT count
    cover = eng.ball_density(X[0], diam * 1.01)
    assert cover == pytest.approx(eng.total_count(), rel=1e-5)
    assert cover == pytest.approx(len(X), rel=0.35)  # CV sanity

    # r = 0: no blow-up, kernel == oracle exactly, bounded by the count
    t0 = encode_cost_queries([ball_query(X[0], 0.0),
                              ball_query(X[0] + 100.0, 0.0)])
    k0 = np.asarray(service_cost_slab(pts, probs, member, t0))
    x0 = np.asarray(C.estimate_service_costs(pts, probs, member, t0,
                                             use_kernels=False))
    np.testing.assert_allclose(k0, x0, rtol=1e-5)
    assert np.all(np.isfinite(k0)) and np.all(k0 >= 0)
    assert k0[1] == 0.0                       # far empty ball: exactly 0
    assert k0[0] <= eng.total_count() + 1e-3

    # empty center set: 0 in both modes
    te = C.CostTable(centers=np.zeros((1, 2, X.shape[1]), np.float32),
                     cvalid=np.zeros((1, 2), bool),
                     mu=np.ones(1, np.float32), param=np.ones(1, np.float32),
                     mode=np.array([C.MODE_BALL], np.int32))
    assert float(service_cost_slab(pts, probs, member, te)[0]) == 0.0


def test_ball_density_monotone_in_radius():
    eng = _engine("ppswor")
    q = _points(seed=0)[0]
    ests = [eng.ball_density(q, r) for r in (0.5, 1.5, 4.0, 50.0)]
    assert all(a <= b + 1e-4 for a, b in zip(ests, ests[1:]))


# ------------------------------------------------ engine: streaming state
def test_cluster_engine_streaming_coords_aligned():
    X = _points(n=500, seed=4)
    eng = ClusterEngine(dim=3, k=48, mu=2.0, seed=1)
    for i in range(3):
        eng.absorb(X[i::3])
    assert eng.epoch == 3
    sk = eng._sketch
    keys = np.asarray(sk.keys)
    coords = np.asarray(eng._coords)
    # recover each absorbed chunk's global keys -> original rows
    order = np.concatenate([np.arange(500)[i::3] for i in range(3)])
    for s in np.nonzero(np.asarray(sk.valid))[0]:
        np.testing.assert_array_equal(coords[s], X[order[keys[s]]])
    # estimates reflect the union: cost of the true centers within HT error
    est = eng.clustering_cost(X[:4])
    exact = float(C.exact_service_costs(X, cost_query(X[:4], 2.0))[0])
    assert est == pytest.approx(exact, rel=0.5)


def test_cluster_engine_sample_survives_absorb():
    """A handed-out sample() must stay readable after the next (donated)
    absorb — same guard as the query engine's merged-slab hand-out."""
    rng = np.random.default_rng(9)
    eng = ClusterEngine(dim=2, k=32, seed=0)
    eng.absorb(rng.normal(0, 1, (200, 2)).astype(np.float32))
    coords, probs, member = eng.sample()
    before = float(jnp.sum(jnp.where(member, probs, 0.0)))
    eng.absorb(rng.normal(0, 1, (200, 2)).astype(np.float32))
    assert float(jnp.sum(jnp.where(member, probs, 0.0))) == before
    assert coords.shape == eng.sample()[0].shape


def test_service_costs_q_chunking_matches_one_shot():
    """A batch past one Q-tile of the kernel (several tiles in ONE launch)
    matches the unchunked XLA batch."""
    from repro.kernels.servicecost import _q_tile
    eng = _engine("ppswor")
    table = _queries(150, 2.0)
    assert _q_tile(150, 64) < 150
    got = eng.service_costs(table)
    pts, probs, member = eng.sample()
    want = np.asarray(C.estimate_service_costs(pts, probs, member, table,
                                               use_kernels=False))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-3)
    wide = encode_cost_queries(
        [cost_query(np.repeat(c[:1], 64, axis=0), 2.0) for c in
         np.asarray(table.centers)[:150]])
    got_w = service_cost_slab(pts, probs, member, wide)
    want_w = np.asarray(C.estimate_service_costs(pts, probs, member, wide,
                                                 use_kernels=False))
    np.testing.assert_allclose(got_w, want_w, rtol=2e-4, atol=1e-3)


def test_cluster_engine_explicit_keys_never_collide_with_default():
    """A default-keyed absorb after an explicit-keyed one must mint fresh
    ids — colliding ids would pair one point's prob with another's coords."""
    rng = np.random.default_rng(13)
    eng = ClusterEngine(dim=2, k=32, seed=0)
    X1 = rng.normal(0, 1, (100, 2)).astype(np.float32)
    X2 = rng.normal(5, 1, (100, 2)).astype(np.float32)
    eng.absorb(X1, keys=np.arange(40, 140))
    eng.absorb(X2)                                  # must start at key 140
    sk = eng._sketch
    keys = np.asarray(sk.keys)[np.asarray(sk.valid)]
    coords = np.asarray(eng._coords)[np.asarray(sk.valid)]
    both = np.concatenate([X1, X2])
    lookup = {40 + i: both[i] for i in range(200)}
    for ky, co in zip(keys, coords):
        np.testing.assert_array_equal(co, lookup[int(ky)])


def test_local_search_zero_rounds_returns_scored_init():
    eng = _engine("ppswor")
    res = local_search(eng, k=3, rounds=0, n_cand=8)
    assert res.rounds == 0 and len(res.history) == 1
    assert res.est_cost == pytest.approx(
        float(eng.service_costs(cost_query(res.centers, eng.mu))[0]),
        rel=1e-6)


def test_cluster_engine_absorb_grows_count():
    eng = ClusterEngine(dim=2, k=32, seed=0)
    rng = np.random.default_rng(0)
    eng.absorb(rng.normal(0, 1, (200, 2)).astype(np.float32))
    c1 = eng.total_count()
    eng.absorb(rng.normal(0, 1, (200, 2)).astype(np.float32))
    assert eng.total_count() > c1
    assert eng.epoch == 2


# ------------------------------------------------ device-built swap tables
def _host_swap_table(cur, cand, mu):
    """A swap round's table as the host encodes it: row 0 the current set,
    row 1 + i*n + j the set with center i swapped for candidate j."""
    k, n = cur.shape[0], cand.shape[0]
    sets = np.broadcast_to(cur, (k * n,) + cur.shape).copy()
    sets = sets.reshape(k, n, k, -1)
    for i in range(k):
        sets[i, :, i, :] = cand
    return cost_table(np.concatenate([cur[None], sets.reshape(k * n, k, -1)]),
                      mu)


@pytest.mark.parametrize("k,n,dim", [(1, 8, 3), (3, 8, 3), (4, 5, 13),
                                     (2, 7, 68)])
def test_swap_cost_table_matches_host_construction(k, n, dim):
    """The device-built table's five fields equal the host encoding bit for
    bit (k = 1, ragged candidate pools, dims off the sublane quantum)."""
    rng = np.random.default_rng(k * 100 + n)
    cur = rng.normal(0, 3, (k, dim)).astype(np.float32)
    cand = rng.normal(0, 3, (n, dim)).astype(np.float32)
    cand[0, 0] = -0.0                                # a sign bit survives
    want = _host_swap_table(cur, cand, 2.0)
    got = swap_cost_table(jnp.asarray(cur), jnp.asarray(cand), 2.0)
    assert got.centers.shape == (1 + k * n, k, dim)
    for name, w, g in zip(CostTable._fields, want, got):
        assert isinstance(g, jax.Array), name
        g = np.asarray(g)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), name
        assert g.tobytes() == w.tobytes(), name


@pytest.mark.parametrize("k,n_cand,slab", [(1, 8, 48), (3, 8, 48),
                                           (3, 32, 12)])
def test_local_search_device_tables_match_host_tables(k, n_cand, slab):
    """The search scores its device-built tables as it scores the same
    tables brought back to numpy: same centers, history and rounds (the
    last case's sample holds fewer members than ``n_cand``)."""
    eng = ClusterEngine.fit(_points(dim=5), k=slab, mu=2.0, seed=3)
    host = []

    def on_host(t):
        host.append(t)
        return eng.service_costs(CostTable(*(np.asarray(x) for x in t)))

    a = local_search(eng, k, rounds=4, n_cand=n_cand, tol=-1.0)
    b = local_search(eng, k, rounds=4, n_cand=n_cand, tol=-1.0,
                     scorer=on_host)
    assert isinstance(host[1].centers, jax.Array)
    if slab < n_cand:
        assert host[1].centers.shape[0] < 1 + k * n_cand
    np.testing.assert_array_equal(a.centers, b.centers)
    assert a.history == b.history and a.rounds == b.rounds == 4


@pytest.mark.parametrize("k,n", [(2, 7), (3, 5), (4, 4)])
@pytest.mark.parametrize("uk", [True, False])
def test_service_costs_device_table_matches_host_table(uk, k, n, tmp_path):
    """A device table scores bit for bit as the same table in numpy, on the
    kernel and the XLA path, with Q just below, at and just above a bucket
    edge; the answer holds Q rows, none of the bucket's null rows; only
    the host table's fields are counted as uploaded, and both count the
    table as padded to the bucket."""
    from repro.telemetry import trace
    eng = ClusterEngine.fit(_points(dim=5), k=48, mu=2.0, seed=3,
                            q_quantum=16, use_kernels=uk)
    rng = np.random.default_rng(7)
    dev = swap_cost_table(
        jnp.asarray(rng.normal(0, 3, (k, 5)).astype(np.float32)),
        jnp.asarray(rng.normal(0, 3, (n, 5)).astype(np.float32)), 2.0)
    host = CostTable(*(np.asarray(x) for x in dev))
    q = 1 + k * n
    outs, counters = [], []
    trace.reset()
    try:
        for t in (dev, host):
            with jax.profiler.trace(str(tmp_path)):
                outs.append(eng.service_costs(t))
            counters.append(trace.snapshot().counters)
            trace.reset()
    finally:
        trace.reset()
    assert outs[0].shape == outs[1].shape == (q,)
    np.testing.assert_array_equal(outs[0], outs[1])
    assert np.all(outs[0] > 0)
    padded = sum(x.nbytes for x in pad_cost_table(host, -(-q // 16) * 16))
    assert counters[0]["cluster.score.upload_bytes"] == 0
    assert counters[1]["cluster.score.upload_bytes"] == padded
    assert (counters[0]["cluster.score.table_bytes"]
            == counters[1]["cluster.score.table_bytes"] == padded)


@pytest.mark.parametrize("uk", [True, False])
def test_in_program_pad_rows_estimate_exactly_zero(uk):
    """The null rows a device table is padded with inside the launch
    estimate exactly 0, and its own rows are the host-padded table's."""
    eng = _engine("ppswor")
    pts, probs, member = eng.sample()
    t = swap_cost_table(jnp.ones((2, 3), jnp.float32),
                        jnp.zeros((4, 3), jnp.float32), 1.0)     # Q = 9
    got = np.asarray(estimate_bucketed(pts, probs, member, t, 16, uk))
    want = np.asarray(C.estimate_service_costs(
        pts, probs, member, pad_cost_table(t, 16), use_kernels=uk))
    assert got.shape == (16,)
    np.testing.assert_array_equal(got[9:], np.zeros(7, np.float32))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("uk", [True, False])
def test_service_costs_launches_one_program_for_a_device_table(uk):
    """Scoring a device table is one jitted launch: no separate pad, no
    second program around the estimate."""
    from repro.core import costs
    from repro.kernels import servicecost
    from repro.launch import cluster
    from tests.dispatch_spy import spy_jit_calls
    eng = ClusterEngine.fit(_points(dim=5), k=48, mu=2.0, seed=3,
                            q_quantum=16, use_kernels=uk)
    dev = swap_cost_table(jnp.ones((3, 5), jnp.float32),
                          jnp.zeros((7, 5), jnp.float32), 2.0)
    want = eng.service_costs(dev)                     # compiles
    with spy_jit_calls(costs, servicecost, cluster) as calls:
        got = eng.service_costs(dev)
    assert calls == {"repro.launch.cluster.estimate_bucketed": 1}
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------ optimizer vs exact oracle
@pytest.mark.parametrize("inst,mu", [(0, 2.0), (1, 1.0), (2, 2.0)])
def test_local_search_matches_exact_on_small_instances(inst, mu):
    """Acceptance: the sample-scored search's EXACT cost is within the HT
    estimate's error bound of the exact-scored search's cost, >= 3 small
    synthetic instances."""
    X = _points(n=300, dim=2, seed=10 + inst, spread=7.0)
    eng = ClusterEngine.fit(X, k=64, mu=mu, seed=inst)
    res_s = local_search(eng, k=3, mu=mu, rounds=10, n_cand=16)
    res_e = local_search(eng, k=3, mu=mu, rounds=10, n_cand=16,
                         scorer=exact_scorer(X))
    ex_s = float(C.exact_service_costs(X, cost_query(res_s.centers, mu))[0])
    ex_e = float(C.exact_service_costs(X, cost_query(res_e.centers, mu))[0])
    # HT error bound at the slab's sample size (cv_bound, q=1), 3 sigma
    bound = 3.0 * C.cv_bound(1.0, eng.k)
    assert ex_s <= ex_e * (1.0 + bound) + 1e-6
    # the search's own estimate agrees with ground truth within the bound
    assert res_s.est_cost == pytest.approx(ex_s, rel=bound)
    # history is monotone improving
    assert all(a >= b for a, b in zip(res_s.history, res_s.history[1:]))


def test_kcenter_covers_sample():
    X = _points(n=400, dim=2, seed=20, spread=10.0)
    eng = ClusterEngine.fit(X, k=64, mu=1.0, seed=0)
    kc = kcenter(eng, 4)
    assert kc.centers.shape == (4, 2)
    # at the returned radius every sampled point is served -> the estimated
    # coverage equals the estimated total exactly (same HT sum)
    assert kc.coverage_est == pytest.approx(kc.total_est, rel=1e-5)
    # well-separated clusters: radius far below the cluster spread
    assert kc.radius < 6.0


# ------------------------------------------------ metric-domain refactor
def test_farthest_point_jit_matches_host_loop():
    """The lax.fori_loop traversal must reproduce the seed's sequential
    host loop exactly (same columns, same argmax tie-breaks)."""
    from repro.core.metric_domains import _pairwise_dist, \
        farthest_point_anchors
    X = jnp.asarray(_points(n=200, seed=6))
    anchors = [0]
    d_min = _pairwise_dist(X, X[:1]).reshape(-1)
    for _ in range(7):
        nxt = int(jnp.argmax(d_min))
        anchors.append(nxt)
        d_min = jnp.minimum(d_min,
                            _pairwise_dist(X, X[nxt:nxt + 1]).reshape(-1))
    got, got_dmin = farthest_point_anchors(X, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(anchors))
    np.testing.assert_array_equal(np.asarray(got_dmin), np.asarray(d_min))


def test_multisketch_runtime_seed_matches_static():
    """The runtime-seed build override (one executable for many seeds)
    must reproduce the static-seed build bit for bit."""
    rng = np.random.default_rng(12)
    keys = np.arange(700, dtype=np.int32)
    w = rng.lognormal(0, 1, 700).astype(np.float32)
    objs = ((C.SUM, 12), (C.COUNT, 6))
    for seed in (3, 9):
        a = C.multisketch_build(
            C.MultiSketchSpec(objectives=objs, seed=seed), keys, w)
        b = C.multisketch_build(
            C.MultiSketchSpec(objectives=objs, seed=0), keys, w, seed=seed)
        for name, x, y in zip(a._fields, a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=name)


def test_metric_sample_is_sketch_backed():
    """universal_metric_sample == the slab scattered back to a dense mask,
    and many seeds share one compiled build (runtime-seed path)."""
    from repro.core.metric_domains import metric_sample_sketch
    X = _points(n=300, seed=8)
    s = C.universal_metric_sample(X, 24, seed=5)
    ms, spec = metric_sample_sketch(X, 24, seed=5)
    assert spec.seed == 5 and spec.scheme == "ppswor"
    sk = ms.sketch
    keys = np.asarray(sk.keys)
    member_slots = np.asarray(sk.member) & np.asarray(sk.valid)
    dense = np.zeros(300, bool)
    dense[keys[member_slots]] = True
    np.testing.assert_array_equal(np.asarray(s.member), dense)
    assert np.all((np.asarray(s.prob) > 0) == dense)
    # slab coords gather the member points
    np.testing.assert_array_equal(
        np.asarray(ms.coords)[member_slots], X[keys[member_slots]])

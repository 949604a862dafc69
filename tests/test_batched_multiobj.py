"""Single-launch batched multi-objective bottom-k pipeline.

Agreement of the fused kernel chain (``multisketch_select`` with
use_kernels: seeds -> batched block-select -> batched merge -> the
membership rule) with the core reference path on
shared u_x, across schemes, ragged n, and |F|; plus a launch-count
regression: the number of pallas_call launches must NOT grow with |F|.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as C
import repro.kernels as K
from repro.kernels import ref as R
from repro.kernels.ops import statfn_of


def _kernel_chain(keys, weights, active, objectives, k,
                                   scheme="ppswor", seed=0):
    """(member, prob) of the kernel chain for (kind, param) objectives."""
    spec = C.MultiSketchSpec(
        objectives=tuple((statfn_of(kind, prm), k)
                         for kind, prm in objectives),
        scheme=scheme, seed=seed)
    return C.multisketch_select(spec, keys, weights, active,
                                use_kernels=True)[:2]


# (kind, param) pools — every family, several params
_OBJ_POOL = ((0, 0.0), (1, 0.0), (2, 5.0), (3, 2.0), (4, 1.5),
             (3, 0.5), (2, 1.0), (4, 0.8))


def _objectives(nf):
    return _OBJ_POOL[:nf]


def _data(rng, n):
    keys = rng.integers(0, 2**31 - 1, n).astype(np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    act = rng.random(n) > 0.07
    return keys, w, act


# ------------------------------------------------- kernel chain vs core path
@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("n", [1024, 1500])  # aligned and ragged
@pytest.mark.parametrize("nf", [1, 3, 8])
def test_batched_kernel_matches_core(rng, scheme, n, nf):
    keys, w, act = _data(rng, n)
    k = 16
    objs = _objectives(nf)
    m_k, p_k = _kernel_chain(
        jnp.asarray(keys), jnp.asarray(w), jnp.asarray(act), objs, k,
        scheme=scheme, seed=3)
    core = C.multi_bottomk_sample(
        keys, w, act, [(statfn_of(kind, prm), k) for kind, prm in objs],
        scheme=scheme, seed=3)
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(core.member))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(core.prob))


def test_batched_kernel_matches_core_large_ragged(rng):
    keys, w, act = _data(rng, 3000)
    objs = _objectives(3)
    m_k, p_k = _kernel_chain(
        jnp.asarray(keys), jnp.asarray(w), jnp.asarray(act), objs, 33)
    core = C.multi_bottomk_sample(
        keys, w, act, [(statfn_of(kind, prm), 33) for kind, prm in objs])
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(core.member))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(core.prob))


def test_k_not_smaller_than_n(rng):
    """k >= n: every active key is a member with p = 1 (tau = +inf)."""
    keys, w, act = _data(rng, 600)
    m_k, p_k = _kernel_chain(
        jnp.asarray(keys), jnp.asarray(w), jnp.asarray(act),
        _objectives(2), 600)
    assert bool(jnp.all(m_k == jnp.asarray(act)))
    np.testing.assert_array_equal(np.asarray(p_k),
                                  np.where(act, 1.0, 0.0).astype(np.float32))


# ----------------------------------------------------- batched sub-primitives
@pytest.mark.parametrize("n,k", [(2048, 16), (3000, 33), (1000, 7)])
def test_batched_bottomk_select_matches_ref(rng, n, k):
    seeds = rng.exponential(1.0, (4, n)).astype(np.float32)
    seeds[rng.random((4, n)) > 0.9] = np.inf
    v, i, t = K.batched_bottomk_select(jnp.asarray(seeds), k)
    rv, ri, rt = R.batched_bottomk_select_ref(seeds, k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(rt))


@pytest.mark.parametrize("n,k,plan", [(4096, 200, "block"),
                                      (4096, 2047, "top_k"),
                                      (600, 600, "top_k"), (300, 40, "block")])
def test_select_plan_by_size(rng, n, k, plan):
    """The select plan is picked from the sizes alone (the block plan only
    while a block's lane-padded candidate row is narrower than the block),
    and both plans return the oracle's bits, ties included."""
    from repro.kernels.blockselect import select_plan
    assert select_plan(n, min(k + 1, n)) == plan
    seeds = rng.exponential(1.0, (3, n)).astype(np.float32)
    seeds[:, ::7] = seeds[:, 3:4]                    # many exact ties
    seeds[rng.random((3, n)) > 0.9] = np.inf
    v, i, t = K.batched_bottomk_select(jnp.asarray(seeds), k)
    rv, ri, rt = R.batched_bottomk_select_ref(seeds, k)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(rt))


@pytest.mark.parametrize("n", [1024, 1500])
def test_fused_seeds_fvals_matches_ref(rng, n):
    keys, w, act = _data(rng, n)
    objs = _objectives(5)
    s, fv = K.fused_seeds_fvals(jnp.asarray(keys), jnp.asarray(w),
                                jnp.asarray(act), objs, seed=5)
    rs, rfv = R.fused_seeds_fvals_ref(keys, w, act, objs, seed=5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(fv), np.asarray(rfv), rtol=1e-6)


# ------------------------------------------------------ launch-count flatness
def _count_pallas_calls(jaxpr):
    """Recursively count pallas_call eqns through nested (closed) jaxprs."""
    def subs(v):
        if hasattr(v, "jaxpr"):       # ClosedJaxpr
            return [v.jaxpr]
        if hasattr(v, "eqns"):        # Jaxpr
            return [v]
        if isinstance(v, (list, tuple)):
            return [s for x in v for s in subs(x)]
        return []

    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += 1
        for v in eqn.params.values():
            for sub in subs(v):
                count += _count_pallas_calls(sub)
    return count


@pytest.mark.parametrize("nf", [1, 3, 8])
def test_fused_path_launch_count_flat_in_F(nf):
    """ONE launch per kernel stage (seeds, block-select), regardless of |F|."""
    n, k = 2048, 16
    keys = jnp.arange(n, dtype=jnp.int32)
    w = jnp.ones((n,), jnp.float32)
    act = jnp.ones((n,), bool)
    objs = _objectives(nf)
    jx = jax.make_jaxpr(
        lambda ke, we, ac: _kernel_chain(ke, we, ac, objs, k))(keys, w, act)
    assert _count_pallas_calls(jx.jaxpr) == 2


def test_unknown_scheme_rejected():
    """A typo'd scheme must not silently mix priority seeds with ppswor
    probabilities."""
    keys = jnp.arange(64, dtype=jnp.int32)
    w = jnp.ones((64,), jnp.float32)
    act = jnp.ones((64,), bool)
    with pytest.raises(ValueError, match="scheme"):
        _kernel_chain(keys, w, act, ((0, 0.0),), 8, scheme="bogus")


# ------------------------------------------------------------- satellites
def test_default_interpret_matches_backend():
    assert K.default_interpret() == (jax.default_backend() == "cpu")
    assert K.resolve_interpret(None) == K.default_interpret()
    assert K.resolve_interpret(True) is True
    assert K.resolve_interpret(False) is False


def test_rank_counts_ragged_n(rng):
    n = 700  # not a multiple of either block size
    w = rng.lognormal(0, 1.0, n).astype(np.float32)
    act = rng.random(n) > 0.07
    u = C.uniform01(np.arange(n, dtype=np.int32), 0)
    from repro.core.hashing import rank_of
    r = rank_of(u, "ppswor")
    rw = jnp.where(act, r / jnp.maximum(jnp.asarray(w), 1e-30), jnp.inf)
    h_k, l_k = K.rank_counts(jnp.where(act, w, 0), u, rw, act)
    h_r, l_r = R.rank_counts_ref(jnp.where(act, w, 0), u, rw, act)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


def test_sample_leaf_single_scan_fixed_slots(rng):
    """distopt wire format invariants (a fixed 3k-slot MultiSketch slab)."""
    from repro.distopt.compression import _merge_leaf, _sample_leaf
    n, k = 4096, 64
    g = (rng.standard_normal(n) * (rng.random(n) < 0.3)).astype(np.float32)
    sk = _sample_leaf(jnp.asarray(g), k, 7, 0.01)
    assert (sk.keys.shape == sk.weights.shape == sk.probs.shape
            == sk.valid.shape == (3 * k,))
    assert sk.seeds.shape == (3, 3 * k) and sk.taus.shape == (3,)
    assert bool(jnp.all((sk.probs > 0) & (sk.probs <= 1.0)))
    assert bool(jnp.all(jnp.where(
        sk.valid, jnp.asarray(g)[jnp.maximum(sk.keys, 0)] == sk.weights,
        True)))
    # members occupy a prefix of the slots; empty slots carry key -1
    valid = sk.valid
    first_invalid = int(jnp.argmin(valid)) if not bool(valid.all()) else 3 * k
    assert bool(jnp.all(~valid[first_invalid:]))
    assert bool(jnp.all(jnp.where(valid, sk.keys >= 0, sk.keys == -1)))
    # HT estimate is exact when every nonzero is sampled (k >= nnz)
    g_small = np.zeros(512, np.float32)
    g_small[:40] = rng.standard_normal(40).astype(np.float32)
    sk = _sample_leaf(jnp.asarray(g_small), 64, 3, 0.01)
    est = _merge_leaf(sk.keys[None], sk.weights[None], sk.probs[None],
                      sk.valid[None], 512, 1)
    np.testing.assert_allclose(np.asarray(est), g_small, atol=1e-5)

"""Hypothesis property suite for MultiSketch merge algebra.

Properties (all BIT-identical, not just statistically equal — exact merge
is the paper's §3.3 composability claim):

  * commutativity / associativity of ``multisketch_merge``;
  * absorb-then-merge == merge-then-absorb (streaming and fan-in folds
    interleave freely);
  * incremental delta fold (``multisketch_absorb_into``) == full stacked
    re-merge (the PR 5 engine contract);
  * threshold closure: every finite tau^(f)'s threshold key is retained in
    the slab, and re-selection over the slab alone is idempotent.

Random key/weight/scheme/capacity draws ride hypothesis when installed
(CI installs it); the checkers are plain functions, and a deterministic
parametrized sweep below exercises the same properties at fixed draws so
the invariants stay tier-1-covered where hypothesis is absent.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as C
from repro.core.multi_sketch import (MultiSketch, multisketch_absorb_into,
                                     multisketch_merge_stacked)

_POOL = [(C.SUM, 5), (C.COUNT, 3), (C.thresh(2.0), 4), (C.cap(1.5), 3),
         (C.moment(1.5), 3)]


def _make_spec(scheme, nf, capacity_slack, seed):
    base = C.MultiSketchSpec(objectives=tuple(_POOL[:nf]), scheme=scheme,
                             seed=seed)
    if capacity_slack:
        base = C.MultiSketchSpec(objectives=tuple(_POOL[:nf]), scheme=scheme,
                                 seed=seed,
                                 capacity=base.default_capacity()
                                 + capacity_slack)
    return base


def _assert_bitsame(a: MultiSketch, b: MultiSketch, msg=""):
    for name, x, y in zip(MultiSketch._fields, a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{msg}{name}")


def _parts(keys, ws, cuts):
    """Split (keys, ws) at relative cut points into >= 1 chunks."""
    n = len(keys)
    idx = sorted({max(1, min(n - 1, int(c * n))) for c in cuts}) if n > 1 \
        else []
    return [(keys[a:b], ws[a:b])
            for a, b in zip([0] + idx, idx + [n]) if b > a]


# ------------------------------------------------------------- checkers
def check_merge_commutative(spec, keys, ws):
    parts = _parts(keys, ws, [0.5])
    if len(parts) < 2:
        return
    a = C.multisketch_build(spec, *parts[0])
    b = C.multisketch_build(spec, *parts[1])
    _assert_bitsame(C.multisketch_merge(spec, a, b),
                    C.multisketch_merge(spec, b, a), "commutative: ")


def check_merge_associative(spec, keys, ws):
    parts = _parts(keys, ws, [0.33, 0.66])
    sks = [C.multisketch_build(spec, k, w) for k, w in parts]
    if len(sks) < 3:
        return
    a, b, c = sks[:3]
    left = C.multisketch_merge(spec, C.multisketch_merge(spec, a, b), c)
    right = C.multisketch_merge(spec, a, C.multisketch_merge(spec, b, c))
    _assert_bitsame(left, right, "associative: ")
    # and both equal the one-shot union build (keys are distinct)
    _assert_bitsame(left, C.multisketch_build(spec, keys, ws), "vs whole: ")


def check_absorb_merge_interchange(spec, keys, ws):
    """absorb-then-merge == merge-then-absorb == one-shot."""
    parts = _parts(keys, ws, [0.4, 0.7])
    if len(parts) < 3:
        return
    (k1, w1), (k2, w2), (k3, w3) = parts[:3]
    a = C.multisketch_build(spec, k1, w1)
    b = C.multisketch_build(spec, k2, w2)
    absorb_then_merge = C.multisketch_merge(
        spec, C.multisketch_absorb(jax.tree.map(jnp.copy, a), k3, w3,
                                   spec=spec, use_kernels=False), b)
    merge_then_absorb = C.multisketch_absorb(
        C.multisketch_merge(spec, a, b), k3, w3, spec=spec,
        use_kernels=False)
    _assert_bitsame(absorb_then_merge, merge_then_absorb, "interchange: ")


def check_incremental_equals_full(spec, keys, ws):
    """Delta fold into a cached merge == full stacked re-merge."""
    parts = _parts(keys, ws, [0.3, 0.6, 0.8])
    if len(parts) < 3:
        return
    sks = [C.multisketch_build(spec, k, w) for k, w in parts]
    cached = sks[0]
    for s in sks[1:-1]:
        cached = C.multisketch_merge(spec, cached, s)
    inc = multisketch_absorb_into(jax.tree.map(jnp.copy, cached), sks[-1],
                                  spec=spec, use_kernels=False)
    stacked = MultiSketch(*jax.tree.map(lambda *xs: jnp.stack(xs), *sks))
    full = multisketch_merge_stacked(spec, stacked)
    _assert_bitsame(inc, full, "incremental vs full: ")


def check_threshold_closure(spec, keys, ws):
    """Every objective's finite tau has its threshold key retained, and
    re-selection over the slab alone reproduces the slab (idempotence)."""
    sk = C.multisketch_build(spec, keys, ws)
    seeds = np.asarray(sk.seeds)
    valid = np.asarray(sk.valid)
    for fi, tau in enumerate(np.asarray(sk.taus)):
        if np.isfinite(tau):
            assert np.any(valid & (seeds[fi] == tau)), \
                f"threshold key of objective {fi} not retained"
    _assert_bitsame(
        C.multisketch_merge(spec, sk, C.multisketch_empty(spec)), sk,
        "idempotence: ")


_CHECKS = [check_merge_commutative, check_merge_associative,
           check_absorb_merge_interchange, check_incremental_equals_full,
           check_threshold_closure]


def _draw_to_inputs(key_seed, ws):
    rng = np.random.default_rng(key_seed)
    keys = rng.choice(200_000, size=len(ws), replace=False).astype(np.int32)
    return keys, np.asarray(ws, np.float32)


# ------------------------------------------------- deterministic sweep
@pytest.mark.parametrize("check", _CHECKS,
                         ids=lambda c: c.__name__.replace("check_", ""))
@pytest.mark.parametrize("scheme,nf,slack,seed,ws", [
    pytest.param("ppswor", 3, 0, 0, None, id="ppswor-3-0-0"),
    pytest.param("priority", 3, 0, 7, None, id="priority-3-0-7"),
    pytest.param("ppswor", 5, 9, 3, None, id="ppswor-5-9-3"),
    pytest.param("priority", 1, 4, 1, None, id="priority-1-4-1"),
    # equal weights: slot order is decided by key alone
    pytest.param("ppswor", 1, 0, 0, [1.0] * 6, id="ppswor-1-0-0-equal-w")])
def test_merge_properties_fixed_draws(check, scheme, nf, slack, seed, ws):
    spec = _make_spec(scheme, nf, slack, seed)
    if ws is None:
        rng = np.random.default_rng(100 + seed)
        n = int(rng.integers(12, 90))
        ws = rng.lognormal(0, 1.4, n).astype(np.float32)
    keys, ws = _draw_to_inputs(seed, ws)
    check(spec, keys, ws)


# ------------------------------------------------- hypothesis wrappers
# soft gate (importorskip would skip the deterministic sweep above too):
# when hypothesis is absent the random-draw wrappers are skipped but the
# fixed-draw sweep still runs under tier-1.
try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("ci", deadline=None, max_examples=20)
    settings.load_profile("ci")
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    weights_strategy = st.lists(
        st.floats(min_value=0.0009765625, max_value=16384.0,
                  allow_nan=False, allow_infinity=False, width=32),
        min_size=6, max_size=80)
    draw_strategy = st.tuples(st.integers(0, 10_000), weights_strategy,
                              st.sampled_from(["ppswor", "priority"]),
                              st.integers(1, 5), st.integers(0, 12),
                              st.integers(0, 1000))

    def _run(check, draw):
        key_seed, ws, scheme, nf, slack, hash_seed = draw
        spec = _make_spec(scheme, nf, slack, hash_seed)
        keys, ws = _draw_to_inputs(key_seed, ws)
        check(spec, keys, ws)

    @given(draw_strategy)
    def test_merge_commutative(draw):
        _run(check_merge_commutative, draw)

    @given(draw_strategy)
    def test_merge_associative(draw):
        _run(check_merge_associative, draw)

    @given(draw_strategy)
    def test_absorb_merge_interchange(draw):
        _run(check_absorb_merge_interchange, draw)

    @given(draw_strategy)
    def test_incremental_equals_full(draw):
        _run(check_incremental_equals_full, draw)

    @given(draw_strategy)
    def test_threshold_closure(draw):
        _run(check_threshold_closure, draw)
else:  # pragma: no cover - environment-dependent
    def test_hypothesis_missing_marker():
        pytest.skip("hypothesis not installed; random-draw suite skipped "
                    "(fixed-draw sweep above still ran)")

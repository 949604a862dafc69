"""Statistical-guarantee tier: the paper's CV bounds as seeded pytest.

Thm 3.1 / §5.1 promise: one multi-objective summary answers every f ∈ F
with the SAME per-objective CV guarantee as a dedicated bottom-k sample —
cv(Q^(f, H)) <= sqrt(1 / (q (k_f - 1))) with q = Q(f, H) / Q(f, X). The
benches eyeball this; serving needs it ENFORCED, so this module measures
many-trial estimator variance at fixed seeds (deterministic — the trials
are hash-seed replications through one vmapped executable, the
runtime-seed build path) and asserts the bound per objective, per scheme,
per |F| ∈ {1, 3, 8}.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as C
from repro.core.multi_sketch import _rebuild

N, K, TRIALS = 1200, 32, 200
# the empirical CV of T trials estimates the true CV with relative
# standard error ~ 1/sqrt(2T); the bound applies to the TRUE CV, so the
# assertion allows that measurement noise (3 sigma) on top — COUNT/CAP sit
# exactly at the bound (the theorem's tight case) and would otherwise
# flicker on the noise
CV_NOISE = 1.0 + 3.0 / np.sqrt(2.0 * TRIALS)


def _pool():
    return [(C.SUM, K), (C.COUNT, K), (C.thresh(3.0), K), (C.cap(2.0), K),
            (C.moment(1.5), K), (C.thresh(0.8), K), (C.cap(5.0), K),
            (C.moment(0.7), K)]


def _data():
    rng = np.random.default_rng(42)
    keys = np.arange(N, dtype=np.int32)
    w = rng.lognormal(0, 1.5, N).astype(np.float32)
    return keys, w, np.ones(N, bool)


def _trial_estimates(spec, keys, w, act):
    """[trials, |F|] segment estimates: one vmapped seeded build (shared
    executable across trials — the runtime hash-seed override path) and
    one HT pass per objective over the stacked slabs."""
    jk, jw, ja = jnp.asarray(keys), jnp.asarray(w), jnp.asarray(act)
    build = jax.jit(jax.vmap(
        lambda s: _rebuild(spec, jk, jw, ja, False, seed=s)))
    sks = build(jnp.arange(TRIALS, dtype=jnp.int32))
    segm = sks.keys % 3 == 0                      # the queried segment H
    out = []
    for f, _ in spec.objectives:
        ht = jnp.where(sks.member & segm,
                       f(sks.weights) / jnp.maximum(sks.probs, 1e-30), 0.0)
        out.append(np.asarray(jnp.sum(ht, axis=1)))
    return np.stack(out, axis=1)


def _check_cv(spec, keys, w, act):
    seg = keys % 3 == 0
    ests = _trial_estimates(spec, keys, w, act)
    for i, (f, kf) in enumerate(spec.objectives):
        ex = float(C.exact(f, w, act, seg))
        q = ex / float(C.exact(f, w, act))
        cv = float(np.std(ests[:, i]) / ex)
        bound = C.cv_bound(q, kf) * CV_NOISE
        assert cv <= bound, (f"{spec.scheme} |F|={spec.nf} {f.name}: "
                             f"cv={cv:.3f} > bound={bound:.3f}")
        # unbiasedness (Eq. 5): the trial mean sits within the estimator's
        # own standard error of the exact value
        bias = abs(float(np.mean(ests[:, i])) - ex) / ex
        assert bias <= 3.0 * max(cv, 1e-3) / np.sqrt(TRIALS) + 1e-2, f.name


@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("nf", [3, 8])
def test_cv_within_bound_multiobjective(scheme, nf):
    """cv <= bound for every objective of a shared |F|-objective summary."""
    keys, w, act = _data()
    spec = C.MultiSketchSpec(objectives=tuple(_pool()[:nf]), scheme=scheme,
                             seed=0)
    _check_cv(spec, keys, w, act)


@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
@pytest.mark.parametrize("kind", ["sum", "count", "thresh", "cap", "moment"])
def test_cv_within_bound_single_objective(scheme, kind):
    """|F| = 1: each StatFn family meets its dedicated-sample bound."""
    f = {"sum": C.SUM, "count": C.COUNT, "thresh": C.thresh(3.0),
         "cap": C.cap(2.0), "moment": C.moment(1.5)}[kind]
    keys, w, act = _data()
    spec = C.MultiSketchSpec(objectives=((f, K),), scheme=scheme, seed=0)
    _check_cv(spec, keys, w, act)


def test_multiobjective_cv_no_worse_than_dedicated():
    """Thm 3.1's other half: the shared summary's per-objective variance
    is NO WORSE than a dedicated sample's (p^(F) >= p^(f) slot-wise), so
    growing F must not degrade an objective already in it."""
    keys, w, act = _data()
    seg = keys % 3 == 0
    cvs = {}
    for nf in (1, 8):
        spec = C.MultiSketchSpec(objectives=tuple(_pool()[:nf]), scheme="ppswor",
                                 seed=0)
        ests = _trial_estimates(spec, keys, w, act)
        ex = float(C.exact(C.SUM, w, act, seg))
        cvs[nf] = float(np.std(ests[:, 0]) / ex)
    # same seeds, strictly more forgiving probabilities at |F|=8: allow
    # only trial noise (the estimators are not identical draws)
    assert cvs[8] <= cvs[1] * 1.25, cvs

"""Per-kernel shape/dtype sweeps: pallas (interpret=True) vs ref.py oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

import repro.kernels as K
from repro.kernels import ref as R
from repro.core.hashing import uniform01, rank_of


OBJS = ((0, 0.0), (3, 2.0), (1, 0.0), (2, 5.0), (4, 1.5))


@pytest.mark.parametrize("n", [1024, 2048, 8192])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("scheme", ["ppswor", "priority"])
def test_fused_seeds_matches_oracle(rng, n, dtype, scheme):
    keys = rng.integers(0, 2**31 - 1, n).astype(np.int32)
    w = rng.lognormal(0, 1.5, n).astype(dtype)
    act = rng.random(n) > 0.1
    out = K.fused_seeds(jnp.asarray(keys), jnp.asarray(w), jnp.asarray(act),
                        OBJS, scheme=scheme, seed=5)
    ref = R.fused_seeds_ref(keys, w.astype(np.float32), act, OBJS,
                            scheme=scheme, seed=5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [512, 1024, 4096])
@pytest.mark.parametrize("sigma", [0.5, 2.5])
def test_rank_counts_matches_oracle(rng, n, sigma):
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, sigma, n).astype(np.float32)
    act = rng.random(n) > 0.07
    u = uniform01(keys, 0)
    r = rank_of(u, "ppswor")
    rw = jnp.where(act, r / jnp.maximum(jnp.asarray(w), 1e-30), jnp.inf)
    h_k, l_k = K.rank_counts(jnp.where(act, w, 0), u, rw, act)
    h_r, l_r = R.rank_counts_ref(jnp.where(act, w, 0), u, rw, act)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    np.testing.assert_array_equal(np.asarray(l_k), np.asarray(l_r))


@pytest.mark.parametrize("n,k", [(2048, 1), (2048, 16), (4096, 64),
                                 (8192, 33)])
def test_block_bottomk_and_global_select(rng, n, k):
    seeds = rng.exponential(1.0, n).astype(np.float32)
    seeds[rng.random(n) > 0.9] = np.inf  # inactive seeds
    b = min(2048, n)
    v_k, i_k = K.block_bottomk(jnp.asarray(seeds), k)
    v_r, i_r = R.block_bottomk_ref(seeds, k, b)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i_k), np.asarray(i_r))
    gv, gi, gtau = K.bottomk_select(jnp.asarray(seeds), k)
    rv, ri, rtau = R.bottomk_select_ref(seeds, k)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri))
    assert float(gtau) == float(rtau)


def test_kernel_composition_matches_core_multi_objective(rng):
    import repro.core as C
    n, k = 4096, 16
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    act = rng.random(n) > 0.05
    objs = ((0, 0.0), (3, 2.0), (1, 0.0))
    spec = C.MultiSketchSpec(
        objectives=tuple((K.ops.statfn_of(kind, prm), k)
                         for kind, prm in objs))
    m_k, p_k = C.multisketch_select(
        spec, jnp.asarray(keys), jnp.asarray(w), jnp.asarray(act),
        use_kernels=True)[:2]
    core = C.multi_bottomk_sample(keys, w, act,
                                  [(C.SUM, k), (C.cap(2.0), k), (C.COUNT, k)],
                                  seed=0)
    assert bool(jnp.all(m_k == core.member))
    assert bool(jnp.allclose(p_k, core.prob, atol=1e-6))


def test_capping_kernel_matches_core_ref(rng):
    import repro.core as C
    n, k = 2048, 16
    keys = np.arange(n, dtype=np.int32)
    w = rng.lognormal(0, 1.5, n).astype(np.float32)
    act = rng.random(n) > 0.05
    u = uniform01(keys, 0)
    m2, hl = K.ops.universal_capping_kernel(jnp.asarray(keys), jnp.asarray(w),
                                            jnp.asarray(act), k)
    cr = C.universal_capping_ref(w, np.asarray(u), act, k)
    assert bool(jnp.all(m2 == cr.member))
    # hl diagnostic is only defined for ACTIVE keys (the kernel zeroes
    # inactive rows; the ref counts their raw-weight pairs)
    assert bool(jnp.all(jnp.where(jnp.asarray(act), hl == cr.hl, True)))

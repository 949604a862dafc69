"""Compile guards for TPU v5e: the serving path's kernels at real widths.

Interpret mode accepts layouts, loop forms and VMEM footprints that the
TPU's compiler refuses. Each test here compiles one kernel entry point
for a described (not attached) v5e chip at the widths ``chip_smoke.py``
runs — a MultiSketch fold over a 2^20-event chunk with |F| = 5
objectives at k = 1024, a 256-predicate query batch, and a service-cost
batch of 128 sets x 64 centres in d = 64 — plus the universal-capping
rank count over 2^20 keys, and checks that the kernel is in the program
as a Mosaic custom call.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler library, and every test worker imports
this file. Where it cannot be described the tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core as C
from repro.core.costs import CostTable
from repro.launch.compile_cache import compile_cache_disabled

NF = 5
K = 1024
SPEC = C.MultiSketchSpec(
    objectives=((C.SUM, K), (C.COUNT, K), (C.thresh(2.0), K),
                (C.cap(4.0), K), (C.moment(2.0), K)), seed=0)
CAP = SPEC.cap                       # sum k_f + |F| + 1 = 5126
N = (1 << 20) + CAP                  # one shard fold: slab + chunk
OBJ = SPEC.kernel_objectives()
B = 256
Q, CMAX, DIM = 128, 64, 64
CLUSTER_CAP = C.MultiSketchSpec(objectives=((C.SUM, K),)).cap


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    with compile_cache_disabled():
        yield SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_fused_seeds_fvals_compiles(one_chip):
    from repro.kernels.seeds import fused_seeds_fvals
    _compile(one_chip,
             lambda k, w, a: fused_seeds_fvals(k, w, a, OBJ, "ppswor", 0,
                                               interpret=False),
             ((N,), jnp.int32), ((N,), jnp.float32), ((N,), jnp.bool_))


def test_batched_bottomk_select_compiles(one_chip):
    from repro.kernels.blockselect import batched_bottomk_select, select_plan
    assert select_plan(N, SPEC.kmax + 2) == "block"
    _compile(one_chip,
             lambda s: batched_bottomk_select(s, SPEC.kmax + 1,
                                              interpret=False),
             ((NF, N), jnp.float32))


def test_compact_take_compiles(one_chip):
    from repro.kernels.compact import compact_take
    _compile(one_chip,
             lambda k, w, m, kp: compact_take(k, w, m, kp, CAP,
                                              interpret=False),
             ((N,), jnp.int32), ((N,), jnp.float32), ((N,), jnp.bool_),
             ((N,), jnp.bool_))


def test_segment_query_slab_compiles(one_chip):
    from repro.kernels.segquery import segment_query_slab
    _compile(one_chip,
             lambda k, w, p, m, t: segment_query_slab(k, w, p, m, t, OBJ,
                                                      interpret=False),
             ((CAP,), jnp.int32), ((CAP,), jnp.float32),
             ((CAP,), jnp.float32), ((CAP,), jnp.bool_),
             ((B, 6), jnp.int32))


def test_rank_counts_compiles(one_chip):
    from repro.kernels.rankcount import rank_counts
    _compile(one_chip,
             lambda w, h, l, a: rank_counts(w, h, l, a, interpret=False),
             ((1 << 20,), jnp.float32), ((1 << 20,), jnp.float32),
             ((1 << 20,), jnp.float32), ((1 << 20,), jnp.bool_))


def test_service_cost_compiles(one_chip):
    from repro.kernels.servicecost import _service_cost_jit
    _compile(one_chip,
             lambda p, pr, m, c, cv, mu, r, mode: _service_cost_jit(
                 p, pr, m, CostTable(c, cv, mu, r, mode), None, False),
             ((CLUSTER_CAP, DIM), jnp.float32), ((CLUSTER_CAP,), jnp.float32),
             ((CLUSTER_CAP,), jnp.bool_), ((Q, CMAX, DIM), jnp.float32),
             ((Q, CMAX), jnp.bool_), ((Q,), jnp.float32),
             ((Q,), jnp.float32), ((Q,), jnp.int32))


def test_bucketed_service_cost_compiles(one_chip, monkeypatch):
    """The one program that pads a device-built swap round's table to its
    bucket and scores it: the widest round the search benchmark runs
    (k = 50 centres, 64 candidates, d = 68)."""
    from repro.core.costs import estimate_bucketed
    from repro.kernels import servicecost
    # this process's backend is the CPU; compile the chip's kernel
    monkeypatch.setattr(servicecost, "resolve_interpret", lambda i: False)
    k, d = 50, 68
    q = 1 + 64 * k
    _compile(one_chip,
             lambda p, pr, m, c, cv, mu, r, mode: estimate_bucketed(
                 p, pr, m, CostTable(c, cv, mu, r, mode), q + 15, None),
             ((CLUSTER_CAP, d), jnp.float32), ((CLUSTER_CAP,), jnp.float32),
             ((CLUSTER_CAP,), jnp.bool_), ((q, k, d), jnp.float32),
             ((q, k), jnp.bool_), ((q,), jnp.float32), ((q,), jnp.float32),
             ((q,), jnp.int32))

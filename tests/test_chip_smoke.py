"""The bring-up smoke's own checks, at tiny sizes on the CPU.

``chip_smoke.check_serving`` is what keeps the pool's fault tolerance from
hiding a device that never folded: these tests drive a small durable pool
clean and under injected faults and assert that the check sees each one.
"""
import numpy as np
import pytest

import chip_smoke as S
import repro.core as C
from repro.launch.pool import FRESH, EnginePool
from tests.faults import FaultInjector


def _spec():
    return C.MultiSketchSpec(objectives=((C.SUM, 8), (C.COUNT, 8)), seed=0)


def _drive(tmp_path, chunks=3, snapshot_every=2):
    """One durable two-shard stream: ``chunks`` absorbs, one submit/pump
    batch and one single query. Returns (pool, receipts, responses)."""
    pool = EnginePool(durability_dir=str(tmp_path),
                      snapshot_every=snapshot_every, retries=0,
                      breaker_threshold=1, breaker_reset=60.0,
                      sleep=lambda s: None)
    pool.create_stream("t", _spec(), shards=2)
    receipts = []
    for i in range(chunks):
        keys, w = S.stream_chunk(0, i, 256, 12)
        receipts.append(pool.absorb("t", keys, w, shard=i % 2))
    preds = [C.key_range(0, 1 << 11), C.hash_fraction(0.5, salt=7)]
    fut = pool.submit("t", predicates=preds)
    pool.pump()
    responses = [("batch", fut.result()), ("single", pool.query("t"))]
    return pool, receipts, responses


def test_check_serving_passes_a_clean_stream(tmp_path):
    pool, receipts, responses = _drive(tmp_path)
    assert all(r.status == FRESH for _, r in responses)
    assert S.check_serving(pool, ("t",), receipts, responses) == []
    pool.close()


def test_check_serving_reports_a_failed_fold(tmp_path):
    """A fold that fails is retried, then the chunk waits in the backlog
    and queries fall back to the last-good slab: every call returns, so
    only the check can tell."""
    with FaultInjector() as inj:
        inj.fail_calls("absorb_fold", [1])
        pool, receipts, responses = _drive(tmp_path)
    assert inj.fired["absorb_fold"] == 1
    bad = S.check_serving(pool, ("t",), receipts, responses)
    assert any("not applied" in b for b in bad)
    assert any("breaker open" in b for b in bad)
    assert any("pending" in b for b in bad)
    assert any(b.startswith("response ") for b in bad)
    pool.close()


def test_check_serving_reports_a_failed_snapshot(tmp_path):
    with FaultInjector() as inj:
        inj.fail_always("ckpt_save")
        pool, receipts, responses = _drive(tmp_path, chunks=4)
    assert all(r.applied for r in receipts)
    bad = S.check_serving(pool, ("t",), receipts, responses)
    assert len(bad) == 1 and bad[0].endswith("snapshot failures")
    pool.close()


def test_smoke_refuses_to_run_without_an_accelerator(capsys):
    """On the CPU the smoke runs nothing, prints no result line and exits
    non-zero."""
    assert S.main([]) != 0
    assert capsys.readouterr().out.strip().splitlines()[-1].startswith(
        "device:")


@pytest.mark.parametrize("phase", ["a", "b"])
def test_smoke_phases_agree_with_references_at_tiny_sizes(tmp_path, phase):
    """The phases' references agree on the CPU at tiny sizes; the only
    failures are the kernel-program checks, which need a TPU."""
    args = S.parse_args(["--k", "16", "--chunks", "3", "--chunk-log2", "10",
                         "--ids-log2", "14", "--queries", "16",
                         "--snapshot-every", "2", "--points-log2", "10",
                         "--dim", "8", "--components", "4",
                         "--cluster-k", "64", "--queries-b", "8",
                         "--centers", "4"])
    failures = []
    if phase == "a":
        S.phase_a(args, str(tmp_path), failures)
    else:
        S.phase_b(args, failures)
    others = [f for f in failures if not f.startswith("program ")]
    assert others == []
    assert np.all([f.startswith("program ") for f in failures])

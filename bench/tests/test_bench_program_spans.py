"""The per-layer metrics read from the program's own spans and counters:
a traced rehearsal prints them all, an untraced one none, and a program
that records nothing (or has no tracer at all) gives no value and no
error."""
import math
import sys

import pytest

import bench_tiny
import harness

PROGRAM = ("build_us_per_set", "encode_us_per_set", "prep_us_per_set",
           "dispatch_us_per_set", "wait_us_per_set", "table_bytes_per_set",
           "jit_compiles")
CELL = "metric_cluster.search"


@pytest.fixture(autouse=True)
def _fresh():
    from repro.telemetry import trace
    trace.reset()                  # the records of one run per process
    yield
    trace.reset()


def test_traced_rehearsal_prints_every_program_metric():
    r = bench_tiny.run(CELL, trace=True)
    assert r["correct"], r["checks"]
    got = r["metrics"]
    assert set(PROGRAM) <= set(got)
    for name in PROGRAM:
        v = got[name]["value"]
        assert math.isfinite(v), name
        if name == "jit_compiles":
            assert v == 0
        else:
            assert v > 0, name
    units = {n: got[n]["unit"] for n in PROGRAM}
    assert units["table_bytes_per_set"] == "B"
    assert units["build_us_per_set"] == "us"
    # a padded table holds at least its own sets' centres, four bytes each
    c = bench_tiny.CLUSTER
    least = min(c["traffic"]["centers"]) * c["config"]["cluster"]["dim"] * 4
    assert got["table_bytes_per_set"]["value"] > least


def test_untraced_rehearsal_prints_no_program_metric():
    r = bench_tiny.run(CELL, trace=False)
    assert not set(PROGRAM) & set(r["metrics"])


def _read():
    cell = harness.find_cell(CELL, overrides=bench_tiny.CLUSTER)
    ctx = harness.Context(cell, harness.Recorder(), None, None)
    return harness.read_per_layer(ctx)


def test_nothing_recorded_reads_as_no_metric():
    assert not set(PROGRAM) & set(_read())


def test_a_program_without_the_tracer_reads_as_no_metric(monkeypatch):
    import repro.telemetry
    monkeypatch.delattr(repro.telemetry, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.telemetry.trace", None)
    assert not set(PROGRAM) & set(_read())

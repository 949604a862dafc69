"""Each cell's driver path end to end at small sizes on the CPU: set-up,
the measured window, collection and the comparison with the plain
reference. A rehearsal prints no device metric."""
import json
import os
import subprocess
import sys

import pytest

import bench_tiny

DEVICE_METRICS = ("idle_share", "roofline")


@pytest.mark.parametrize("cell", bench_tiny.CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end_and_is_correct(cell, trace):
    r = bench_tiny.run(cell, trace=trace)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["rehearsal"] is True
    assert list(r)[-1] == "checks"
    for c in r["checks"].values():
        assert c["value"] <= c["limit"]
    names = set(r["metrics"])
    assert not any(d in n for n in names for d in DEVICE_METRICS)
    assert "busy_s" not in r["device"] and "breakdown" not in r
    if not trace:
        assert "setup_s" in names and len(names) >= 2


def test_run_cell_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(bench_tiny.BENCH, "run_cell.py"),
         "--workload", "metric_cluster.search", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=bench_tiny.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric():
    bench = json.load(open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(bench_tiny.BENCH, "metrics",
                                           m["name"] + ".py"))
        for w in m["workloads"]:
            assert w in e2e[m["moves"]]["workloads"]
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert os.path.isfile(os.path.join(bench_tiny.BENCH, "traffic",
                                           w["name"] + ".json"))
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}

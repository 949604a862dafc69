"""A cell, a configuration and a per-layer metric are added as new files
and new BENCHMARK.json entries alone: no file that is there changes."""
import hashlib
import json
import os
import shutil

import bench_tiny


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if f.endswith((".py", ".json")):
                p = os.path.join(d, f)
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    open(p, "rb").read()).hexdigest()
    return out


def test_throwaway_cell_config_and_metric_from_new_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(bench_tiny.BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")))
    before = _digest(root)

    # new files only: a configuration, a traffic mix of an existing kind,
    # and a per-layer metric's reader
    conf = json.load(open(os.path.join(root, "bench", "configs",
                                       "metric_cluster.json")))
    conf["name"] = "cluster_tiny"
    conf["cluster"]["mu"] = 1.0
    json.dump(conf, open(os.path.join(root, "bench", "configs",
                                      "cluster_tiny.json"), "w"))
    traffic = json.load(open(os.path.join(root, "bench", "traffic",
                                          "metric_cluster.search.json")))
    traffic["centers"] = [4]
    json.dump(traffic, open(os.path.join(root, "bench", "traffic",
                                         "cluster_tiny.median.json"), "w"))
    with open(os.path.join(root, "bench", "metrics",
                           "throwaway_calls.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return len(ctx.recorder.records.get('score_shapes', []))"
                "\n")
    bench["configs"].append({"name": "cluster_tiny", "source": "test",
                             "file": "bench/configs/cluster_tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "cluster_tiny.median",
                               "config": "cluster_tiny", "traffic": "median",
                               "chips": 1, "why": "test"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["sets_scored_per_s"]["workloads"].append("cluster_tiny.median")
    bench["per_layer"].append({"name": "throwaway_calls", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "cluster engine",
                               "moves": "sets_scored_per_s",
                               "workloads": ["cluster_tiny.median"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    extra = {"config": bench_tiny.CLUSTER["config"],
             "traffic": {**bench_tiny.CLUSTER["traffic"], "centers": [4]}}
    r = bench_tiny.run("cluster_tiny.median", trace=True, root=root,
                       extra=extra)
    assert r["correct"], r["checks"]
    assert r["metrics"]["throwaway_calls"]["value"] > 0
    r = bench_tiny.run("cluster_tiny.median", trace=False, root=root,
                       extra=extra)
    assert {"sets_scored_per_s", "setup_s"} == set(r["metrics"])
    after = _digest(root)
    assert all(after[k] == v for k, v in before.items())

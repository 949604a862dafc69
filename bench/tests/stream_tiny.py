"""The key-value stream cells at small sizes, staged for tests on the CPU.

The cells ``kv_dashboard.ingest`` and ``kv_dashboard.read`` are not in
``BENCHMARK.json``: the program keeps a member with p = 0 where two seeds
tie at an objective's k-th place, and their deployment and traffic are
not yet taken from a public source (PERF.md). Their kinds, reference,
generator and readers are in ``bench/``; the configuration, traffic and
entries that drive them live here, at sizes a test can hold, and
``stage`` writes a root whose ``BENCHMARK.json`` holds those entries
beside the accepted ones. Every path of a chip run is taken at these
sizes: set-up, window, trace reading, collection, reopening, reference.
"""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELLS = ("kv_dashboard.ingest", "kv_dashboard.read")

# The limits separate the program from the control at these sizes (see
# test_stream_limits_lie_between_program_and_control_readings).
CONFIG = {
    "name": "kv_dashboard",
    "stream": {"shards": 4, "scheme": "ppswor",
               "objectives": [["sum", 0, 32], ["count", 0, 32],
                              ["thresh", 2, 32], ["cap", 4, 32],
                              ["moment", 2, 32]],
               "hash_seed": 150907445, "snapshot_every": 2,
               "keep_snapshots": 3, "b_quantum": 16},
    "events": {"ids_log2": 16, "zipf_s": 0.99, "pareto_alpha": 1.5,
               "chunk_log2": 12},
    "reduced": [],
    "limits": {"member_diff": 0, "prob_gap": 1e-5, "answer_gap": 1e-5,
               "recovered_diff": 0, "guarantee_breaks": 0},
}
TRAFFIC = {
    "kv_dashboard.ingest": {"kind": "stream_ingest", "chunks": 10,
                            "setup_chunks": 2, "check_predicates": 32},
    "kv_dashboard.read": {"kind": "stream_read", "fill_chunks": 4,
                          "rate_per_s": 100, "panel_share": 0.75,
                          "panel_rows": 16, "panel_rows_max": 48,
                          "lookup_rows_max": 16, "check_requests": 40},
}


def _m(name, unit, better, source, layer, moves, cell):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [cell]}


_IN, _RD = CELLS
ENTRIES = {
    "configs": [{"name": "kv_dashboard",
                 "source": "arXiv:1509.07445 section 2 (statistics)",
                 "file": "bench/configs/kv_dashboard.json", "reduced": [],
                 "why": "one tenant's durable EnginePool stream"}],
    "workloads": [
        {"name": _IN, "config": "kv_dashboard", "traffic": "ingest",
         "chips": 1, "why": "closed loop of durable chunks, no reads"},
        {"name": _RD, "config": "kv_dashboard", "traffic": "read",
         "chips": 1, "why": "open-loop Poisson reads, no fold"}],
    "end_to_end": [
        {"name": "ingest_events_per_s", "unit": "events/s",
         "better": "higher", "source": "host_clock", "workloads": [_IN]},
        {"name": "query_p50_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "workloads": [_RD]},
        {"name": "query_p99_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "workloads": [_RD]}],
    "per_layer": [
        _m("wal_append_ms", "ms", "lower", "host_clock", "durability",
           "ingest_events_per_s", _IN),
        _m("snapshot_ms", "ms", "lower", "host_clock", "durability",
           "ingest_events_per_s", _IN),
        _m("fold_device_ms", "ms", "lower", "device_trace", "engine fold",
           "ingest_events_per_s", _IN),
        _m("blockselect_roofline", "%", "higher", "device_trace",
           "kernels", "ingest_events_per_s", _IN),
        _m("idle_share.ingest", "%", "lower", "device_trace", "device",
           "ingest_events_per_s", _IN),
        _m("pump_ms", "ms", "lower", "host_clock", "pool admission",
           "query_p99_ms", _RD),
        _m("coalesced_rows", "rows", "higher", "host_clock",
           "pool admission", "query_p99_ms", _RD),
        _m("segquery_roofline", "%", "higher", "device_trace", "kernels",
           "query_p99_ms", _RD),
        _m("idle_share.read", "%", "lower", "device_trace", "device",
           "query_p99_ms", _RD)],
}


def merged(bench: dict) -> dict:
    """``bench`` with the stream cells' entries appended."""
    out = dict(bench)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        out[key] = list(bench[key]) + ENTRIES[key]
    return out


def _write(path: str, obj) -> None:
    if os.path.lexists(path):      # a link to a file of bench/: not through it
        os.remove(path)
    with open(path, "w") as f:
        json.dump(obj, f)


def stage(tmp: str) -> str:
    """A root in ``tmp``: the benchmark's files, the stream cells' tiny
    configuration and traffic beside them, and the merged entries."""
    bench = os.path.join(tmp, "bench")
    os.mkdir(bench)
    for name in os.listdir(BENCH):
        src = os.path.join(BENCH, name)
        if name in ("configs", "traffic"):
            os.mkdir(os.path.join(bench, name))
            for f in os.listdir(src):
                os.symlink(os.path.join(src, f), os.path.join(bench, name, f))
        else:
            os.symlink(src, os.path.join(bench, name))
    _write(os.path.join(bench, "configs", "kv_dashboard.json"), CONFIG)
    for cell, traffic in TRAFFIC.items():
        _write(os.path.join(bench, "traffic", cell + ".json"), traffic)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        _write(os.path.join(tmp, "BENCHMARK.json"), merged(json.load(f)))
    return tmp


def run(cell: str, root: str, seed: int = 2**33 + 7, seconds: float = 1.0,
        trace: bool = False, control: bool = False, readings: bool = False):
    import run_cell
    return run_cell.run(cell, seed, seconds, trace, rehearse=True,
                        control=control, readings=readings, root=root)

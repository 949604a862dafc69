#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run_cell.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) is built from
its files: ``bench/configs/<config>.json``, ``bench/traffic/<cell>.json``
and, for each per-layer metric, ``bench/metrics/<metric>.py``. The run:

  1. refuses to go on without a TPU with compiled (not interpreted)
     Pallas kernels, or with fewer chips than the cell asks for;
  2. keeps JAX's persistent compilation cache at the checkout's fixed
     ``.jax_cache`` (or where ``JAX_COMPILATION_CACHE_DIR`` says);
  3. sets the cell up and compiles every shape its traffic uses
     (``setup_s`` runs from process start to the window's start);
  4. measures for ``--seconds``; with ``--trace 1`` the window is traced
     and the per-layer metrics are read instead of the end-to-end ones;
  5. reads the device's peak memory, frees the program's state, and
     compares what the timed path produced with the plain reference,
     printing each number beside its limit on standard error;
  6. prints one JSON object as the last line of standard output.

Exit code 0 means a result was printed (``correct`` may still be false);
any other code means there is no result.
"""
from __future__ import annotations

import os
import sys
import time


def _process_age() -> float:
    """Seconds since this process started (Linux; 0 elsewhere)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


_T0 = time.perf_counter() - _process_age()

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
_SRC = os.path.join(os.path.dirname(HERE), "src")
if os.path.isdir(_SRC) and _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import argparse  # noqa: E402
import ctypes  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

import harness  # noqa: E402


def run(workload: str, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, overrides=None, control: bool = False,
        readings: bool = False, root: str = harness.ROOT) -> dict:
    """One run of one cell; returns the result object.

    ``rehearse`` allows a run without a TPU (tests, at small sizes through
    ``overrides``): the result then carries no device metric. With
    ``control`` the cell's control (the reference one precision step
    lower) is compared in the program's place and decides ``correct``;
    with ``readings`` the program decides it and the control's numbers
    are added as ``control_checks``."""
    import jax
    cell = harness.find_cell(workload, root=root, overrides=overrides)
    device = harness.device_info(jax)
    if not rehearse:
        from repro.kernels import default_interpret
        if device["platform"] != "tpu":
            raise harness.CellError(
                f"no TPU found (platform {device['platform']!r})")
        if default_interpret():
            raise harness.CellError("Pallas kernels would be interpreted")
        if device["count"] < int(cell.entry["chips"]):
            raise harness.CellError(
                f"cell asks for {cell.entry['chips']} chips, found "
                f"{device['count']}")
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    kind = harness.kind_module(cell)
    rec = harness.Recorder(annotate=trace)
    rec.live = False
    state = kind.setup(cell, seed, rec)
    if hasattr(kind, "prepare"):
        kind.prepare(state, cell, seed, seconds)
    gc.collect()
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        # no Python function tracing: it slows the host's loops several
        # times over; the harness's spans are trace annotations
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - _T0
    rec.live = True
    try:
        with rec.span("window"):
            res = kind.window(state, seconds, rec)
    finally:
        rec.live = False
        if trace:
            jax.profiler.stop_trace()
    mem = harness.memory_peak(jax)
    reduced = None
    if trace:
        import xplane
        try:
            reduced = xplane.reduce(xplane.load(xplane.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
    out = kind.collect(state, cell, seed)
    del state
    gc.collect()
    sides = ("control",) if control else ("program",)
    sides += ("control",) if readings and not control else ()
    compared = kind.check(cell, seed, out, sides=sides)
    checks = compared[sides[0]]
    correct = _passes(checks)
    dev = {**device, "memory_peak_bytes": mem}
    if rehearse:
        reduced = None              # a CPU run gives no device metric
    if trace:
        peak = None if rehearse else _peak(device["kind"])
        ctx = harness.Context(cell, rec, reduced, peak)
        metrics = harness.read_per_layer(ctx)
        if reduced is not None:
            dev["busy_s"] = reduced.busy_s
            dev["window_s"] = reduced.window_s
    else:
        metrics = {m["name"]: {"value": float(res["metrics"][m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    if rehearse:
        dev["rehearsal"] = True
    result = {"correct": bool(correct), "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "metrics": metrics,
              "device": dev}
    if reduced is not None:
        result["breakdown"] = {
            "device_ops": sorted(([k, v] for k, v in reduced.ops.items()),
                                 key=lambda kv: -kv[1])[:10],
            "idle_gaps": [[label, secs] for label, secs in reduced.gaps]}
    if len(sides) > 1:
        result["control_correct"] = _passes(compared["control"])
        result["control_checks"] = compared["control"]
    result["checks"] = checks
    return result


def _passes(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def _peak(kind: str) -> dict:
    import peaks
    return peaks.peaks(kind)


def _keep_freed_memory():
    """Serve large host arrays from the heap and keep freed memory there
    (glibc: no mmap below 1 GiB, no trim), as a caching allocator such as
    tcmalloc would. A search round allocates several fresh arrays of tens
    of MB; on a sandboxed host every fresh page faults at about 1 GB/s, at
    a cost that swings with the host's load, so without this the window
    measures the page-fault path more than the program."""
    try:
        libc = ctypes.CDLL("libc.so.6")
    except OSError:
        return
    libc.mallopt(-3, 1 << 30)       # M_MMAP_THRESHOLD
    libc.mallopt(-1, 1 << 34)       # M_TRIM_THRESHOLD


def main(argv=None) -> int:
    _keep_freed_memory()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except harness.CellError as e:
        print(f"no result: {e}", file=sys.stderr, flush=True)
        return 2
    harness.print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

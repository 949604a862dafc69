"""The benchmark's machinery shared by every cell: finding a cell's files by
name, spans and records, the per-layer metric readers, and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Everything that
belongs to it is found by name, so a later change adds a cell, a
configuration or a metric by adding files:

    bench/configs/<config>.json    the deployment: sizes, guarantees,
                                   source, ``assumed``, ``reduced`` and the
                                   limits of its correctness checks
    bench/traffic/<cell>.json      the traffic mix: ``kind`` names the
                                   generator in ``bench/kinds/<kind>.py``,
                                   the rest are its parameters
    bench/metrics/<metric>.py      a ``read(ctx)`` returning the per-layer
                                   metric's value, or None when the run
                                   holds nothing to read it from
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class CellError(RuntimeError):
    """The run cannot produce a result (no chip, bad files, failed path)."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.isfile(path):
        raise CellError(f"no BENCHMARK.json at {root}")
    return load_json(path)


@dataclasses.dataclass
class Cell:
    name: str
    root: str            # the checkout: BENCHMARK.json and bench/ are here
    entry: dict          # the BENCHMARK.json workload entry
    config: dict         # bench/configs/<config>.json
    traffic: dict        # bench/traffic/<cell>.json
    end_to_end: list     # the end-to-end metric entries this cell reports
    per_layer: list      # the per-layer metric entries this cell reports


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    ws = metric.get("workloads")
    if ws is not None:
        return cell in ws
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def find_cell(name: str, root: str = ROOT, bench: Optional[dict] = None,
              overrides: Optional[dict] = None) -> Cell:
    """The cell ``name`` with its configuration and traffic files; the
    optional ``overrides`` ({"config": {...}, "traffic": {...}}) replace
    entries of either, for rehearsals at small sizes."""
    bench = benchmark(root) if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"]
                 if c["name"] == entry["config"]), None)
    if conf is None:
        raise CellError(f"no configuration {entry['config']!r}")
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     name + ".json"))
    for part, d in (("config", config), ("traffic", traffic)):
        for k, v in ((overrides or {}).get(part) or {}).items():
            if isinstance(v, dict) and isinstance(d.get(k), dict):
                d[k] = {**d[k], **v}
            else:
                d[k] = v
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, ())]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, root, entry, config, traffic, e2e, per)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise CellError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kind_module(cell: Cell):
    kind = cell.traffic["kind"]
    path = os.path.join(cell.root, "bench", "kinds", kind + ".py")
    if not os.path.isfile(path):
        raise CellError(f"no traffic generator bench/kinds/{kind}.py")
    return load_module(path, "bench_kind_" + kind)


# ---------------------------------------------------------------------------
# spans and records
# ---------------------------------------------------------------------------

class Recorder:
    """Host spans (seconds, by name) and records of one run.

    With ``annotate`` each span is also a ``jax.profiler.TraceAnnotation``
    named ``bench.<name>``, so a trace puts it on the device's clock and
    the reducer can name the idle gaps by it."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.spans: Dict[str, List[float]] = {}
        self.records: Dict[str, list] = {}
        self.live = True            # spans are recorded only while live

    def _annotation(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with self._annotation(name):
            try:
                yield
            finally:
                if self.live:
                    self.spans.setdefault(name, []).append(
                        time.perf_counter() - t0)

    def record(self, name: str, item):
        if self.live:
            self.records.setdefault(name, []).append(item)

# ---------------------------------------------------------------------------
# per-layer metrics and the result line
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader can look at."""

    cell: Cell
    recorder: Recorder
    trace: object            # xplane.Reduced of the window, or None
    peak: Optional[dict]     # bench.peaks entry of the device


def read_per_layer(ctx: Context) -> dict:
    out = {}
    for m in ctx.cell.per_layer:
        path = os.path.join(ctx.cell.root, "bench", "metrics",
                            m["name"] + ".py")
        if not os.path.isfile(path):
            raise CellError(f"no reader bench/metrics/{m['name']}.py")
        value = load_module(path, "bench_metric_" + m["name"].replace(
            ".", "_")).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_info(jax_mod) -> dict:
    devs = jax_mod.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax_mod) -> int:
    peak = 0
    for d in jax_mod.local_devices():
        try:
            stats = d.memory_stats() or {}
        except (RuntimeError, NotImplementedError):
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def print_checks(checks: dict, stream=sys.stderr):
    """Each number compared beside its limit, as the run's last lines on
    standard error."""
    for name, c in checks.items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{verdict}", file=stream, flush=True)

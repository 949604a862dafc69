"""Reduction of a profiler trace to device busy time, operation totals and
idle gaps attributed to the harness's spans.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. A device plane (``/device:TPU:<n>``) holds the operations that
ran on the chip, each with a start and a duration in nanoseconds on the
host's clock; the host plane's line of the thread that ran the window
(named after the process, such as ``python3``) holds the harness's spans
(``jax.profiler.TraceAnnotation``, named ``bench.<span>``), on the same
clock. From those:

    busy_s     the union of the operations' intervals inside the window,
               averaged over the devices traced
    window_s   the length of the ``bench.window`` span
    ops        seconds per operation, named ``<module>/<op>``
    gaps       the longest intervals inside the window in which no
               operation ran, each named by the innermost harness span
               open at its midpoint (``idle`` when none was)

``load`` reads a file; ``reduce`` works on plain tuples, so it is tested
on synthetic events as well as on a recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW = "window"          # the span around the measured window


@dataclasses.dataclass
class Op:
    device: str
    name: str
    module: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def _stat(ev, name: str):
    try:
        for k, v in ev.stats:
            if k == name:
                return v
    except (TypeError, ValueError):
        return None
    return None


def load(path: str, device_plane: str = r"^/device:TPU:\d+$",
         op_line: str = r"^XLA Ops$", module_line: str = r"^XLA Modules$"
         ) -> Trace:
    """Device operations and harness spans of one trace file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    dev_re, op_re, mod_re = (re.compile(device_plane), re.compile(op_line),
                             re.compile(module_line))
    for plane in pd.planes:
        if dev_re.search(plane.name):
            lines = list(plane.lines)
            mods = []
            for line in lines:
                if mod_re.search(line.name):
                    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name) for ev in line.events)
            for line in lines:
                if not op_re.search(line.name):
                    continue
                for ev in line.events:
                    mod = _stat(ev, "hlo_module")
                    if mod is None:
                        mod = _containing(mods, ev.start_ns)
                    ops.append(Op(plane.name, _op_name(ev.name),
                                  str(mod or ""), float(ev.start_ns),
                                  float(ev.duration_ns)))
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append(Span(ev.name[len(SPAN_PREFIX):],
                                      float(ev.start_ns),
                                      float(ev.duration_ns)))
    return Trace(ops, spans)


def _op_name(text: str) -> str:
    """The HLO instruction's name: a TPU trace names an operation by its
    whole HLO text (``%fused_seeds.1 = (...) custom-call(...)``), whose
    operands name other instructions."""
    m = re.match(r"%?([\w.\-]+) = ", text)
    return m.group(1) if m else text


def _containing(mods, t) -> Optional[str]:
    lo, hi = 0, len(mods)
    while lo < hi:                       # last module starting at or before t
        mid = (lo + hi) // 2
        if mods[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and mods[lo - 1][0] <= t <= mods[lo - 1][1]:
        return mods[lo - 1][2]
    return None


def _union(intervals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _module_name(m: str) -> str:
    """``jit__absorb_jit(123)`` -> ``_absorb_jit``: the jitted function."""
    m = re.sub(r"(\(\d+\)|\.\d+)$", "", m)
    return m[4:] if m.startswith("jit_") else m


@dataclasses.dataclass
class Reduced:
    busy_s: float
    window_s: float
    devices: int
    ops: dict            # "<module>/<op>" -> seconds
    gaps: list           # [(label, seconds)], longest first


def reduce(trace: Trace, top: int = 10) -> Reduced:
    windows = [s for s in trace.spans if s.name == WINDOW]
    if not windows:
        raise ValueError("trace has no bench.window span")
    w = max(windows, key=lambda s: s.dur_ns)
    w0, w1 = w.start_ns, w.start_ns + w.dur_ns
    devices = sorted({o.device for o in trace.ops})
    busy_ns = 0.0
    ops: dict = {}
    all_busy = []
    for d in devices:
        iv = []
        for o in trace.ops:
            if o.device != d:
                continue
            s, e = max(o.start_ns, w0), min(o.start_ns + o.dur_ns, w1)
            if e <= s:
                continue
            iv.append((s, e))
            key = f"{_module_name(o.module)}/{o.name}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        u = _union(iv)
        busy_ns += sum(e - s for s, e in u)
        all_busy.append(u)
    n_dev = max(len(devices), 1)
    # gaps: where no device of the trace was busy
    u = _union([iv for dev in all_busy for iv in dev])
    gaps, t = [], w0
    for s, e in u:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if w1 > t:
        gaps.append((t, w1))
    spans = [s for s in trace.spans if s.name != WINDOW]
    labeled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        inner = [sp for sp in spans
                 if sp.start_ns <= mid <= sp.start_ns + sp.dur_ns]
        label = (min(inner, key=lambda sp: sp.dur_ns).name if inner
                 else "idle")
        labeled.append((label, (e - s) * 1e-9))
    return Reduced(busy_s=busy_ns * 1e-9 / n_dev, window_s=(w1 - w0) * 1e-9,
                   devices=len(devices), ops=ops, gaps=labeled)


def op_seconds(red: Reduced, op: str) -> float:
    """Total seconds of the operations whose name contains ``op``."""
    return sum(secs for key, secs in red.ops.items()
               if op in key.partition("/")[2])

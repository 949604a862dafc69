"""Program spans and counters, recorded only while a JAX profiler session
is active.

An operator who captures a profile (``jax.profiler.trace`` /
``start_trace``, or an on-demand capture through the profiler server)
turns them on; nothing else does, so there is no switch. Off, a span costs
one flag check and returns a shared null context, and a counter costs the
same check.

While on, each ``span(name)``:

  * is a ``jax.profiler.TraceAnnotation`` named ``name``, so it lands in
    the same ``.xplane.pb`` as the device's operations, on the same clock:
    an idle gap of the device can be read against the program phase open
    at that moment;
  * appends one record ``(name, t0, t1, parent)`` (``time.perf_counter``
    seconds; ``parent`` is the name of the innermost span open on the same
    thread, or None) to an in-memory buffer of at most ``MAX_SPANS``
    records; the overflow is counted as ``trace.dropped``.

``count(name, n)`` adds to a counter while on; ``n`` may be a function
of no arguments, called only while on, for a count that costs work to
take. A ``jax.monitoring`` listener adds the runtime's own counter
``jax.traces`` (jit cache misses: jaxpr traces made inside no other
compile step).

``snapshot()`` returns what was recorded; ``reset()`` clears it.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
from jax import monitoring

try:  # the flag jax.profiler.TraceAnnotation itself checks
    from jax._src.lib import _profiler
    _enabled = _profiler.TraceMe.is_enabled
except (ImportError, AttributeError):
    def _enabled() -> bool:
        return False

MAX_SPANS = 1 << 20

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"

SpanRecord = Tuple[str, float, float, Optional[str]]


class Snapshot(NamedTuple):
    spans: List[SpanRecord]       # (name, t0, t1, parent)
    counters: Dict[str, float]


_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_local = threading.local()
_spans: List[SpanRecord] = []
_counters: Dict[str, float] = {}


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _add(name: str, n) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


class _Span:
    __slots__ = ("name", "parent", "t0", "ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        _stack().pop()
        with _lock:
            if len(_spans) < MAX_SPANS:
                _spans.append((self.name, self.t0, t1, self.parent))
            else:
                n = _counters.get("trace.dropped", 0)
                _counters["trace.dropped"] = n + 1
        return False


def span(name: str):
    """A context manager recording the span ``name`` while a profiler
    session is active, and doing nothing otherwise."""
    if not _enabled():
        return _NULL
    return _Span(name)


def count(name: str, n=1) -> None:
    """Add ``n`` (or, if it is callable, ``n()``) to the counter ``name``
    while a profiler session is active."""
    if _enabled():
        _add(name, n() if callable(n) else n)


def snapshot() -> Snapshot:
    """Copies of the span records and counters recorded so far."""
    with _lock:
        return Snapshot(list(_spans), dict(_counters))


def reset() -> None:
    """Forget every span record and counter."""
    with _lock:
        _spans.clear()
        _counters.clear()


# A jit cache miss traces its function, lowers it and compiles it. Tracing
# also traces every function it calls that is not traced yet, and lowering
# may trace more (an interpreted kernel's body): each is a nested trace
# event, and only a trace inside no other compile event counts. Each event
# announces its start as a scalar and its end as a duration, on the thread
# that compiles.
_COMPILE_EVENTS = (_TRACE_EVENT,
                   "/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")


def _on_scalar(event: str, value, **kw) -> None:
    if event in _COMPILE_EVENTS:
        _local.depth = getattr(_local, "depth", 0) + 1


def _on_duration(event: str, secs: float, **kw) -> None:
    if event not in _COMPILE_EVENTS:
        return
    depth = _local.depth = max(getattr(_local, "depth", 1) - 1, 0)
    if event == _TRACE_EVENT and depth == 0:
        count("jax.traces")


monitoring.register_scalar_listener(_on_scalar)
monitoring.register_event_duration_secs_listener(_on_duration)

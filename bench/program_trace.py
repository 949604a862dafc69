"""The program's own spans and counters (``repro.telemetry.trace``), as
the per-layer metrics of the search read them.

The program records them only while the traced run's profiler session is
open. A run that recorded nothing, or a program without the tracer,
reads as None."""

SETS = "cluster.score.sets"


def snapshot():
    """The program's spans and counters, or None where it has none."""
    try:
        from repro.telemetry import trace
    except ImportError:             # a program without its own spans
        return None
    snap = trace.snapshot()
    return snap if snap.spans or snap.counters else None


def us_per_set(span: str):
    """Host time in the program span ``span``, summed over the window,
    per set scored (the counter ``cluster.score.sets``), us."""
    snap = snapshot()
    sets = snap.counters.get(SETS) if snap else None
    if not sets:
        return None
    return 1e6 * sum(t1 - t0 for name, t0, t1, _ in snap.spans
                     if name == span) / sets


def per_set(counter: str):
    """The program counter ``counter`` over the window, per set scored."""
    snap = snapshot()
    sets = snap.counters.get(SETS) if snap else None
    if not sets:
        return None
    return snap.counters.get(counter, 0) / sets

"""Jit cache misses inside the traced window, count.

Layer: runtime (JAX): the program counter ``jax.traces``, which counts
each jaxpr trace made outside any other compile step while the run's
profiler session is open (``repro.telemetry.trace``). Set-up compiles
every shape the window uses, so the window should read 0. Moves
``sets_scored_per_s``."""


def read(ctx):
    import program_trace
    snap = program_trace.snapshot()
    return None if snap is None else snap.counters.get("jax.traces", 0)

"""Bytes of cost table copied from host memory per set scored, B.

Layer: cluster engine (``ClusterEngine.service_costs``): the program
counter ``cluster.score.upload_bytes`` (the padded table's fields that
were still in host memory; a field already on the device counts 0) over
the window, divided by the counter ``cluster.score.sets``. The program
counts them (``repro.telemetry.trace``) only while the run's profiler
session is open; a program without the counter reads as None. Moves
``sets_scored_per_s``."""

COUNTER = "cluster.score.upload_bytes"


def read(ctx):
    import program_trace
    snap = program_trace.snapshot()
    if snap is None or COUNTER not in snap.counters:
        return None
    return program_trace.per_set(COUNTER)

"""Bytes of cost table handed to the device per set scored, B.

Layer: cluster engine (``ClusterEngine.service_costs``): the program
counter ``cluster.score.table_bytes`` (the five fields of each padded
table) over the window, divided by the counter ``cluster.score.sets``.
The program counts them (``repro.telemetry.trace``) only while the run's
profiler session is open. Moves ``sets_scored_per_s``."""


def read(ctx):
    import program_trace
    return program_trace.per_set("cluster.score.table_bytes")

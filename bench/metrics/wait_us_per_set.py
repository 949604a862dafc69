"""Host time the engine waits for its scores per set scored, us.

Layer: cluster engine (``ClusterEngine.service_costs``): the program span
``cluster.score.wait`` (the read-back of the scores, which blocks on the
table's host relayout, the kernel and the copy back), summed over the
traced window and divided by the counter ``cluster.score.sets``. The program records it
(``repro.telemetry.trace``) only while the run's profiler session is
open. Moves ``sets_scored_per_s``."""


def read(ctx):
    import program_trace
    return program_trace.us_per_set("cluster.score.wait")

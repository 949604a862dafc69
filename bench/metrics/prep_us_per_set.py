"""Host time of the engine's table preparation per set scored, us.

Layer: cluster engine (``ClusterEngine.service_costs``): the program span
``cluster.score.prep`` (the table's encoding check, its host copy and
its padding to the Q bucket), summed over the traced window and divided
by the counter ``cluster.score.sets``. The program records it
(``repro.telemetry.trace``) only while the run's profiler session is
open. Moves ``sets_scored_per_s``."""


def read(ctx):
    import program_trace
    return program_trace.us_per_set("cluster.score.prep")

"""Host time of the search's table encoding per set scored, us.

Layer: search (``launch/cluster.py`` ``local_search``): the program span
``cluster.search.encode`` (``core/costs.py`` ``cost_table`` over a
round's sets, one Python step a set), summed over the traced window and
divided by the counter ``cluster.score.sets``. The program records it
(``repro.telemetry.trace``) only while the run's profiler session is
open. Moves ``sets_scored_per_s``."""


def read(ctx):
    import program_trace
    return program_trace.us_per_set("cluster.search.encode")

"""Share of the traced window in which no operation ran on the device, %.

Layer: device: 1 minus the union of the operation intervals of the chip
the stream runs on, over the window, from the profiler trace. Moves
``query_p99_ms``."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)

"""Mean host time of one ``EnginePool.pump`` of the window, ms.

Layer: pool admission (``launch/pool.py``): the admission queue drained,
its requests coalesced by objectives into one fused launch each, the
answers read back and split. The window pumps only when it has submitted
work, so every pump serves some. The harness's span around the call.
Moves ``query_p99_ms``.
"""


def read(ctx):
    xs = ctx.recorder.spans.get("pump")
    return 1e3 * sum(xs) / len(xs) if xs else None

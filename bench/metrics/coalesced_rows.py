"""Mean predicate rows per fused query launch of the window, rows.

Layer: pool admission: the rows of each coalesced table the pool hands
the engine (``SegmentQueryEngine.query_many``, wrapped on the stream's
instance; one call is one launch), averaged over the window's calls.
Moves ``query_p99_ms``.
"""


def read(ctx):
    xs = ctx.recorder.records.get("launch")
    return sum(b for _, b in xs) / len(xs) if xs else None

"""The ``segment_query`` kernel's share of its roofline, %.

Layer: kernels. Each fused launch of the window answers its coalesced
rows over the merged slab; the least time of those calls
(``stream_cost.segment_query``: the slab and the rows read, the answers
written, against the HBM bandwidth) over the kernel's device time in the
trace. Moves ``query_p99_ms``.
"""


def read(ctx):
    import stream_cost as sc
    import xplane
    calls = ctx.recorder.records.get("launch")
    if ctx.trace is None or ctx.peak is None or not calls:
        return None
    objs = ctx.cell.config["stream"]["objectives"]
    cap = sum(int(o[2]) for o in objs) + len(objs) + 1
    least = sum(sc.least_seconds(sc.segment_query(cap, b, nf), ctx.peak)
                for nf, b in calls)
    dev = xplane.op_seconds(ctx.trace, "segment_query")
    return 100.0 * least / dev if dev > 0 else None

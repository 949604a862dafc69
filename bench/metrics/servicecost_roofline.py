"""The ``service_cost`` kernel's share of its roofline, %.

Layer: kernels. The least time the chip needs for the window's scoring
calls (``kernel_cost.service_cost``: Q sets of Cmax centres against the
sample's slab), over the kernel's device time in the trace. The distance
contraction is compute-bound at the larger Q; it runs in float32 at
HIGHEST precision, several bf16 passes, against the bf16 peak. Moves
``sets_scored_per_s``.
"""


def read(ctx):
    import kernel_cost as kc
    import xplane
    shapes = ctx.recorder.records.get("score_shapes")
    if ctx.trace is None or ctx.peak is None or not shapes:
        return None
    c = ctx.cell.config["cluster"]
    cap = int(c["k"]) + 2
    d = int(c["dim"])
    least = sum(kc.least_seconds(kc.service_cost(cap, q, cm, d),
                                 ctx.peak)[0] for q, cm in shapes)
    dev = xplane.op_seconds(ctx.trace, "service_cost")
    return 100.0 * least / dev if dev > 0 else None

"""Mean host time of one ``ClusterEngine.service_costs`` call of the
search, ms.

Layer: cluster engine (``launch/cluster.py``): the cost table's encoding
and padding, its copy to the device, the fused launch and the copy back.
Read from the harness's span in the counting scorer. Moves
``sets_scored_per_s``."""


def read(ctx):
    xs = ctx.recorder.spans.get("score")
    return 1e3 * sum(xs) / len(xs) if xs else None

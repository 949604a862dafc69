"""Mean host time of one snapshot, ms.

Layer: durability (``EnginePool.snapshot``: the checkpoint of every
shard slab through ``ckpt/manager.py``, then the WAL prune past the
oldest kept snapshot), every ``snapshot_every`` folds: the harness's
span around the method, wrapped on the pool. Moves
``ingest_events_per_s``.
"""


def read(ctx):
    xs = ctx.recorder.spans.get("snapshot")
    return 1e3 * sum(xs) / len(xs) if xs else None

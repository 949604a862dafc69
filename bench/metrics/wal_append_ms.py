"""Mean host time of one WAL append with its fsync, per chunk, ms.

Layer: durability (``launch/wal.py`` ``WriteAheadLog.append``, which the
pool calls before every acknowledgement): the harness's span around the
method, wrapped on the stream's instance. Moves ``ingest_events_per_s``.
"""


def read(ctx):
    xs = ctx.recorder.spans.get("wal_append")
    return 1e3 * sum(xs) / len(xs) if xs else None

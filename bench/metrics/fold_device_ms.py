"""Device time of the fold programs per chunk absorbed, ms.

Layer: engine fold (``core/multi_sketch.py``): every operation of the
programs a chunk's absorb runs (the shard fold ``_absorb_jit``, the
absorb-time merged-slab fold ``_absorb_into_jit`` and the probabilities'
``_finalize_probs_jit``) summed over the traced window, over the chunks
the window absorbed. Moves ``ingest_events_per_s``.
"""

FOLDS = ("_absorb_jit", "_absorb_into_jit", "_finalize_probs_jit")


def read(ctx):
    chunks = ctx.recorder.records.get("chunk_events")
    if ctx.trace is None or not chunks:
        return None
    secs = sum(s for key, s in ctx.trace.ops.items()
               if key.partition("/")[0] in FOLDS)
    return 1e3 * secs / len(chunks) if secs > 0 else None

"""The ``block_select`` kernel's share of its roofline, %.

Layer: kernels. Each chunk's absorb selects the k_max + 1 smallest seeds
of every objective twice: in the shard fold over the shard's slab and the
chunk (``_absorb_jit``, cap + chunk rows) and in the merged-slab fold
over the merged slab and the shard's (``_absorb_into_jit``, 2 cap rows).
The least time of those calls (``stream_cost.block_select``, bytes
against the HBM bandwidth) over the kernel's device time in the trace,
counting only the programs in which the kernel ran. Moves
``ingest_events_per_s``.
"""

ROWS = {"_absorb_jit": lambda cap, n: cap + n,
        "_absorb_into_jit": lambda cap, n: 2 * cap}


def read(ctx):
    import stream_cost as sc
    chunks = ctx.recorder.records.get("chunk_events")
    if ctx.trace is None or ctx.peak is None or not chunks:
        return None
    objs = ctx.cell.config["stream"]["objectives"]
    nf = len(objs)
    kmax = max(int(o[2]) for o in objs)
    cap = sum(int(o[2]) for o in objs) + nf + 1
    least = dev = 0.0
    for module, rows in ROWS.items():
        secs = sum(s for key, s in ctx.trace.ops.items()
                   if key.partition("/")[0] == module
                   and "block_select" in key.partition("/")[2])
        if secs > 0:
            dev += secs
            least += sum(sc.least_seconds(
                sc.block_select(nf, rows(cap, n), kmax + 1), ctx.peak)
                for n in chunks)
    return 100.0 * least / dev if dev > 0 else None

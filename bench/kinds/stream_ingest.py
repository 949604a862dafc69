"""Closed-loop durable ingest into one tenant's key-value stream.

Set-up makes ``chunks`` chunks of the seed in host memory (the loop's
next chunk is always ready, so the rate is the absorb capacity), opens
the durable stream and absorbs the first ``setup_chunks`` of them with
one read after the first: the read builds the merged slab, so every later
absorb also maintains it, as under an open dashboard, and every fold
program is compiled before the window.

The window absorbs the next chunk, and the next, until ``--seconds``
have passed; there are no reads in it. ``ingest_events_per_s`` is the
acknowledged events of the window over the time from its start to the
last acknowledgement. After the window a panel of ``check_predicates``
fixed predicates over all objectives is answered through the pool and
kept for the check, with the sample and the reopened stream
(``kv_stream``).
"""
from __future__ import annotations

import time

import kv_stream
import stream_gen


def setup(cell, seed: int, rec) -> dict:
    t = cell.traffic
    state = kv_stream.open_stream(cell, rec)
    state["chunks"] = kv_stream.chunks(cell.config, seed, 0,
                                       int(t["chunks"]))
    for i in range(int(t["setup_chunks"])):
        kv_stream.absorb(state, *state["chunks"][i])
        if i == 0:
            kv_stream.answer(state, (0,), stream_gen.NEVER_TABLE)
    return state


def window(state: dict, seconds: float, rec) -> dict:
    todo = state["chunks"]
    first = state["acked"]
    t0 = time.perf_counter()
    t_end = t0 + seconds
    t_last = t0
    events = 0
    while t_last < t_end and state["acked"] < len(todo):
        keys, weights = todo[state["acked"]]
        with rec.span("absorb"):
            kv_stream.absorb(state, keys, weights)
        t_last = time.perf_counter()
        events += keys.shape[0]
        rec.record("chunk_events", keys.shape[0])
    del state["chunks"]
    attempted = state["acked"] - first
    return {"metrics": {"ingest_events_per_s": events / (t_last - t0)},
            "attempted": attempted, "failed": state["broken"]}


def collect(state: dict, cell, seed: int) -> dict:
    cfg = cell.config
    rng = stream_gen.rng_of(seed, 7)
    table = stream_gen.predicates(rng, int(cell.traffic["check_predicates"]),
                                  int(cfg["events"]["ids_log2"]),
                                  int(cfg["stream"]["hash_seed"]))
    fs = tuple(range(len(cfg["stream"]["objectives"])))
    r = kv_stream.answer(state, fs, table)
    if kv_stream.fresh(r):
        state["queries"].append((fs, table, r.values))
    else:
        state["broken"] += 1
    return kv_stream.collect(state)


def check(cell, seed: int, out: dict, sides=("program",)) -> dict:
    return kv_stream.check(cell, seed, out, sides)

"""Open-loop dashboard reads of one tenant's key-value stream.

Set-up fills the durable stream with the seed's first ``fill_chunks``
chunks (round-robin over the shards, one read after the first), then
compiles every ``b_quantum`` bucket of coalesced rows up to
``panel_rows_max`` for the panels over all objectives and up to
``lookup_rows_max`` for the SUM lookups, and the lookups' one-row path.
The pool's admission queue holds at most 128 requests, so no coalesced
launch can outgrow those the traffic file allows for.

The window holds reads only: requests arrive as Poisson events at
``rate_per_s``, a ``panel_share`` of them panels of ``panel_rows``
predicates over every objective (key ranges and hash fractions in turn),
the rest single key-range SUM lookups. One thread submits every request
now due, then pumps the pool once, which coalesces them by objectives
into one fused launch each; with none due it sleeps until the next. A
request's latency runs from its due time to the end of the pump that
answered it. ``query_p50_ms`` and ``query_p99_ms`` are over every
request answered; a shed request or an answer that is not FRESH counts
in ``failed``. A seeded sample of the answers is kept for the check.
"""
from __future__ import annotations

import sys
import time

import numpy as np

import kv_stream
import stream_gen


def setup(cell, seed: int, rec) -> dict:
    t = cell.traffic
    state = kv_stream.open_stream(cell, rec)
    for i, (keys, weights) in enumerate(kv_stream.chunks(
            cell.config, seed, 0, int(t["fill_chunks"]))):
        kv_stream.absorb(state, keys, weights)
        if i == 0:
            kv_stream.answer(state, (0,), stream_gen.NEVER_TABLE)
    nf = len(cell.config["stream"]["objectives"])
    quantum = int(cell.config["stream"]["b_quantum"])
    for fs, top, single in ((tuple(range(nf)), t["panel_rows_max"], False),
                            ((0,), t["lookup_rows_max"], True)):
        for b in [1] * single + list(range(quantum, int(top) + 1, quantum)):
            kv_stream.answer(state, fs,
                             np.repeat(stream_gen.NEVER_TABLE, b, axis=0))
    engine = state["engine"]
    query_many = engine.query_many

    def counted(fs=None, predicates=None):
        rec.record("launch", (len(fs), int(np.shape(predicates)[0])))
        return query_many(fs, predicates)
    engine.query_many = counted
    return state


def prepare(state: dict, cell, seed: int, seconds: float):
    t = cell.traffic
    cfg = cell.config
    rate = float(t["rate_per_s"])
    n = int(rate * seconds * 1.2) + 64
    rng = stream_gen.rng_of(seed, 9)
    ids, hseed = int(cfg["events"]["ids_log2"]), int(cfg["stream"]["hash_seed"])
    rows = int(t["panel_rows"])
    panel = rng.random(n) < float(t["panel_share"])
    n_panel = int(panel.sum())
    panels = stream_gen.predicates(rng, n_panel * rows, ids, hseed)
    lookups = stream_gen.predicates(rng, n - n_panel, ids, hseed,
                                    hashed=False)
    tables, p, q = [], 0, 0
    for is_panel in panel:
        if is_panel:
            tables.append(panels[p * rows:(p + 1) * rows])
            p += 1
        else:
            tables.append(lookups[q:q + 1])
            q += 1
    objs = state["engine"].spec.objectives
    nf = len(objs)
    state.update(
        due=stream_gen.poisson_arrivals(rng, rate, n), panel=panel,
        tables=tables,
        fs={True: tuple(range(nf)), False: (0,)},
        fns={True: tuple(f for f, _ in objs), False: (objs[0][0],)},
        keep=rng.random(n) < float(t["check_requests"]) / (rate * seconds))


def window(state: dict, seconds: float, rec) -> dict:
    from repro.launch.pool import RejectedError
    pool = state["pool"]
    due, panel, tables = state["due"], state["panel"], state["tables"]
    keep, fns, fsi = state["keep"], state["fns"], state["fs"]
    lat, late = [], []
    failed = i = 0
    t0 = time.perf_counter()
    while i < len(due) and due[i] < seconds:
        now = time.perf_counter() - t0
        if due[i] > now:
            time.sleep(due[i] - now)
            continue
        batch = []
        while i < len(due) and due[i] <= now:
            late.append(now - due[i])
            try:
                batch.append((i, pool.submit(kv_stream.NAME,
                                             fs=fns[bool(panel[i])],
                                             predicates=tables[i])))
            except RejectedError:
                failed += 1
            i += 1
        with rec.span("pump"):
            pool.pump()
        done = time.perf_counter() - t0
        for j, fut in batch:
            r = fut.result()
            if not kv_stream.fresh(r):
                failed += 1
                state["broken"] += 1       # served, but not FRESH
                continue
            lat.append(done - due[j])
            if keep[j]:
                state["queries"].append((fsi[bool(panel[j])], tables[j],
                                         r.values))
    lat_ms = 1e3 * np.asarray(lat)
    late_ms = 1e3 * np.asarray(late)
    print(f"read window: {i} requests due, {len(lat)} answered, {failed} "
          f"failed; generator late p50 {np.median(late_ms):.4f} ms, max "
          f"{late_ms.max():.4f} ms", file=sys.stderr, flush=True)
    return {"metrics": {"query_p50_ms": float(np.percentile(lat_ms, 50)),
                        "query_p99_ms": float(np.percentile(lat_ms, 99))},
            "attempted": i, "failed": failed}


def collect(state: dict, cell, seed: int) -> dict:
    return kv_stream.collect(state)


def check(cell, seed: int, out: dict, sides=("program",)) -> dict:
    return kv_stream.check(cell, seed, out, sides)

"""What the key-value stream kinds (``stream_ingest``, ``stream_read``)
share: the durable stream's set-up, its chunks, the copy-out after the
window and the comparison with the plain reference.

The stream is one tenant of an ``EnginePool`` with a durability
directory under ``TMPDIR`` (WAL, snapshots), its chunks round-robin over
the configuration's shards. The pool is driven from one thread; the WAL
append and the snapshot are wrapped on their instances in the harness's
spans ``wal_append`` and ``snapshot`` (neither layer has a span of its
own). After the window ``collect`` copies the merged slab out, closes the
pool, reopens the stream from the run's WAL and snapshots and counts the
slots that differ from the live slab; ``check`` regenerates every
acknowledged chunk from the seed and compares the sample and the answers
with ``ref_stream``.
"""
from __future__ import annotations

import concurrent.futures
import functools
import shutil
import tempfile

import numpy as np

import ref_stream
import stream_gen

NAME = "kv"
_FIELDS = ("keys", "weights", "probs", "seeds", "member", "aux", "valid",
           "taus")


def spec_of(cfg: dict):
    from repro.core.funcs import StatFn
    from repro.core.multi_sketch import MultiSketchSpec
    s = cfg["stream"]
    return MultiSketchSpec(
        objectives=tuple((StatFn(kind, float(p)), int(k))
                         for kind, p, k in s["objectives"]),
        scheme=s["scheme"], seed=int(s["hash_seed"]))


def chunks(cfg: dict, seed: int, start: int, stop: int) -> list:
    """Chunks ``start``..``stop - 1`` of the seed, made on a few threads
    (the generator releases the GIL in its bulk draws)."""
    make = functools.partial(stream_gen.stream_chunk, seed,
                             events=cfg["events"])
    with concurrent.futures.ThreadPoolExecutor(8) as ex:
        return list(ex.map(make, range(start, stop)))


def _timed(rec, obj, attr: str, span: str):
    """Wrap ``obj.attr`` on the instance in the recorder's span."""
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with rec.span(span):
            return fn(*a, **kw)
    setattr(obj, attr, wrapped)


def open_stream(cell, rec) -> dict:
    from repro.launch.pool import EnginePool
    s = cell.config["stream"]
    state_dir = tempfile.mkdtemp(prefix="kv-stream-")
    pool = EnginePool(durability_dir=state_dir,
                      snapshot_every=int(s["snapshot_every"]),
                      keep_snapshots=int(s["keep_snapshots"]))
    engine = pool.create_stream(NAME, spec_of(cell.config),
                                shards=int(s["shards"]),
                                b_quantum=int(s["b_quantum"]))
    _timed(rec, pool._stream(NAME).wal, "append", "wal_append")
    _timed(rec, pool, "snapshot", "snapshot")
    return {"pool": pool, "engine": engine, "dir": state_dir, "acked": 0,
            "broken": 0, "shards": int(s["shards"]), "queries": []}


def absorb(state: dict, keys, weights) -> bool:
    """Ingest the next chunk; True when it was acknowledged durably and
    applied (anything else breaks the first guarantee)."""
    r = state["pool"].absorb(NAME, keys, weights,
                             shard=state["acked"] % state["shards"])
    ok = (r.durable and r.applied and r.accepted == keys.shape[0]
          and r.seq == state["acked"] + 1)
    if r.durable:
        state["acked"] += 1
    if not ok:
        state["broken"] += 1
    return ok


def answer(state: dict, fs, table):
    """Serve one batch of wire rows through ``submit`` and ``pump``, over
    the objectives of indices ``fs``; returns the response."""
    pool = state["pool"]
    objs = state["engine"].spec.objectives
    fut = pool.submit(NAME, fs=tuple(objs[i][0] for i in fs),
                      predicates=table)
    pool.pump()
    return fut.result()


def fresh(response) -> bool:
    from repro.launch.pool import FRESH
    return (response.status == FRESH and response.error is None
            and response.values is not None)


def _host_slab(sk) -> dict:
    return {f: np.asarray(getattr(sk, f)) for f in _FIELDS}


def _bits(x):
    return x.view(np.uint32) if x.dtype == np.float32 else x


def _slots_differ(a: dict, b: dict) -> int:
    """Slots (and thresholds) whose bits differ between two slabs."""
    if a["keys"].shape != b["keys"].shape:
        return int(max(a["keys"].shape[0], b["keys"].shape[0]))
    diff = np.zeros(a["keys"].shape[0], bool)
    for f in _FIELDS[:-1]:
        ne = _bits(a[f]) != _bits(b[f])
        diff |= ne if ne.ndim == 1 else ne.any(axis=0)
    return int(diff.sum() + (_bits(a["taus"]) != _bits(b["taus"])).sum())


def collect(state: dict) -> dict:
    """The live merged slab's sample, the served answers kept for the
    check, and the slots the reopened stream differs in."""
    from repro.launch.pool import EnginePool
    pool = state["pool"]
    st = pool.stats(NAME)
    broken = (state["broken"] + int(st["pending"])
              + int(st["snapshot_failures"]))
    live = _host_slab(state["engine"].merged)
    pool.close()
    try:
        again = EnginePool(durability_dir=state["dir"])
        back = _host_slab(again.restore_stream(NAME).merged)
        again.close()
    finally:
        shutil.rmtree(state["dir"], ignore_errors=True)
    m = live["member"]
    order = np.argsort(live["keys"][m])
    return {"acked": state["acked"], "broken": broken,
            "members": live["keys"][m][order].astype(np.int64),
            "probs": live["probs"][m][order],
            "recovered_diff": _slots_differ(live, back),
            "queries": state["queries"]}


def check(cell, seed: int, out: dict, sides=("program",)) -> dict:
    """The numbers compared, with their limits, for each side: the
    ``program``'s outputs, or the ``control``'s put in their place (the
    reference with every weight rounded to bfloat16)."""
    cfg = cell.config
    limits = cfg["limits"]
    objs = cfg["stream"]["objectives"]
    hseed = int(cfg["stream"]["hash_seed"])
    wmax = ref_stream.max_weights(
        iter(_regenerate(cfg, seed, out["acked"])),
        int(cfg["events"]["ids_log2"]))
    ref = ref_stream.sample(wmax, objs, hseed)
    qs = out["queries"]
    want = np.concatenate([ref.answers(t, fs).ravel() for fs, t, _ in qs]
                          or [np.zeros(0)])

    def numbers(keys, probs, got, recovered):
        nums = {"member_diff": ref_stream.member_diff(ref.keys, keys),
                "prob_gap": ref_stream.prob_gap(ref, keys, probs),
                "answer_gap": ref_stream.answer_gap(want, got),
                "recovered_diff": recovered,
                "guarantee_breaks": out["broken"]}
        return {k: {"value": float(v), "limit": float(limits[k])}
                for k, v in nums.items()}

    res = {}
    for side in sides:
        if side == "program":
            got = np.concatenate([np.asarray(v, np.float64).ravel()
                                  for _, _, v in qs] or [np.zeros(0)])
            res[side] = numbers(out["members"], out["probs"], got,
                                out["recovered_diff"])
        elif side == "control":
            low = ref_stream.sample(wmax, objs, hseed, bf16=True)
            got = np.concatenate([low.answers(t, fs).ravel()
                                  for fs, t, _ in qs] or [np.zeros(0)])
            res[side] = numbers(low.keys, low.probs, got, 0)
        else:
            raise ValueError(f"no side {side!r}")
    return res


def _regenerate(cfg: dict, seed: int, n: int):
    step = 32
    for s in range(0, n, step):
        yield from chunks(cfg, seed, s, min(s + step, n))

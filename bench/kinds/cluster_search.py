"""Closed-loop k-means local search over a fitted clustering sample.

Set-up makes the points on the device, fits a ``ClusterEngine`` on all of
them in chunks of ``2^fit_chunk_log2`` points (the anchors freeze at the
first), and compiles every shape the window uses: for each centre count,
the search's start (its farthest-point seeding and one Q = 1 score) and
one full round's score (Q = 1 + k * n_cand sets of k centres).

The window runs ``local_search`` after ``local_search``, with the centre
counts of ``centers`` in turn, ``rounds`` swap rounds each: the traffic's
``tol`` of -1 takes the best swap of every round, so no search stops
early. Every seed thus does the same searches in the same order on its
own points; a seeded order would let the seed choose which counts the
window's last searches have, and a k = 50 round scores sets several times
slower than a k = 10 one. Every round scores its
sets in one call of ``ClusterEngine.service_costs``, made through a
counting scorer: ``sets_scored_per_s`` is the sets scored over the
window, from its start to the end of the last call. A seeded sample of
the scored sets is kept and checked against the reference after the
window.
"""
from __future__ import annotations

import time

import numpy as np

import ref_cluster
from gen import mixture_points


class _WindowClosed(Exception):
    pass


def _engine_kw(cfg: dict) -> dict:
    c = cfg["cluster"]
    return dict(k=int(c["k"]), mu=float(c["mu"]), n_anchors=int(c["anchors"]),
                seed=int(c["hash_seed"]), chunk=1 << int(c["fit_chunk_log2"]))


def setup(cell, seed: int, rec) -> dict:
    from repro.core.costs import cost_table
    from repro.launch.cluster import ClusterEngine, local_search
    c = cell.config["cluster"]
    t = cell.traffic
    n, d = int(c["points"]), int(c["dim"])
    pts = mixture_points(seed, n, d, int(c["components"]))
    eng = ClusterEngine(dim=d, **_engine_kw(cell.config))
    step = 1 << int(c["fit_chunk_log2"])
    for s in range(0, n, step):
        eng.absorb(pts[s:s + step])
    nc = int(t["n_cand"])
    for k in t["centers"]:
        local_search(eng, int(k), rounds=0, n_cand=nc)
        cand = np.asarray(eng._coords)[:nc]
        sets = np.broadcast_to(cand[:int(k)], (1 + int(k) * nc, int(k), d))
        eng.service_costs(cost_table(sets, float(c["mu"])))
    return {"engine": eng, "points": pts}


def prepare(state: dict, cell, seed: int, seconds: float):
    ks = [int(k) for k in cell.traffic["centers"]]
    state["order"] = ks * 64            # 64 turns cover any window
    state["keep_rng"] = np.random.default_rng([seed, 22])
    state["traffic"] = cell.traffic


def window(state: dict, seconds: float, rec) -> dict:
    from repro.launch.cluster import local_search
    eng = state["engine"]
    t = state["traffic"]
    keep_rng = state["keep_rng"]
    kept = []
    per_call = int(t["check_sets_per_call"])
    cap = int(t["check_sets"])
    sets = [0]
    t0 = time.perf_counter()
    t_end = t0 + seconds
    t_last = [t0]

    def scorer(table):
        with rec.span("score"):
            out = eng.service_costs(table)
        now = time.perf_counter()
        q = int(np.shape(table.mu)[0])
        cm = int(np.shape(table.centers)[1])
        sets[0] += q
        rec.record("score_shapes", (q, cm))
        if len(kept) < cap:
            for i in keep_rng.choice(q, min(per_call, q), replace=False):
                kept.append((np.asarray(table.centers[i])[
                    np.asarray(table.cvalid[i])], float(out[i])))
        t_last[0] = now
        if now >= t_end:
            raise _WindowClosed
        return out

    try:
        for k in state["order"]:
            with rec.span("search"):
                local_search(eng, k, rounds=int(t["rounds"]),
                             n_cand=int(t["n_cand"]), tol=float(t["tol"]),
                             scorer=scorer)
    except _WindowClosed:
        pass
    elapsed = t_last[0] - t0
    state["kept"] = kept
    calls = len(rec.records.get("score_shapes", []))
    return {"metrics": {"sets_scored_per_s": sets[0] / elapsed},
            "attempted": calls, "failed": 0}


def collect(state: dict, cell, seed: int) -> dict:
    eng = state["engine"]
    member = np.asarray(eng._sketch.member)
    keys = np.asarray(eng._sketch.keys)[member]
    order = np.argsort(keys)
    out = {"members": keys[order],
           "probs": np.asarray(eng._sketch.probs)[member][order],
           "kept": state["kept"], "points": state["points"]}
    del state["engine"]
    return out


def check(cell, seed: int, out: dict, sides=("program",)) -> dict:
    """The numbers compared, with their limits, for each side: the
    ``program``'s outputs, or the ``control``'s put in their place (the
    reference with every point and centre rounded to bfloat16)."""
    c = cell.config["cluster"]
    limits = cell.config["limits"]
    args = (out["points"], 1 << int(c["fit_chunk_log2"]), int(c["k"]),
            float(c["mu"]), int(c["anchors"]), int(c["hash_seed"]))
    ref = ref_cluster.reference(*args)
    sets = [s for s, _ in out["kept"]]
    want = ref.costs(sets)

    def numbers(vals, members, probs):
        # the members' inclusion probabilities, where the weights'
        # distances lose precision before a score shows it; a key that
        # only one of the two samples holds counts as a gap of 1
        both, i, j = np.intersect1d(members, ref.keys, return_indices=True)
        pgap = (float(np.max(np.abs(np.asarray(probs, np.float64)[i]
                                    - ref.probs[j]) / ref.probs[j]))
                if both.size else 1.0)
        if ref_cluster.mismatch(ref.keys, members):
            pgap = max(pgap, 1.0)
        nums = {"score_gap": float(np.max(np.abs(vals - want) / want))
                if len(sets) else 0.0,
                "prob_gap": pgap}
        return {k: {"value": v, "limit": float(limits[k])}
                for k, v in nums.items()}

    res = {}
    for side in sides:
        if side == "program":
            got = np.array([v for _, v in out["kept"]], np.float64)
            res[side] = numbers(got, out["members"], out["probs"])
        elif side == "control":
            low = ref_cluster.reference(*args, bf16=True)
            res[side] = numbers(low.costs(sets), low.keys, low.probs)
        else:
            raise ValueError(f"no side {side!r}")
    return res

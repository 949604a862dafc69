"""Device-resident segment-query engine (the serving tier).

The sharded build (launch.summary) re-selects the merged sample EAGERLY,
replicated on every device, on every build — wasted work when the summary
is rebuilt often and queried rarely, and the wrong shape for serving where
per-shard sketches trickle in (telemetry collectors, checkpointed slabs,
cross-job merges). This engine is the lazy counterpart, the "precompute a
compact sampled structure once, answer many queries cheaply" pattern of
distance-oracle sampling (arXiv:1203.4903):

  * per-shard ``MultiSketch`` slabs stay RESIDENT on device — absorbing a
    chunk touches only its shard's slab (the jit'd donated streaming fold);
  * the merged slab is maintained AT ABSORB TIME (the default): after the
    shard fold, the post-fold shard slab is delta-folded into the cached
    merged slab in the same donated epoch — the exact arithmetic of the
    lazy ladder's incremental path, run one query early — so queries
    under churn hit an always-fresh cache and pay ZERO merge work;
    exactness is the threshold-closure merge invariant (core.merge
    docstring). When the cache is cold or stale (first query, restore,
    non-monotone mutation) materialization falls back to the PR 5 lazy
    ladder: cache hit -> incremental delta fold of the dirty shards ->
    full stacked re-merge;
  * shard LIFECYCLE bounds a long-running engine's memory: ``gc`` merges
    cold shards (absorb-epoch age / live-count water-marks) into the
    compacted base slab (shard 0), parks the victims on one shared inert
    slab, and truncates trailing dead shards — device residency stays
    O(capacity), not O(epochs). ``spill`` persists victims through the
    checkpoint manager first (evict-to-disk hook);
  * ``query_many`` answers a batch of B segment predicates x |F|
    objectives in ONE fused launch over the merged slab
    (kernels.segquery), with B bucketed to a quantum so jit traces stay
    bounded. Single ``query`` calls route through the same batched path —
    a repeated query is O(1) launches, never a retrace.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.funcs import StatFn
from repro.core.multi_sketch import (MultiSketch, MultiSketchSpec,
                                     multisketch_absorb,
                                     multisketch_absorb_slabs,
                                     multisketch_empty,
                                     multisketch_overflow,
                                     multisketch_query_many, pad_chunk)
from repro.core.predicates import EVERYTHING, SegmentPredicate


def _full_remerge(shards, *, spec, use_kernels):
    """Full re-merge expressed as a stacked delta fold into a fresh empty
    slab — the SAME compiled program family (``_absorb_into_jit``) as the
    incremental and absorb-time folds. Routing every merged-slab producer
    through one program keeps the merged bits identical regardless of
    path: XLA codegens transcendentals (the ppswor ``-expm1(-w*tau)``
    inclusion probability) with shape-dependent last-ulp rounding, so a
    separately jitted ``multisketch_merge_stacked`` at [m, cap] can
    disagree with the [cap]-delta fold by one ulp in ``probs`` even
    though the retained multiset is exact by threshold closure."""
    dk = jnp.stack([s.keys for s in shards])
    dw = jnp.stack([s.weights for s in shards])
    dv = jnp.stack([s.valid for s in shards])
    empty = jax.tree.map(jnp.copy, multisketch_empty(spec))
    return multisketch_absorb_slabs(empty, dk, dw, dv, spec=spec,
                                    use_kernels=use_kernels)


class SegmentQueryEngine:
    """Resident per-shard MultiSketches + lazy merge + batched queries.

    One engine serves every (f, H) query the spec's objectives cover; the
    per-objective CV guarantee (paper Thm 3.1) is the serving SLO.
    """

    def __init__(self, spec: MultiSketchSpec, shards: int = 1,
                 b_quantum: int = 16, chunk: int = 256,
                 use_kernels: Optional[bool] = None,
                 max_delta: Optional[int] = None,
                 absorb_time: bool = True,
                 gc_max_live: Optional[int] = None):
        if shards < 1:
            raise ValueError(f"need >= 1 shard, got {shards}")
        self.spec = spec
        self.b_quantum = int(b_quantum)
        self.chunk = int(chunk)
        self.use_kernels = use_kernels
        # incremental-merge eligibility ceiling: fold at most this many
        # dirty shards into the cached merged slab before a full re-merge
        # is the cheaper rebuild (None -> any strict subset of the shards)
        self.max_delta = max_delta
        # absorb-time merged-slab maintenance: fold each chunk into the
        # cached merged slab in the SAME epoch as its shard fold, so the
        # query path never pays merge work under churn. False reverts to
        # the PR 5 query-time lazy ladder (hit / delta fold / full merge).
        self.absorb_time = bool(absorb_time)
        # auto-GC water-mark: after any mutation that grows the live shard
        # count past this bound, cold shards are merged into the base slab
        # (None -> manual ``gc`` only). Deterministic in the absorb history,
        # so a WAL replay reproduces every auto-GC at the same point.
        self.gc_max_live = (None if gc_max_live is None
                            else max(int(gc_max_live), 1))
        # one shared inert slab backs never-touched and GC'd shards — the
        # donated fold re-points a shard at fresh buffers before its first
        # absorb, so device residency is O(live shards), not O(shards)
        self._empty = multisketch_empty(spec)
        self._shards = [self._empty for _ in range(shards)]
        self._min_shards = shards  # construction layout: never truncated
        self._epoch = 0            # bumped by every state mutation
        self.last_gc_epoch = -1    # epoch of the most recent GC merge
        self._merged: Optional[MultiSketch] = None
        self._merged_epoch = -1    # epoch the cached merged slab reflects
        self._overflow_epoch = -1  # epoch merge_stats["overflow"] reflects
        self._overflow_dev = None  # (epoch, device scalar) pre-dispatched
        # -- dirty-epoch tracking (the incremental-merge contract) --------
        # _shard_epochs[i]: epoch of shard i's last mutation; _merged_base:
        # snapshot of _shard_epochs the cached merged slab reflects (None
        # after a non-monotone mutation — set_shard/load_stacked replace
        # data, so the cached merge no longer covers the residents and the
        # delta fold would be inexact; only a full re-merge recovers).
        self._shard_epochs = [0] * shards
        self._shard_live = [False] * shards  # holds data (host-side gauge)
        self._merged_base: Optional[list] = None
        self._merged_handed_out = False   # `merged` property gave out refs
        # full / incremental / hit / absorb_time / gc_merges counts — the
        # launch-accounting record (tests pin "zero-merge epoch => no
        # query-time fold dispatch") — plus gauges (live_shards,
        # bytes_resident) and the saturation health flag: ``overflow``
        # goes True when a materialized merged slab is FULL, i.e.
        # compaction may have truncated S ∪ Z and the cv guarantee
        # silently degrades; serving tiers surface it per response
        # (launch.pool)
        self.merge_stats = {"full": 0, "incremental": 0, "hit": 0,
                            "absorb_time": 0, "gc_merges": 0,
                            "live_shards": 0, "bytes_resident": 0,
                            "overflow": False}
        self._update_gauges()

    # -- resident state ----------------------------------------------------
    @property
    def num_shards(self) -> int:
        return len(self._shards)

    @property
    def epoch(self) -> int:
        return self._epoch

    def absorb(self, keys, weights, active=None, shard: int = 0):
        """Fold a chunk into one shard's resident slab (donated device
        fold). With ``absorb_time`` (the default) the POST-FOLD shard slab
        is then delta-folded into the cached merged slab in the same epoch
        — the exact computation (same executable, same input slabs, hence
        the same bits) the lazy ladder would run at the next query — so
        the next query is a pure cache hit: zero merge work on the query
        path, bit-identical to the lazy full re-merge by threshold
        closure. NOT the raw chunk: the maintained fold must reproduce the
        query-time delta fold's arithmetic exactly, and folding the
        un-selected chunk runs the re-selection over a different input
        shape (last-ulp transcendental drift in probs). A cold/stale cache
        skips maintenance (the lazy ladder at query time remains the
        fallback and re-seeds it)."""
        if not 0 <= shard < len(self._shards):
            raise IndexError(f"shard {shard} out of range "
                             f"({len(self._shards)} shards)")
        # absorb-time eligibility, judged BEFORE the epoch bump: the cache
        # must be CURRENT (every prior epoch already folded in), seeded
        # from a monotone history, at a non-truncating capacity (where
        # delta == full bit-for-bit — same gate as ``_dirty_shards``)
        maintain = (self.absorb_time and self._merged is not None
                    and self._merged_epoch == self._epoch
                    and self._merged_base is not None
                    and self.spec.cap >= self.spec.default_capacity())
        alias = (self._merged is not None
                 and self._merged is self._shards[shard])
        # single-shard fast path: when the maintained cache ALIASES the
        # target shard, the shard fold IS the merged-slab fold — re-alias
        # after it instead of folding the chunk twice
        realias = maintain and alias
        if self._shards[shard] is self._empty:
            # never-touched / GC'd shards share one inert slab; give the
            # donated fold its own buffers
            self._shards[shard] = jax.tree.map(jnp.copy, self._empty)
        elif alias and (self._merged_handed_out or not realias):
            # a handed-out ``merged`` slab may ALIAS this shard's live
            # state (the single-shard fast path); re-point the shard at
            # fresh buffers first, so the donated fold cannot invalidate
            # the caller's copy
            self._shards[shard] = jax.tree.map(jnp.copy,
                                               self._shards[shard])
        keys, weights, active = pad_chunk(keys, weights, active, self.chunk)
        self._shards[shard] = multisketch_absorb(
            self._shards[shard], keys, weights, active, spec=self.spec,
            use_kernels=self.use_kernels)
        self._epoch += 1
        self._shard_epochs[shard] = self._epoch
        self._shard_live[shard] = True
        if realias:
            self._merged = self._shards[shard]
            self._merged_handed_out = False
            self._stamp_absorb_time()
        elif maintain:
            merged = self._merged
            if (self._merged_handed_out or merged is self._empty
                    or any(merged is s for s in self._shards)):
                # visible outside the engine (caller handle / shared inert
                # slab / shard alias) — the donated fold needs its own
                # buffers
                merged = jax.tree.map(jnp.copy, merged)
                self._merged_handed_out = False
            # the shard's whole slab is the delta (dedup-by-max-weight
            # makes re-folding its older rows a no-op) — the single-dirty-
            # shard delta fold of the lazy ladder, run one query early
            d = self._shards[shard]
            self._merged = multisketch_absorb_slabs(
                merged, d.keys, d.weights, d.valid, spec=self.spec,
                use_kernels=self.use_kernels)
            self._stamp_absorb_time()
        self._maybe_auto_gc()
        self._update_gauges()

    def drain(self) -> None:
        """Block until every async-dispatched device computation behind
        the current state has executed — shard folds, absorb-time merged-
        slab maintenance (including the probs finalize) and the pre-
        dispatched saturation flag. Absorb never blocks; a serving pump
        calls this between requests so no query pays for the previous
        epoch's device backlog on its critical path."""
        pending = [self._shards]
        if self._merged is not None:
            pending.append(self._merged)
        if self._overflow_dev is not None:
            pending.append(self._overflow_dev[1])
        jax.block_until_ready(pending)
        # already blocking on the host: finish the saturation-flag read
        # too, so the epoch's first query skips even that device->host
        # transfer
        if self._merged is not None and self._merged_epoch == self._epoch:
            self._refresh_overflow(self._merged)

    def _stamp_absorb_time(self):
        """The cache reflects THIS epoch (absorb-time maintenance)."""
        self._merged_epoch = self._epoch
        self._merged_base = list(self._shard_epochs)
        self.merge_stats["absorb_time"] += 1
        # dispatch the tiny all(valid) saturation reduction NOW, async —
        # the epoch's first query then reads an already-computed scalar
        # instead of paying a dispatch + device sync on its critical path
        # (the absorb itself still never blocks on it)
        self._overflow_dev = (self._epoch,
                              multisketch_overflow(self._merged))

    def set_shard(self, shard: int, sketch: MultiSketch):
        """Install a prebuilt slab (a collector's state, a checkpointed
        sketch, a slab wired from another job) as one shard's residency.
        The slab is COPIED in: a later absorb on this shard donates the
        resident buffers, and the caller's handle must stay valid.

        Replacing a shard's content is NON-MONOTONE (the old contribution
        may vanish), so the cached merged slab is dropped entirely — the
        next query takes the full re-merge path, never the delta fold."""
        self._shards[shard] = jax.tree.map(jnp.copy, sketch)
        self._epoch += 1
        self._shard_epochs[shard] = self._epoch
        self._shard_live[shard] = True
        self._drop_merged_cache()
        self._update_gauges()

    def shard_slab(self, shard: int) -> MultiSketch:
        """Shard ``shard``'s resident slab, by REFERENCE — the hand-off
        read half: a scale-out rebalance moves a shard between hosts as
        ``target.set_shard(s, source.shard_slab(s))`` (the receiving
        ``set_shard`` copies, so the transfer is a bit-exact snapshot)
        then ``source.clear_shard(s)``. Callers who hold the reference
        past this engine's next mutation of the shard must copy it first:
        a later absorb donates the resident buffers."""
        return self._shards[shard]

    def shard_live(self, shard: int) -> bool:
        """Whether ``shard`` holds data (False: parked on the inert empty
        slab — never absorbed into, GC'd away, or handed off)."""
        return bool(self._shard_live[shard])

    def clear_shard(self, shard: int):
        """Park one shard back on the shared inert slab — the hand-off
        release half (see ``shard_slab``): after the receiving host has
        copied the slab in, the source host drops its residency so the
        shard is owned exactly once across the group. NON-MONOTONE (the
        shard's contribution leaves this engine's union), so the cached
        merged slab is dropped — next query takes the full path."""
        self._shards[shard] = self._empty
        self._epoch += 1
        self._shard_epochs[shard] = self._epoch
        self._shard_live[shard] = False
        self._drop_merged_cache()
        self._update_gauges()

    def add_shard(self, sketch: MultiSketch):
        """Append a prebuilt slab as a NEW shard (copied in, like
        ``set_shard``) — cross-job fan-in: slabs restored from another
        job's checkpoint merge lazily with the resident state. A new shard
        only ADDS data: under ``absorb_time`` a current cache absorbs the
        new slab in this same epoch (the delta fold); otherwise the next
        query folds just the new slab into the cached merge."""
        maintain = (self.absorb_time and self._merged is not None
                    and self._merged_epoch == self._epoch
                    and self._merged_base is not None
                    and self.spec.cap >= self.spec.default_capacity())
        self._shards.append(jax.tree.map(jnp.copy, sketch))
        self._epoch += 1
        self._shard_epochs.append(self._epoch)
        self._shard_live.append(True)
        if maintain:
            merged = self._merged
            if (self._merged_handed_out or merged is self._empty
                    or any(merged is s for s in self._shards)):
                merged = jax.tree.map(jnp.copy, merged)
                self._merged_handed_out = False
            # the new slab is the whole delta; its buffers stay resident
            # (absorb_slabs donates only the state side)
            self._merged = multisketch_absorb_slabs(
                merged, sketch.keys, sketch.weights, sketch.valid,
                spec=self.spec, use_kernels=self.use_kernels)
            self._stamp_absorb_time()
        self._maybe_auto_gc()
        self._update_gauges()

    def load_stacked(self, stacked: MultiSketch):
        """Adopt a stacked batch of per-shard slabs (leaves [m, ...], e.g.
        from ``launch.summary.sharded_multisketch_shards``) as the resident
        state — the merge stays lazy until the first query. Wholesale
        replacement: the merged-slab cache is dropped (full path next) and
        the adopted layout becomes the new un-truncatable base layout.

        The engine is resident on one device: rows that arrive spread
        over a mesh (one per device) are brought to that device first —
        the folds' Pallas kernels cannot be partitioned across devices."""
        stacked = jax.device_put(
            stacked, next(iter(self._empty.keys.devices())))
        m = stacked.keys.shape[0]
        self._shards = [jax.tree.map(lambda x, i=i: x[i], stacked)
                        for i in range(m)]
        self._min_shards = m
        self._epoch += 1
        self._shard_epochs = [self._epoch] * m
        self._shard_live = [True] * m
        self._drop_merged_cache()
        self._update_gauges()

    def _drop_merged_cache(self):
        self._merged = None
        self._merged_epoch = -1
        self._merged_base = None
        self._merged_handed_out = False

    def _update_gauges(self):
        """Host-side residency gauges (no device sync): live shard count
        and device bytes actually resident — shared/aliased buffers (the
        inert slab, the single-shard merged alias) counted once."""
        self.merge_stats["live_shards"] = int(sum(self._shard_live))
        seen: set = set()
        total = 0
        slabs = list(self._shards) + [self._empty]
        if self._merged is not None:
            slabs.append(self._merged)
        for sk in slabs:
            for leaf in sk:
                if id(leaf) not in seen:
                    seen.add(id(leaf))
                    total += int(getattr(leaf, "nbytes", 0))
        self.merge_stats["bytes_resident"] = total

    # -- shard lifecycle (GC / spill) ---------------------------------------
    def _maybe_auto_gc(self):
        if (self.gc_max_live is not None
                and sum(self._shard_live) > self.gc_max_live):
            self.gc(max_live=self.gc_max_live)

    def gc_plan(self, max_live: Optional[int] = None,
                min_age: Optional[int] = None) -> list:
        """Victim shard indices a ``gc`` with these water-marks would merge
        into the base slab, oldest (by last-absorb epoch) first. Pure —
        serving tiers call this to WAL a deterministic victim list before
        applying (``launch.pool``). Defaults to the engine's auto water-mark
        when neither bound is given."""
        if max_live is None and min_age is None:
            max_live = self.gc_max_live
        if len(self._shards) <= 1:
            return []
        cand = sorted((i for i in range(1, len(self._shards))
                       if self._shard_live[i]),
                      key=lambda i: (self._shard_epochs[i], i))
        vict: set = set()
        if min_age is not None:
            vict = {i for i in cand
                    if self._epoch - self._shard_epochs[i] >= int(min_age)}
        if max_live is not None:
            target = max(int(max_live), 1)
            n_live = len(cand) + (1 if self._shard_live[0] else 0)
            for i in cand:                   # age order: evict oldest first
                if n_live - len(vict) <= target:
                    break
                vict.add(i)
        return sorted(vict)

    def gc(self, max_live: Optional[int] = None,
           min_age: Optional[int] = None,
           spill_dir: Optional[str] = None) -> list:
        """Merge cold shards into the compacted base slab (shard 0).

        ``max_live`` bounds the LIVE shard count (oldest evicted first);
        ``min_age`` evicts every shard idle for that many epochs. Victims
        are folded into the base (one delta fold — exact by threshold
        closure at non-truncating capacity, so the union, and every query
        answer, is bit-identical to keeping the shards separate), then
        parked on the shared inert slab; trailing dead shards beyond the
        construction layout are dropped. With ``spill_dir`` the victim
        slabs are first persisted through the checkpoint manager
        (``spill``) so they can be re-adopted later via ``from_checkpoint``
        + ``add_shard``. Returns the victim indices merged."""
        return self.gc_apply(self.gc_plan(max_live, min_age),
                             spill_dir=spill_dir)

    def gc_apply(self, victims, spill_dir: Optional[str] = None) -> list:
        """Apply a GC merge to an explicit victim list (``gc_plan`` output
        or a WAL-replayed directive — serving recovery must reproduce the
        recorded decision, not re-plan it)."""
        victims = sorted({int(i) for i in victims})
        if not victims:
            return []
        if victims[0] < 1 or victims[-1] >= len(self._shards):
            raise ValueError(f"gc victims {victims} out of range "
                             f"(1..{len(self._shards) - 1})")
        # a cache that is current stays current: a GC merge moves data
        # between shards but never changes the union, so the merged slab
        # is re-stamped across the epoch bump instead of invalidated
        cache_current = (self._merged is not None
                         and self._merged_epoch == self._epoch
                         and self._merged_base is not None)
        if spill_dir is not None:
            self.spill(spill_dir, victims)
        base = self._shards[0]
        if base is self._empty or (self._merged is not None
                                   and self._merged is base):
            # the donated base fold must own its buffers
            base = jax.tree.map(jnp.copy, base)
        if len(victims) == 1:
            d = self._shards[victims[0]]
            dk, dw, dv = d.keys, d.weights, d.valid
        else:
            dk = jnp.stack([self._shards[i].keys for i in victims])
            dw = jnp.stack([self._shards[i].weights for i in victims])
            dv = jnp.stack([self._shards[i].valid for i in victims])
        self._shards[0] = multisketch_absorb_slabs(
            base, dk, dw, dv, spec=self.spec, use_kernels=self.use_kernels)
        for i in victims:
            self._shards[i] = self._empty
            self._shard_live[i] = False
        self._epoch += 1
        self._shard_epochs[0] = self._epoch
        self._shard_live[0] = True
        for i in victims:
            self._shard_epochs[i] = self._epoch
        while (len(self._shards) > max(self._min_shards, 1)
               and not self._shard_live[-1]
               and self._shards[-1] is self._empty):
            self._shards.pop()
            self._shard_epochs.pop()
            self._shard_live.pop()
        self.merge_stats["gc_merges"] += 1
        self.last_gc_epoch = self._epoch
        if cache_current:
            self._merged_epoch = self._epoch
            self._merged_base = list(self._shard_epochs)
        # stale caches stay stale: base + victims now read as dirty, and
        # the delta fold stays exact (the base contains the victims' data)
        self._update_gauges()
        return victims

    def spill(self, directory: str, shards) -> int:
        """Persist the given shards' slabs through ckpt.manager (atomic,
        crc'd) — the evict-to-disk hook a GC uses before parking victims.
        The saved step is ``from_checkpoint``-compatible: restoring the
        spill directory rebuilds an engine over exactly the spilled slabs,
        whose merged slab can be re-adopted via ``add_shard``."""
        from repro.ckpt.manager import CheckpointManager
        from repro.core.multi_sketch import spec_to_meta
        shards = [int(i) for i in shards]
        mgr = CheckpointManager(directory)
        step = max(mgr.list_steps(), default=-1) + 1
        mgr.save(step, {"shards": [self._shards[i] for i in shards]},
                 extra_meta={"multisketch_spec": spec_to_meta(self.spec),
                             "num_shards": len(shards),
                             "spilled_from": shards,
                             "spill_epoch": self._epoch})
        return step

    @classmethod
    def from_sharded(cls, spec: MultiSketchSpec, mesh, keys, weights,
                     active=None, axis: str = "data", **kw
                     ) -> "SegmentQueryEngine":
        """Build per-shard slabs over mesh-sharded data (local selection
        only — no replicated merge) and hold them resident."""
        from repro.launch.summary import sharded_multisketch_shards
        stacked = sharded_multisketch_shards(spec, mesh, keys, weights,
                                             active, axis=axis)
        eng = cls(spec, shards=stacked.keys.shape[0], **kw)
        eng.load_stacked(stacked)
        return eng

    # -- checkpointing -----------------------------------------------------
    def save_checkpoint(self, directory: str, step: Optional[int] = None,
                        blocking: bool = True,
                        extra_meta: Optional[dict] = None):
        """Persist the resident per-shard slabs + the spec (as JSON extra
        metadata) through ckpt.manager — atomic, crc-checked, keep-last-k.
        The slabs are plain arrays, so the checkpoint is mesh- and
        job-agnostic: any process restores it with ``from_checkpoint`` and
        merges it with its own state (threshold closure keeps that exact).

        ``step`` defaults to one past the newest existing step — the
        manager treats an already-present step as saved and skips it, so
        re-saving an updated engine must mint a fresh step number.
        ``extra_meta``: caller-owned JSON-able entries merged into the
        stored metadata (e.g. the serving pool's applied WAL sequence) —
        engine keys win on collision.
        """
        from repro.ckpt.manager import CheckpointManager
        from repro.core.multi_sketch import spec_to_meta
        mgr = CheckpointManager(directory)
        if step is None:
            step = max(mgr.list_steps(), default=-1) + 1
        ex = dict(extra_meta or {})
        ex.update({"multisketch_spec": spec_to_meta(self.spec),
                   "num_shards": len(self._shards),
                   "b_quantum": self.b_quantum,
                   "chunk": self.chunk,
                   "max_delta": self.max_delta,
                   "shard_live": [bool(x) for x in self._shard_live],
                   "min_shards": self._min_shards,
                   "gc_max_live": self.gc_max_live,
                   "absorb_time": self.absorb_time})
        mgr.save(step, {"shards": list(self._shards)}, blocking=blocking,
                 extra_meta=ex)
        return mgr

    @classmethod
    def from_checkpoint(cls, directory: str,
                        use_kernels: Optional[bool] = None,
                        return_meta: bool = False):
        """Rebuild an engine from the newest intact checkpoint: the spec
        comes from the stored metadata, the per-shard slabs from the
        crc-verified arrays — BOTH from the SAME step, falling back step by
        step when one is corrupt (a newer save's spec must never be paired
        with an older save's slabs). Queries over the restored engine are
        bit-identical to the saved one's (the slabs ARE the state).

        ``return_meta=True`` -> ``(engine, extra)`` where ``extra`` is the
        restored step's OWN extra-metadata dict — callers recovering
        stateful context (e.g. the pool's applied WAL sequence) need it
        from the step actually restored, not the newest one written."""
        from repro.ckpt.manager import CheckpointManager
        from repro.core.multi_sketch import spec_from_meta
        mgr = CheckpointManager(directory)
        for step in reversed(mgr.list_steps()):
            try:
                _, meta = mgr.read_meta(step)
                ex = meta["extra"]
                spec = spec_from_meta(ex["multisketch_spec"])
                num_shards = int(ex["num_shards"])
            except (FileNotFoundError, KeyError, ValueError, TypeError):
                continue
            template = {"shards": [multisketch_empty(spec)
                                   for _ in range(num_shards)]}
            state = mgr.restore_step(step, template)
            if state is None:
                continue
            md = ex.get("max_delta")
            gml = ex.get("gc_max_live")
            eng = cls(spec, shards=num_shards,
                      b_quantum=int(ex.get("b_quantum", 16)),
                      chunk=int(ex.get("chunk", 256)),
                      use_kernels=use_kernels,
                      max_delta=None if md is None else int(md),
                      absorb_time=bool(ex.get("absorb_time", True)),
                      gc_max_live=None if gml is None else int(gml))
            eng._shards = [MultiSketch(*(jnp.asarray(x) for x in s))
                           for s in state["shards"]]
            eng._epoch += 1
            eng._shard_epochs = [eng._epoch] * num_shards
            live = ex.get("shard_live")
            eng._shard_live = ([bool(x) for x in live]
                               if live is not None and len(live) == num_shards
                               else [True] * num_shards)
            eng._min_shards = int(ex.get("min_shards", num_shards))
            eng._update_gauges()
            return (eng, ex) if return_meta else eng
        raise FileNotFoundError(
            f"no intact checkpoint restorable under {directory}")

    # -- lazy merge-on-demand ----------------------------------------------
    def _dirty_shards(self) -> Optional[list]:
        """Shard indices mutated since the cached merge, or None when the
        cache can't seed an incremental fold (no cache / non-monotone
        history / truncating capacity, where delta != full bit-for-bit)."""
        if (self._merged is None or self._merged_base is None
                or self.spec.cap < self.spec.default_capacity()):
            return None
        base = self._merged_base
        return [i for i in range(len(self._shards))
                if i >= len(base) or self._shard_epochs[i] > base[i]]

    def _incremental_eligible(self, dirty: Optional[list]) -> bool:
        if dirty is None or not dirty:
            return False
        limit = (len(self._shards) - 1 if self.max_delta is None
                 else self.max_delta)
        return len(dirty) <= max(limit, 0)

    def _materialize_merged(self) -> MultiSketch:
        """The merged slab, maintained at most once per epoch: a cache hit,
        an INCREMENTAL delta fold (absorb the dirty shards' slabs into the
        cached merged slab — donated buffers, exact by threshold closure,
        bit-identical to the full path), or the full stacked re-merge."""
        if self._merged_epoch == self._epoch:
            self.merge_stats["hit"] += 1
            return self._refresh_overflow(self._merged)
        dirty = self._dirty_shards()
        if self._incremental_eligible(dirty):
            merged = self._merged
            if self._merged_handed_out or any(
                    merged is s for s in self._shards):
                # the cached slab is visible outside the engine (a caller
                # handle, or the single-shard alias of a live shard) — the
                # delta fold donates its buffers, so re-point at fresh ones
                merged = jax.tree.map(jnp.copy, merged)
                self._merged_handed_out = False
            if len(dirty) == 1:
                d = self._shards[dirty[0]]
                dk, dw, dv = d.keys, d.weights, d.valid
            else:
                # stack only the three leaves the delta fold consumes —
                # probs/seeds/member/aux/taus are recomputed by the
                # re-selection and would be copied just to be discarded
                dk = jnp.stack([self._shards[i].keys for i in dirty])
                dw = jnp.stack([self._shards[i].weights for i in dirty])
                dv = jnp.stack([self._shards[i].valid for i in dirty])
            self._merged = multisketch_absorb_slabs(
                merged, dk, dw, dv, spec=self.spec,
                use_kernels=self.use_kernels)
            self.merge_stats["incremental"] += 1
        elif len(self._shards) == 1:
            self._merged = self._shards[0]
            self.merge_stats["full"] += 1
        else:
            self._merged = _full_remerge(
                self._shards, spec=self.spec,
                use_kernels=self.use_kernels)
            self.merge_stats["full"] += 1
        self._merged_epoch = self._epoch
        self._merged_base = list(self._shard_epochs)
        return self._refresh_overflow(self._merged)

    def _refresh_overflow(self, sk: MultiSketch) -> MultiSketch:
        """Refresh the saturation flag at most once per epoch, at QUERY
        time — ``multisketch_overflow`` syncs the device, and absorb-time
        maintenance must not pay that sync on every fold. Maintained
        epochs pre-dispatched the reduction (``_stamp_absorb_time``), so
        the host read here usually lands on a finished scalar."""
        if self._overflow_epoch != self._epoch:
            pre = self._overflow_dev
            dev = (pre[1] if pre is not None and pre[0] == self._epoch
                   else multisketch_overflow(sk))
            self.merge_stats["overflow"] = bool(dev)
            self._overflow_epoch = self._epoch
        return sk

    @property
    def merged(self) -> MultiSketch:
        """The merged slab, materialized at most once per epoch. The handle
        stays valid across later updates: the next incremental fold donates
        only engine-owned buffers (a handed-out slab is re-pointed first,
        same discipline as ``absorb`` on the single-shard alias)."""
        sk = self._materialize_merged()
        self._merged_handed_out = True
        return sk

    # -- queries -----------------------------------------------------------
    def query_many(self, fs: Optional[Sequence[StatFn]] = None,
                   predicates=EVERYTHING) -> np.ndarray:
        """Q(f_i, H_b) for every objective x predicate -> float [|F|, B].

        ONE fused launch over the merged slab regardless of B and |F|
        (kernels.segquery); B is padded to ``b_quantum`` with never-matching
        predicates so same-bucket batches share one compiled executable.
        """
        fs = (tuple(f for f, _ in self.spec.objectives) if fs is None
              else tuple(fs))
        # internal access: queries read the slab without marking it handed
        # out, so the next delta fold may still donate its buffers
        return multisketch_query_many(self._materialize_merged(), fs,
                                      predicates, b_quantum=self.b_quantum,
                                      use_kernels=self.use_kernels)

    def query(self, f: StatFn, predicate: SegmentPredicate = EVERYTHING
              ) -> float:
        """Single Q(f, H) — routed through the batched path (same compiled
        executable as any 1-query batch of this objective)."""
        return float(self.query_many((f,), predicate)[0, 0])

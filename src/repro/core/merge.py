"""Mergeable fixed-capacity sketches (paper §2.5, §3.3, §5.2 composability).

A ``Sketch`` is the wire/state format of a universal monotone sample: a
fixed-capacity array of (key, weight, u) triples covering S ∪ Z plus validity
bits. Fixed capacity makes sketches jit-compatible and collective-friendly:
merging across shards is an ``all_gather`` + re-selection, and merging across
time (streaming) is a concat + re-selection. Both are EXACT: the paper proves
S∪Z of a union is contained in the union of the parts' S∪Z sets, so
re-running selection on concatenated retained keys reproduces the sample the
union data set would have produced.

u_x comes from the shared hash (core.hashing), so the same key sampled on two
shards carries the same u — the coordination requirement.

The MULTI-OBJECTIVE counterpart lives in core.multi_sketch: ``MultiSketch``
is the fixed-capacity wire format for S^(F) ∪ Z of a multi-objective
bottom-k sample, with static half ``MultiSketchSpec`` (objectives (f, k_f),
scheme, hash seed, capacity). Wire layout: keys/weights/probs/member/aux/
valid slabs [capacity] plus per-objective seeds [|F|, capacity] and taus
[|F|]. Its merge invariants:

  * coordination — all parts hash u_x from the same (key, spec.seed), so
    per-objective samples of a union are unions of per-part samples;
  * threshold closure — each sketch retains in Z the threshold key (the
    arg of tau^(f,k_f)) of EVERY objective, so the union's (k_f+1)-th
    smallest f-seed is always present among the parts' retained keys;
  * max-weight dedup — a key retained by several parts keeps max w_x
    (the paper's weight of a merged data set).

  Under these, re-selection over concatenated retained slabs reproduces
  member set, p^(F) AND taus of the union sample exactly, for any chunking
  (streaming ``multisketch_absorb``) and any shard fan-in (``all_gather`` +
  ``multisketch_merge_stacked``). Capacity sum_f k_f + |F| suffices always.

``sketch_estimate`` below is the single HT-estimate implementation shared
by both formats (they agree on the member/weights/probs/keys fields).

QUERY-ENGINE CONTRACT (launch.query.SegmentQueryEngine + kernels.segquery):
serving reads a sketch through batched segment queries, and the merge
invariants above are exactly what make that correct:

  * a query batch is B predicate rows x |F| objectives evaluated against
    ONE merged slab in ONE kernel launch; each estimate is the same HT sum
    as ``sketch_estimate`` (sum over member slots of f(w)/p restricted to
    the segment), so per-objective CV guarantees (Thm 3.1) apply per row;
  * predicates use the int32 wire format of core.predicates — one row
    [lo, hi, mask, want, salt, flags] meaning
    ``lo <= v <= hi and (v & mask) == want`` with v = key, or
    v = hash31(key, salt) when flags bit 0 (ON_HASH) is set. hash31 is the
    top 31 bits of the shared key hash, so ON_HASH rows select the SAME
    uniform key fraction on every shard/host (coordination);
  * the engine keeps per-shard slabs resident and materializes the merged
    slab lazily, memoized per absorb epoch. Because merging is EXACT (the
    invariants above), a lazily-merged answer is bit-identical to querying
    the eager ``launch.summary.sharded_multisketch`` result, for any
    absorb/merge interleaving;

  INCREMENTAL-MERGE CONTRACT (dirty-epoch semantics). The engine tracks,
  per shard, the epoch of its last mutation and the epoch snapshot its
  cached merged slab reflects. When an epoch's dirty set is a strict
  subset of the shards (bounded by ``max_delta``), the merged slab is
  maintained INCREMENTALLY: the dirty shards' slabs are folded straight
  into the cached merged slab (``multi_sketch.multisketch_absorb_into`` —
  one (1 + |dirty|) x capacity re-selection, cached-slab buffers donated)
  instead of re-running ``merge_stacked`` over all S shards. Exactness is
  the same threshold-closure argument: the cached slab summarizes the
  union U of ALL shards' data at snapshot time, each dirty slab
  summarizes its shard's current data D_i, and absorbs only ADD data, so
  sketch(U ∪ (∪ D_i)) — what the delta fold re-selects — is the sketch
  of the same union data set the full re-merge would summarize:
  BIT-IDENTICAL, asserted across schemes and |F| in the test tier. The
  contract's preconditions, enforced by the engine:
    * monotone history — ``set_shard``/``load_stacked`` REPLACE shard
      content (old keys may vanish from the union), so they drop the
      cache and force the next merge down the full path;
    * non-truncating capacity (>= the spec default) — a truncated
      compaction voids exact merging, so delta and full results could
      legitimately diverge; the engine then always re-merges fully;
    * donated-buffer discipline — the delta fold consumes only
      engine-owned merged-slab buffers; a slab handed out via the public
      ``merged`` property is re-pointed (copied) first, and resident
      shard slabs ride the delta WITHOUT donation.

  ABSORB-TIME MAINTENANCE (zero-merge serving, the engine default).
  The same delta fold can run one query early: after the shard fold of
  an absorb, the POST-FOLD shard slab is folded into the cached merged
  slab in the same donated epoch, so the cache is already current when
  the next query arrives — the query path dispatches ZERO merge work
  (asserted by dispatch-count spies in the test tier and the bench-smoke
  CI gate). Exactness is the incremental contract verbatim (the shard
  slab summarizes a superset of the delta; max-weight dedup makes
  re-folding its older rows a no-op), under the same preconditions:
  maintenance only runs while the cache is current, the history is
  monotone and capacity is non-truncating — any violation falls back to
  the lazy ladder and reseeds maintenance at the next full merge. The
  MERGED SLAB IS AUTHORITATIVE between epochs: queries never consult
  shard slabs directly, so quarantine (rejected NaN/negative rows never
  reach a fold) and the ``overflow`` flag — refreshed at most once per
  epoch at query time, never on the absorb path, which must not pay a
  device sync — both describe the merged slab the answers came from.

  BIT-IDENTITY MECHANISM (``multisketch_finalize``). Value-exactness of
  every path above is the threshold-closure argument; BIT-exactness of
  ``probs`` additionally requires one canonical program for the
  inclusion probability, because XLA codegens transcendentals with
  shape-dependent last-ulp rounding (a [c] delta fold and a [m, c]
  stacked re-merge can disagree by one ulp on the same slab). Every
  host-level producer therefore overwrites probs with the fixed-shape
  spec-keyed finalizer after compaction; in-trace producers
  (``multisketch_absorb_inline``, shard_map interiors) are finalized at
  their host-level boundary (``launch.summary.sharded_multisketch``).

  SHARD LIFECYCLE (GC / evict / spill). Long-running engines bound live
  shard count and device bytes: ``gc`` folds cold shards (oldest
  last-absorb epoch first, under ``max_live``/``min_age`` water-marks)
  into the compacted BASE slab (shard 0) with the same exact delta fold,
  parks victims on a shared inert slab and truncates trailing dead
  shards — the union is unchanged, so a current merged cache is
  re-stamped across the GC epoch, never re-merged, and answers are
  bit-identical to keeping the shards separate. ``gc_plan`` is pure and
  deterministic in the absorb history, so a serving tier can WAL the
  victim list BEFORE applying (apply-then-append, launch.wal GC
  markers): replay reproduces the RECORDED decision and lands in the
  identical post-GC state; a marker lost to a crash merely replays into
  the pre-GC layout, whose merged slab is bit-identical. ``spill``
  persists victim slabs through ckpt.manager first, so evicted shards
  can be re-adopted later (``from_checkpoint`` + ``add_shard``) —
  a long-running ``EnginePool`` stream holds O(capacity) device memory,
  with ``merge_stats`` gauges (live_shards, gc_merges, bytes_resident)
  exposed through pool responses and telemetry.

  * slabs are plain arrays, so CHECKPOINTING is ``ckpt.manager`` over the
    shard list plus the spec stored as JSON extra-metadata
    (``multi_sketch.spec_to_meta``); ``SegmentQueryEngine.from_checkpoint``
    reconstructs the spec first, restores the crc-verified slabs into it,
    and — by the same exactness — a restored-then-merged engine (cross-job
    fan-in via ``add_shard``) answers exactly like a one-shot build over
    the union data set.

SERVICE-COST WIRE FORMAT (core.costs + kernels.servicecost): the metric
domain (paper §7) replaces key predicates with CENTER-SET queries — a
query is (centers [Q, Cmax, dim], cvalid [Q, Cmax], mu, param, mode) rows
where mode selects min-dist^mu clustering cost or the radius-r ball
indicator; center sets are runtime data (an optimizer proposes them), so
the wire format is arrays, not static rows. An all-invalid row estimates
exactly 0 (the Q-bucket padding element). ``core.costs.
service_cost_values`` defines the semantics; the fused kernel evaluates
the identical function in one launch (centers on sublanes, slab slots on
lanes), flat in both Q and Cmax.

CLUSTER-ENGINE CONTRACT (launch.cluster.ClusterEngine): the metric twin of
the query engine. Resident state is a MultiSketch over point keys whose
weights are the anchor-based universal upper-bound probabilities
(core.metric_domains) PLUS a coords slab realigned slot-by-slot after
every donated fold — so the fused service-cost kernel reads coordinates,
probs and member bits from the same resident arrays. Anchor normalizers
freeze at the first chunk (ppswor seeds are only coordinated under a
fixed normalization), every absorb bumps an epoch counter (the external
staleness signal, mirroring the query engine — queries always read the
live slab), and every estimate is the same HT sum as
``sketch_estimate`` with f_C(x) in place of f(w_x) — so per-objective CV
guarantees carry over to every candidate center set the optimizer scores.

SERVING-TIER FAILURE-SEMANTICS CONTRACT (launch.pool.EnginePool): the
multi-tenant serving tier leans on the exactness invariants above to
degrade WITHOUT becoming wrong. Its rules:

  * degradation ladder — every response is labeled FRESH (live merged
    slab, epoch_lag == 0), STALE (answered, but from the last-good merged
    slab and/or with accepted-but-unfolded chunks; ``epoch_lag`` counts
    exactly how many), or REJECTED (admission queue full, deadline
    passed, or no last-good slab to fall back to). There is no fourth
    state: an answer the pool cannot label is an answer it refuses.
  * staleness is never wrongness — a stale merged slab is still an EXACT
    multi-objective sketch of a PREFIX of the stream (merging exactness
    above), so every HT estimate served from it is unbiased for that
    prefix with the full Thm 3.1 per-objective CV guarantee; ``epoch_lag``
    tells the caller which prefix. The ``overflow`` flag rides along so a
    capacity-saturated (guarantee-voiding) slab is always visible.
  * durability & replay exactness — ingest is WAL-appended (crc-framed,
    fsync'd) BEFORE the device fold, and snapshots store the slabs plus
    the applied WAL sequence. Crash recovery = restore newest intact
    snapshot -> replay the WAL tail in sequence order -> BIT-IDENTICAL
    slabs to the uncrashed engine: the fold is deterministic and absorb
    order was fixed by the WAL, so this is the same streaming-exactness
    argument as ``multisketch_absorb``. Corrupt snapshots fall back a
    step (crc catch) and replay a longer tail; torn WAL tails drop only
    the final, unacknowledged record.
  * quarantine — non-finite/negative weights and out-of-range keys are
    masked per ROW (inactive, key -1, weight 0) before the fold, which
    makes them indistinguishable from ``pad_chunk`` padding: the slab is
    bit-identical to one that absorbed only the clean rows, and the
    quarantine count is surfaced per absorb receipt and per stream.

SCALE-OUT CONTRACT (launch.pool.ShardedEnginePool): the multi-host tier
adds machine loss and re-partitioning on top of the ladder above, still
without a fourth answer state. Its rules:

  * cross-host exactness — shards are placed by rendezvous hash over the
    host group; each owner folds its shards locally and a read merges the
    per-host MERGED slabs through the same shared fold family as a
    single-host engine (composability is transitive through intermediate
    merges, paper §3.3, and compaction is deterministic in the retained
    multiset) — so the group answer is BIT-IDENTICAL to a never-sharded
    union engine over the same records, not merely unbiased.
  * REBALANCE markers — a re-partition applies the shard hand-offs
    first, THEN appends one REBALANCE marker (launch.wal, shard == -2)
    whose payload is the FULL new placement: the same apply-then-append
    discipline as GC markers. Replay dispatches markers in sequence
    order, so recovery lands every record on the owner the marker
    recorded; a marker lost or torn by a crash merely recovers the
    PRE-move placement — a different partition of the SAME union, whose
    merged answers are bit-identical (merging exactness above). Dead
    hosts' shards are rebuilt from newest intact checkpoint + full WAL
    tail (GC markers included — GC moves mass across shards, so a
    filtered replay would be wrong).
  * replica promotion — every FRESH answer's merged slab is copied, with
    its applied sequence, to the top-2 rendezvous-ranked live hosts for
    the stream. When an owner dies, reads fall back to the newest
    surviving replica at STALE with ``epoch_lag`` = acks since that
    slab; losing every replica holder is REJECTED, never a guess.
    Accepted-but-unappliable chunks stay WAL-durable in a bounded
    pending backlog (sheds at ``pending_limit``) and fold on rebalance.
    The cluster tier mirrors this with ``ClusterEngine.handoff``: the
    replica carries the FROZEN anchor normalizers, so a promoted
    follower keeps absorbing sample-coordinated with the source, bit
    for bit — re-deriving anchors on promotion would silently decouple
    the samples.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .hashing import uniform01
from .universal import UniversalSample, universal_monotone_sample

_INF = jnp.float32(jnp.inf)


class Sketch(NamedTuple):
    keys: jnp.ndarray     # int32 [c] — key ids (-1 for empty slots)
    weights: jnp.ndarray  # float32 [c]
    probs: jnp.ndarray    # float32 [c] — p(w) for members (0 otherwise)
    member: jnp.ndarray   # bool [c] — in S (vs auxiliary-only in Z)
    valid: jnp.ndarray    # bool [c]
    k: int                # sample-size parameter (static)
    seed: int             # hash seed (static; must match to merge)


def sketch_capacity(n_hint: int, k: int) -> int:
    """Suggested capacity ~ 2 k ln n (Thm 5.1 bound + slack for Z)."""
    import math
    return int(2 * k * max(2.0, math.log(max(n_hint, 4))) + 2 * k)


def build_sketch(keys, weights, active, k: int, capacity: int,
                 seed: int = 0) -> Sketch:
    """Compute S^(M,k) over a batch and compact S ∪ Z into a Sketch."""
    s = universal_monotone_sample(keys, weights, active, k, seed=seed)
    return _compact(keys, weights, s, k, capacity, seed)


def _compact(keys, weights, s: UniversalSample, k: int, capacity: int,
             seed: int) -> Sketch:
    keep = s.member | s.aux
    # order: kept first (members before aux), then by weight desc, then key
    order = jnp.lexsort((jnp.asarray(keys, jnp.int32),
                         -jnp.asarray(weights, jnp.float32), ~s.member, ~keep))
    n = order.shape[0]
    if n < capacity:  # pad so every sketch carries exactly `capacity` slots
        order = jnp.concatenate([order, jnp.zeros(capacity - n, order.dtype)])
        pad_valid = jnp.arange(capacity) < n
    else:
        order = order[:capacity]
        pad_valid = jnp.ones((capacity,), bool)
    take = order
    kk = jnp.asarray(keys, jnp.int32)[take]
    keep_t = keep[take] & pad_valid
    return Sketch(
        keys=jnp.where(keep_t, kk, -1),
        weights=jnp.where(keep_t, jnp.asarray(weights, jnp.float32)[take],
                          0.0),
        probs=jnp.where(keep_t, s.prob[take], 0.0),
        member=s.member[take] & keep_t,
        valid=keep_t,
        k=k, seed=seed)


def _merge_core(ak, aw, av, bk, bw, bv, *, k, capacity, seed):
    s = _rebuild(jnp.concatenate([ak, bk]), jnp.concatenate([aw, bw]),
                 jnp.concatenate([av, bv]), k, capacity, seed)
    return s.keys, s.weights, s.probs, s.member, s.valid


_merge_jit = partial(jax.jit, static_argnames=("k", "capacity", "seed"))(
    _merge_core)
# the donated variant reuses both input slabs' buffers for the result —
# for fold-style callers (state <- merge(state, new)) that never touch the
# inputs again; re-using a donated slab is an error by design
_merge_jit_donated = partial(jax.jit,
                             static_argnames=("k", "capacity", "seed"),
                             donate_argnums=(0, 1, 2, 3, 4, 5))(_merge_core)


def merge_sketches(a: Sketch, b: Sketch, donate: bool = False) -> Sketch:
    """Merge two sketches (same k/seed): concat, dedup (keep max weight),
    re-select. Exact per paper §5.2.

    jit-cached per (k, capacity, seed, shapes) — repeated merges under one
    spec reuse a single compiled executable. ``donate=True`` additionally
    donates BOTH input slabs' device buffers to the output (zero
    steady-state allocation for streaming folds); the inputs must not be
    used afterwards.
    """
    assert a.k == b.k and a.seed == b.seed, "sketches must share k and hash seed"
    fn = _merge_jit_donated if donate else _merge_jit
    keys, weights, probs, member, valid = fn(
        a.keys, a.weights, a.valid, b.keys, b.weights, b.valid,
        k=a.k, capacity=a.keys.shape[0], seed=a.seed)
    return Sketch(keys=keys, weights=weights, probs=probs, member=member,
                  valid=valid, k=a.k, seed=a.seed)


def merge_many(sketches_keys, sketches_weights, sketches_valid, k: int,
               capacity: int, seed: int) -> Sketch:
    """Merge a stacked batch of sketches [m, c] -> one sketch (tree-free,
    single re-selection). Used after all_gather over the mesh."""
    return _rebuild(sketches_keys.reshape(-1), sketches_weights.reshape(-1),
                    sketches_valid.reshape(-1), k, capacity, seed)


def _rebuild(keys, weights, valid, k: int, capacity: int, seed: int) -> Sketch:
    # dedup by key keeping max weight (paper: w_x = max over elements)
    order = jnp.lexsort((-weights, keys))
    sk, sw, sv = keys[order], weights[order], valid[order]
    dup = jnp.concatenate([jnp.zeros((1,), bool), sk[1:] == sk[:-1]])
    act = sv & ~dup & (sk >= 0)
    s = universal_monotone_sample(sk, sw, act, k, seed=seed)
    return _compact(sk, sw, s, k, capacity, seed)


def sketch_estimate(sk, f, segment_fn=None) -> jnp.ndarray:
    """HT estimate of Q(f, H) from a sketch (``Sketch`` or ``MultiSketch`` —
    any record with member/weights/probs/keys fields).

    segment_fn: optional vectorized predicate over keys selecting the
    segment H (default: the whole data set).
    """
    member = sk.member
    if segment_fn is not None:
        member = member & jnp.asarray(segment_fn(sk.keys), bool)
    contrib = jnp.where(member,
                        f(sk.weights) / jnp.maximum(sk.probs, 1e-30), 0.0)
    return jnp.sum(contrib)

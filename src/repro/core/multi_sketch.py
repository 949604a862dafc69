"""MultiSketch: the mergeable fixed-capacity multi-objective summary.

This is the device-resident state + wire format for S^(F) ∪ Z of a
multi-objective bottom-k sample (paper §3.2/§3.3), replacing the ephemeral
per-call ``MultiBottomK`` wherever a sample must survive across batches
(streaming), across shards (``all_gather``) or across hosts (telemetry).

Wire format — a pytree of arrays with static half ``MultiSketchSpec``:

  keys    int32   [c]      key ids, -1 on empty slots
  weights float32 [c]      w_x (merged data sets: max over occurrences)
  probs   float32 [c]      p_x^(F) = max_f p_x^(f) for members, else 0
  seeds   float32 [nf, c]  per-objective f-seeds r_x / f(w_x) (+inf invalid)
  member  bool    [c]      x ∈ S^(F)
  aux     bool    [c]      x ∈ Z (per-objective threshold keys, see below)
  valid   bool    [c]      slot occupied
  taus    float32 [nf]     tau^(f,k_f): the (k_f+1)-th smallest f-seed

  spec (static, hashable, jit-static): objectives ((StatFn, k_f), ...),
  scheme ('ppswor' | 'priority'), hash seed, capacity.

Merge invariant (paper §3.3 composability): because every per-objective
sample shares u_x = hash(key, seed), S^(f,k_f) of a union of data sets is
contained in the union of the parts' S^(f,k_f); and the union's threshold
key (the arg of tau^(f)) has per-part seed rank <= k_f + 1, so it is a part
member OR a part threshold key. We therefore retain in Z the threshold key
of EVERY objective (<= |F| slots — a superset of the paper's
estimation-only Z, which keeps only thresholds of some member's most
forgiving objective). With that, re-running selection on the concatenated
retained keys of any parts reproduces the member set, probabilities AND
thresholds of the sample the union data set would have produced — exactly.
Hence ``absorb`` (streaming fold), ``merge`` and ``merge_stacked``
(post-all_gather) are all the same re-selection and agree with a one-shot
build over the concatenated data for any chunking and any order.

Capacity: |S^(F)| <= sum_f k_f (hard, each S^(f) holds k_f keys) and
|Z| <= |F|, so the default capacity sum_f k_f + |F| + 1 never truncates; a
truncated compaction drops lowest-weight aux slots first and voids the
exactness guarantee (detectable: multisketch_overflow()).

Selection reuses the PR 1 single-launch batched kernels
(fused_seeds_fvals + batched block-select) when ``use_kernels`` — the
default on the host-facing entry points; inside shard_map/manual-collective
regions callers pass use_kernels=False and get the identical pure-XLA path
(one stacked top_k), bit-compatible with the kernels.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .bottomk import (bottomk_members, bottomk_members_any_order,
                      conditional_prob, f_seed)
from .funcs import StatFn
from .hashing import uniform01

_INF = jnp.float32(jnp.inf)

# StatFn kind -> seeds-kernel objective code (kernels/seeds.py)
_KERNEL_KIND = {"sum": 0, "count": 1, "thresh": 2, "cap": 3, "moment": 4}


@dataclasses.dataclass(frozen=True)
class MultiSketchSpec:
    """Static half of a MultiSketch (hashable -> usable as jit-static arg).

    Two sketches are mergeable iff their specs are equal: same objectives
    (f, k_f) in the same order, same scheme, same hash seed.
    """

    objectives: Tuple[Tuple[StatFn, int], ...]
    scheme: str = "ppswor"
    seed: int = 0
    capacity: int = 0  # 0 -> default_capacity()

    def __post_init__(self):
        if self.scheme not in ("priority", "ppswor"):
            raise ValueError(
                f"unknown scheme {self.scheme!r} (want 'priority' or 'ppswor')")
        object.__setattr__(self, "objectives",
                           tuple((f, int(k)) for f, k in self.objectives))

    @property
    def nf(self) -> int:
        return len(self.objectives)

    @property
    def kmax(self) -> int:
        return max(k for _, k in self.objectives)

    def default_capacity(self) -> int:
        """sum_f k_f + |F| is a HARD bound on |S^(F) ∪ Z|, so this never
        truncates; the +1 spare slot keeps ``multisketch_overflow`` (slab
        full => possible truncation) False whenever exactness holds."""
        return sum(k for _, k in self.objectives) + self.nf + 1

    @property
    def cap(self) -> int:
        return self.capacity if self.capacity > 0 else self.default_capacity()

    def kernel_objectives(self) -> Optional[Tuple[Tuple[int, float], ...]]:
        """(kind, param) encoding for the fused seeds kernel; None if any
        objective (e.g. combo) has no kernel encoding."""
        enc = []
        for f, _ in self.objectives:
            kind = _KERNEL_KIND.get(f.kind)
            if kind is None:
                return None
            enc.append((kind, float(f.param)))
        return tuple(enc)


class MultiSketch(NamedTuple):
    """Array half of the summary — a plain pytree: jit/donate/collective
    friendly. See module docstring for the wire format."""

    keys: jnp.ndarray     # int32 [c]
    weights: jnp.ndarray  # float32 [c]
    probs: jnp.ndarray    # float32 [c]
    seeds: jnp.ndarray    # float32 [nf, c]
    member: jnp.ndarray   # bool [c]
    aux: jnp.ndarray      # bool [c]
    valid: jnp.ndarray    # bool [c]
    taus: jnp.ndarray     # float32 [nf]


def multisketch_empty(spec: MultiSketchSpec) -> MultiSketch:
    """The identity element of ``merge``/``absorb``."""
    c, nf = spec.cap, spec.nf
    return MultiSketch(
        keys=jnp.full((c,), -1, jnp.int32),
        weights=jnp.zeros((c,), jnp.float32),
        probs=jnp.zeros((c,), jnp.float32),
        seeds=jnp.full((nf, c), _INF, jnp.float32),
        member=jnp.zeros((c,), bool),
        aux=jnp.zeros((c,), bool),
        valid=jnp.zeros((c,), bool),
        taus=jnp.full((nf,), _INF, jnp.float32))


def multisketch_slab_bytes(spec: MultiSketchSpec) -> int:
    """Static wire/device size of ONE slab in bytes — keys/weights/probs
    (3 x 4c) + seeds (4 x nf x c) + member/aux/valid (3 x c) + taus
    (4 x nf). The unit of every bytes-moved model over folds and of the
    engine's ``bytes_resident`` gauge."""
    c, nf = spec.cap, spec.nf
    return c * (15 + 4 * nf) + 4 * nf


# ---------------------------------------------------------------------------
# selection (member/prob/aux/taus over a fixed-shape batch)
# ---------------------------------------------------------------------------

def multisketch_select(spec: MultiSketchSpec, keys, weights, active,
                       use_kernels: bool = False, seed=None):
    """Multi-objective bottom-k selection with the MERGEABLE aux set.

    Returns (member [n], prob [n] = p^(F), aux [n], seeds [nf, n],
    taus [nf]). Membership and tau are ``bottomk.bottomk_members``: the
    f-members are the k_f active keys first in (f-seed, key) order, and
    aux holds the (k_f+1)-th, the threshold key, of EVERY objective (a
    merge-sufficient superset of the estimation-minimal Z of
    core.multi_objective.multi_bottomk_sample; member and prob are the
    same). ``keys`` must come in ascending order, as ``_rebuild`` sorts
    them: the selection ranks equal seeds by position. ``seed`` (runtime
    override, may be traced) defaults to the static spec.seed.
    """
    keys = jnp.asarray(keys, jnp.int32)
    w = jnp.asarray(weights, jnp.float32)
    act = jnp.asarray(active, bool)
    n = keys.shape[0]
    ks = tuple(kf for _, kf in spec.objectives)
    seed = spec.seed if seed is None else seed

    enc = spec.kernel_objectives()
    cand = None
    # the seeds kernel bakes the seed in as a compile-time constant; traced
    # seeds (e.g. per-step reseeding inside a jitted exchange) take the
    # XLA path, which accepts them at runtime.
    if use_kernels and enc is not None and isinstance(seed, (int,)):
        from repro.kernels.blockselect import batched_bottomk_select
        from repro.kernels.seeds import fused_seeds_fvals
        seeds, fvals = fused_seeds_fvals(keys, w, act, enc, spec.scheme,
                                         int(seed))
        cand = batched_bottomk_select(seeds, max(ks) + 1)[:2]
    else:
        u = uniform01(keys, seed)
        seeds = jnp.stack([f_seed(w, act, f, u, spec.scheme)
                           for f, _ in spec.objectives])
        fvals = jnp.stack([jnp.where(act, f(w), 0.0)
                           for f, _ in spec.objectives])

    member_f, taus, thr = bottomk_members(seeds, keys, ks, cand)
    p_f = jnp.where(member_f,
                    conditional_prob(fvals, taus[:, None], spec.scheme), 0.0)
    member = member_f.any(axis=0)
    prob = jnp.where(member, p_f.max(axis=0), 0.0)
    aux = (jnp.zeros((n,), bool).at[jnp.where(thr >= 0, thr, n)]
           .set(True, mode="drop") & ~member)
    return member, prob, aux, seeds, taus


def _compact(spec: MultiSketchSpec, keys, weights, member, prob, aux, seeds,
             taus, use_kernels: bool) -> MultiSketch:
    """Compact S^(F) ∪ Z into the fixed-capacity slab: members by weight
    desc, then aux by weight desc, equal priorities by key. ``keys`` must
    come in ascending order and be deduped: both takes rank equal
    priorities by position."""
    c = spec.cap
    keep = member | aux
    if use_kernels:
        from repro.kernels.compact import compact_take
        take, tvalid = compact_take(keys, weights, member, keep, c)
    else:
        w = jnp.maximum(jnp.asarray(weights, jnp.float32), 0.0)
        inv = 1.0 / (1.0 + w)
        pri = jnp.where(keep & (keys >= 0),
                        jnp.where(member, inv, 2.0 + inv), _INF)
        n = pri.shape[0]
        if n < c:
            pri = jnp.pad(pri, (0, c - n), constant_values=jnp.inf)
        neg, take = jax.lax.top_k(-pri, c)
        tvalid = jnp.isfinite(-neg) & (take < n)
        take = jnp.where(tvalid, take, 0).astype(jnp.int32)
    tk = jnp.where(tvalid, take, 0)
    return MultiSketch(
        keys=jnp.where(tvalid, jnp.asarray(keys, jnp.int32)[tk], -1),
        weights=jnp.where(tvalid, jnp.asarray(weights, jnp.float32)[tk], 0.0),
        probs=jnp.where(tvalid, prob[tk], 0.0),
        seeds=jnp.where(tvalid[None, :], seeds[:, tk], _INF),
        member=member[tk] & tvalid,
        aux=aux[tk] & tvalid,
        valid=tvalid,
        taus=taus)


def _rebuild(spec: MultiSketchSpec, keys, weights, valid,
             use_kernels: bool, seed=None) -> MultiSketch:
    """Sort by key, dedup (keep max weight — the paper's w_x for merged
    data sets), select, compact: the one core of build, absorb and merge,
    so that each sees its entries in the same order."""
    keys = jnp.asarray(keys, jnp.int32)
    w = jnp.asarray(weights, jnp.float32)
    # sort (key asc, VALID first, weight desc): each key's first occurrence
    # is its max-weight valid one, so the dup mask can never let an invalid
    # slot shadow a real observation of the same key. (valid, weight) ride
    # ONE int32 column — the IEEE bits of a non-negative float are
    # monotone, NaN goes after every number, invalid rows after all valid
    # ones — so this is a 2-key sort: on a TPU a 3-key sort of a 2^20-row
    # fold compiles about twice as long. Rows the column ties (weights
    # <= 0, invalid rows) can never be selected, so their order is free.
    # The where (not a max, which may keep -0.0) zeroes every weight
    # <= 0 to +0.0: -0.0's sign bit would wrap the int32 subtraction.
    valid = jnp.asarray(valid, bool)
    wbits = jax.lax.bitcast_convert_type(jnp.where(w > 0, w, 0.0), jnp.int32)
    rank = jnp.where(jnp.isnan(w), 0x7F800001, 0x7F800000 - wbits)
    rank = jnp.where(valid, rank, 0x7FFFFFFF)
    order = jax.lax.sort(
        (keys, rank, jax.lax.iota(jnp.int32, keys.shape[0])),
        num_keys=2, is_stable=True)[2]
    sk, sw = keys[order], w[order]
    sv = valid[order]
    dup = jnp.concatenate([jnp.zeros((1,), bool), sk[1:] == sk[:-1]])
    act = sv & ~dup & (sk >= 0)
    member, prob, aux, seeds, taus = multisketch_select(
        spec, sk, sw, act, use_kernels=use_kernels, seed=seed)
    return _compact(spec, sk, sw, member, prob, aux, seeds, taus,
                    use_kernels)


# ---------------------------------------------------------------------------
# probs finalizer: one canonical program for the inclusion probability
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("spec",))
def _finalize_probs_jit(keys, weights, seeds, member, valid, taus, *, spec):
    """Recompute p^(F) from the compacted slab in ONE fixed-shape program.

    The retained multiset (keys/weights/seeds/member/taus) of any merge
    path is exact by threshold closure, but ``probs`` passes through a
    transcendental (the ppswor ``-expm1(-f(w)*tau)``), and XLA codegens
    transcendentals with shape-dependent last-ulp rounding — two
    differently-shaped fold programs (a [c] delta fold vs a [m, c]
    stacked re-merge) can disagree by one ulp on the same slab. Host
    entry points therefore overwrite probs with this [c]-shaped program,
    keyed only by spec: identical slabs get identical prob bits no
    matter which fold produced them.

    Per-objective membership is ``bottomk.bottomk_members`` over the
    slab's valid entries. Every f-member and every threshold key is
    retained, so the rule gives the selection's members and taus; the
    slab holds its slots in retention order, so the rule sorts them by
    key first.
    """
    fvals = jnp.stack([jnp.where(valid, f(weights), 0.0)
                       for f, _ in spec.objectives])
    member_f, _, _ = bottomk_members_any_order(
        jnp.where(valid[None, :], seeds, _INF), keys,
        tuple(k for _, k in spec.objectives))
    p_f = jnp.where(member_f,
                    conditional_prob(fvals, taus[:, None], spec.scheme), 0.0)
    return jnp.where(member, p_f.max(axis=0), 0.0)


def multisketch_finalize(sk: MultiSketch, *,
                         spec: MultiSketchSpec) -> MultiSketch:
    """Canonicalize ``sk.probs`` (see ``_finalize_probs_jit``). Idempotent;
    every host-level producer in this module applies it on return, so
    slabs with equal retained state compare bit-equal in all 8 fields."""
    return sk._replace(probs=_finalize_probs_jit(
        sk.keys, sk.weights, sk.seeds, sk.member, sk.valid, sk.taus,
        spec=spec))


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("spec", "use_kernels"))
def _build_jit(keys, weights, active, *, spec, use_kernels):
    return _rebuild(spec, keys, weights, active, use_kernels)


@partial(jax.jit, static_argnames=("spec", "use_kernels"))
def _build_seeded_jit(keys, weights, active, seed, *, spec, use_kernels):
    return _rebuild(spec, keys, weights, active, use_kernels, seed=seed)


def multisketch_build(spec: MultiSketchSpec, keys, weights, active=None,
                      use_kernels: Optional[bool] = None,
                      seed=None) -> MultiSketch:
    """One-shot S^(F) ∪ Z over a batch, compacted to the wire format.

    The same core as ``absorb`` and ``merge`` (``_rebuild``): the batch is
    sorted by key, so the build equals a fold of the same keys into an
    empty sketch, bit for bit. A key repeated in one batch is one key, of
    its largest weight (the paper's w_x for merged data sets), as it is
    across batches.

    ``seed``: optional RUNTIME hash-seed override (a traced int32 is fine)
    — many-seed callers (replication studies, the metric-domain sampler)
    share ONE compiled executable instead of retracing per spec.seed. The
    seeded path always uses the XLA selection (the kernels bake the seed
    in at compile time).
    """
    keys = jnp.asarray(keys, jnp.int32)
    weights = jnp.asarray(weights, jnp.float32)
    active = (jnp.ones(keys.shape, bool) if active is None
              else jnp.asarray(active, bool))
    if seed is not None:
        return multisketch_finalize(
            _build_seeded_jit(keys, weights, active,
                              jnp.asarray(seed, jnp.int32),
                              spec=spec, use_kernels=False), spec=spec)
    return multisketch_finalize(
        _build_jit(keys, weights, active, spec=spec,
                   use_kernels=True if use_kernels is None else use_kernels),
        spec=spec)


def multisketch_absorb_inline(spec: MultiSketchSpec, state: MultiSketch,
                              keys, weights, active=None,
                              use_kernels: bool = False) -> MultiSketch:
    """Pure (un-jitted) fold body: state <- state ∪ chunk.

    For callers that are ALREADY inside a jit trace (a train step folding
    telemetry, a shard_map exchange) — fuses into the enclosing program.
    Host callers want :func:`multisketch_absorb` (jitted, donated buffers).
    """
    keys = jnp.asarray(keys, jnp.int32).reshape(-1)
    weights = jnp.asarray(weights, jnp.float32).reshape(-1)
    active = (jnp.ones(keys.shape, bool) if active is None
              else jnp.asarray(active, bool).reshape(-1))
    ck = jnp.concatenate([state.keys, keys])
    cw = jnp.concatenate([state.weights, weights])
    cv = jnp.concatenate([state.valid, active])
    return _rebuild(spec, ck, cw, cv, use_kernels)


@partial(jax.jit, static_argnames=("spec", "use_kernels"),
         donate_argnums=(0,))
def _absorb_jit(state, keys, weights, active, *, spec, use_kernels):
    return multisketch_absorb_inline(spec, state, keys, weights, active,
                                     use_kernels)


def multisketch_absorb(state: MultiSketch, keys, weights, active=None, *,
                       spec: MultiSketchSpec,
                       use_kernels: Optional[bool] = None) -> MultiSketch:
    """Device-resident streaming fold: state <- state ∪ chunk.

    jit-compiled per (spec, chunk shape) with the STATE BUFFERS DONATED —
    the returned sketch reuses the old state's memory, so a training loop
    folds telemetry with zero host round-trips and zero steady-state
    allocation. The old ``state`` must not be used again.
    """
    keys = jnp.asarray(keys, jnp.int32).reshape(-1)
    return multisketch_finalize(_absorb_jit(
        state, keys, jnp.asarray(weights, jnp.float32).reshape(-1),
        (jnp.ones(keys.shape, bool) if active is None
         else jnp.asarray(active, bool).reshape(-1)),
        spec=spec, use_kernels=True if use_kernels is None else use_kernels),
        spec=spec)


@partial(jax.jit, static_argnames=("spec", "use_kernels"),
         donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
def _absorb_into_jit(skeys, sweights, sprobs, sseeds, smember, saux, svalid,
                     staus, dkeys, dweights, dvalid, *, spec, use_kernels):
    """The delta fold body: flat state leaves (all donated — the incremental
    merge reuses the cached merged slab's buffers) + the delta's
    keys/weights/valid only (seeds/probs are recomputed by re-selection, so
    the delta slabs' other leaves never leave the device's resident state)."""
    del sprobs, sseeds, smember, saux, staus  # donated, recomputed
    return _rebuild(spec,
                    jnp.concatenate([skeys, dkeys]),
                    jnp.concatenate([sweights, dweights]),
                    jnp.concatenate([svalid, dvalid]), use_kernels)


def delta_slab_pad(keys, weights, valid, cap: int, m_quantum: int = 1):
    """Pad a flattened delta (m slabs x cap slots) with inert slots (key -1,
    weight 0, invalid) so the slab count reaches the next power-of-two
    multiple of ``m_quantum`` — incremental merges with 1, 2, 3.. dirty
    shards then share O(log m) compiled executables instead of one per m."""
    m = -(-keys.shape[0] // cap)
    mq = max(m_quantum, 1)
    while mq < m:
        mq *= 2
    pad = mq * cap - keys.shape[0]
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), -1, jnp.int32)])
        weights = jnp.concatenate([weights, jnp.zeros((pad,), jnp.float32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    return keys, weights, valid


def multisketch_absorb_into(state: MultiSketch, delta: MultiSketch, *,
                            spec: MultiSketchSpec,
                            use_kernels: Optional[bool] = None,
                            pad_deltas: bool = True) -> MultiSketch:
    """Delta-aware incremental merge: state <- state ∪ delta, IN PLACE.

    ``state`` is an already-merged slab (e.g. a query engine's cached
    merged slab) whose buffers are DONATED — the result reuses its memory,
    and the old handle must not be used again. ``delta`` is one sketch or a
    stacked batch (leaves [m, c]) of sketches under the same spec — the
    dirty shards of an absorb epoch; its buffers are NOT donated (shard
    slabs stay resident).

    Exactness (core.merge docstring): ``state`` summarizes the union data
    set U and each delta slab summarizes some D_i, so re-selection over the
    concatenated retained keys reproduces the sketch of U ∪ (∪ D_i) —
    bit-identical to a full re-merge over ALL shards whenever U covers
    every non-dirty shard's data, i.e. after any sequence of absorbs
    (monotone additions). Replacing a shard's content wholesale
    (``set_shard``/``load_stacked``) voids that containment; callers must
    take the full-merge path there.

    ``use_kernels=None`` resolves to the backend default — the fused
    kernel chain on a real accelerator, the bit-compatible XLA selection
    when kernels would run under the Pallas interpreter (slab-scale
    rebuilds are latency-bound; the interpreted chain is ~15x slower than
    its XLA twin while producing identical bits).
    """
    return multisketch_absorb_slabs(state, delta.keys, delta.weights,
                                    delta.valid, spec=spec,
                                    use_kernels=use_kernels,
                                    pad_deltas=pad_deltas)


def multisketch_absorb_slabs(state: MultiSketch, delta_keys, delta_weights,
                             delta_valid, *, spec: MultiSketchSpec,
                             use_kernels: Optional[bool] = None,
                             pad_deltas: bool = True) -> MultiSketch:
    """`multisketch_absorb_into` taking the delta's three CONSUMED leaves
    directly ([c] or [m, c]) — re-selection recomputes probs/seeds/taus,
    so callers holding whole sketches (the engine's dirty shards) need
    not stack the other five leaves just to have them discarded."""
    if use_kernels is None:
        from repro.kernels._util import default_interpret
        use_kernels = not default_interpret()
    # the hot path (one dirty shard, resident slab leaves) must not pay
    # per-op dispatch: reshape/convert only when the delta is stacked or
    # host-side, and padding is a no-op at an exact power-of-two count
    dk, dw, dv = delta_keys, delta_weights, delta_valid
    if getattr(dk, "ndim", None) != 1:
        dk = jnp.asarray(dk, jnp.int32).reshape(-1)
        dw = jnp.asarray(dw, jnp.float32).reshape(-1)
        dv = jnp.asarray(dv, bool).reshape(-1)
    if pad_deltas and dk.shape[0] != spec.cap:
        dk, dw, dv = delta_slab_pad(dk, dw, dv, spec.cap)
    return multisketch_finalize(
        _absorb_into_jit(state.keys, state.weights, state.probs,
                         state.seeds, state.member, state.aux,
                         state.valid, state.taus, dk, dw, dv,
                         spec=spec, use_kernels=use_kernels), spec=spec)


@partial(jax.jit, static_argnames=("spec", "use_kernels"))
def _merge_jit(a, b, *, spec, use_kernels):
    return _rebuild(spec,
                    jnp.concatenate([a.keys, b.keys]),
                    jnp.concatenate([a.weights, b.weights]),
                    jnp.concatenate([a.valid, b.valid]), use_kernels)


def multisketch_merge(spec: MultiSketchSpec, a: MultiSketch, b: MultiSketch,
                      use_kernels: Optional[bool] = None) -> MultiSketch:
    """Exact merge of two sketches built under the same spec."""
    return multisketch_finalize(_merge_jit(
        a, b, spec=spec,
        use_kernels=True if use_kernels is None else use_kernels), spec=spec)


def multisketch_merge_stacked(spec: MultiSketchSpec, stacked: MultiSketch,
                              use_kernels: bool = False) -> MultiSketch:
    """Merge a stacked batch of sketches (leaves have a leading [m] axis,
    e.g. straight out of ``all_gather``) in ONE re-selection — no tree
    reduction. Works inside shard_map (default use_kernels=False; the
    finalize inlines into the enclosing trace there — in-trace callers
    that need canonical prob bits re-finalize the host-level result, as
    ``launch.summary.sharded_multisketch`` does)."""
    return multisketch_finalize(
        _rebuild(spec, stacked.keys.reshape(-1),
                 stacked.weights.reshape(-1), stacked.valid.reshape(-1),
                 use_kernels), spec=spec)


def pad_chunk(keys, weights, active=None, chunk: int = 256):
    """Pad a host chunk of keyed observations to the ``chunk`` quantum
    (keys -1, weights 0, inactive) so the absorb fold's jit traces stay
    bounded. ``active`` defaults to weights > 0. Shared by every host
    collector fronting :func:`multisketch_absorb`."""
    import numpy as np
    keys = np.asarray(keys, np.int32).reshape(-1)
    weights = np.asarray(weights, np.float32).reshape(-1)
    active = (weights > 0 if active is None
              else np.asarray(active, bool).reshape(-1))
    n = keys.shape[0]
    npad = max(chunk, -(-n // chunk) * chunk)
    if npad > n:
        keys = np.pad(keys, (0, npad - n), constant_values=-1)
        weights = np.pad(weights, (0, npad - n))
        active = np.pad(active, (0, npad - n))
    return keys, weights, active


def quarantine_chunk(keys, weights, active=None):
    """Per-ROW input quarantine for absorb paths facing untrusted producers.

    A malformed row — NaN/inf/negative weight, NaN/inf/negative or
    out-of-int32-range key — is rejected individually (marked inactive,
    weight zeroed, key set to -1) instead of poisoning or dropping the
    whole chunk: the surviving rows fold exactly as if the producer had
    never sent the bad ones (an inactive slot is indistinguishable from
    ``pad_chunk`` padding, so the resulting slab is bit-identical to
    absorbing only the clean rows at the same chunk quantum).

    Returns ``(keys int32, weights float32, active bool, n_quarantined)``
    where ``n_quarantined`` counts rows that were active on entry but
    rejected here — the per-stream poison-producer health signal
    (``EnginePool`` accumulates it per tenant).
    """
    import numpy as np
    kf = np.asarray(keys).reshape(-1).astype(np.float64)
    wf = np.asarray(weights).reshape(-1).astype(np.float64)
    act = (np.ones(kf.shape, bool) if active is None
           else np.asarray(active, bool).reshape(-1))
    bad_w = ~np.isfinite(wf) | (wf < 0.0)
    bad_k = (~np.isfinite(kf) | (kf < 0.0)
             | (kf > float(np.iinfo(np.int32).max)))
    bad = bad_w | bad_k
    n_quarantined = int(np.count_nonzero(bad & act))
    out_k = np.where(bad, -1.0, kf).astype(np.int32)
    out_w = np.where(bad, 0.0, wf).astype(np.float32)
    return out_k, out_w, act & ~bad, n_quarantined


def statfn_to_meta(f: StatFn) -> dict:
    """JSON-able encoding of a StatFn (combo recurses)."""
    d = {"kind": f.kind, "param": float(f.param)}
    if f.kind == "combo":
        d["terms"] = [[float(c), statfn_to_meta(g)] for c, g in f.terms]
    return d


def statfn_from_meta(d: dict) -> StatFn:
    terms = tuple((float(c), statfn_from_meta(g))
                  for c, g in d.get("terms", []))
    return StatFn(d["kind"], float(d.get("param", 0.0)), terms)


def spec_to_meta(spec: MultiSketchSpec) -> dict:
    """JSON-able encoding of a spec — the static half of the checkpoint
    wire format (ckpt.manager stores it beside the slab arrays, so a
    restoring job reconstructs the spec without sharing code state)."""
    return {"objectives": [[statfn_to_meta(f), int(k)]
                           for f, k in spec.objectives],
            "scheme": spec.scheme, "seed": int(spec.seed),
            "capacity": int(spec.capacity)}


def spec_from_meta(d: dict) -> MultiSketchSpec:
    return MultiSketchSpec(
        objectives=tuple((statfn_from_meta(f), int(k))
                         for f, k in d["objectives"]),
        scheme=d["scheme"], seed=int(d["seed"]),
        capacity=int(d.get("capacity", 0)))


def multisketch_overflow(sk: MultiSketch) -> jnp.ndarray:
    """True iff the slab is full — i.e. compaction MAY have truncated
    S ∪ Z and the exact-merge guarantee is voided. Never True at the
    default capacity (one spare slot past the hard |S ∪ Z| bound)."""
    return jnp.all(sk.valid)


def multisketch_estimate(sk: MultiSketch, f: StatFn,
                         segment_fn=None) -> jnp.ndarray:
    """HT estimate of Q(f, H) from the sketch (paper Eq. 5: inverse
    p^(F) weighting). ``segment_fn``: vectorized key predicate for H."""
    from .merge import sketch_estimate
    return sketch_estimate(sk, f, segment_fn)


@partial(jax.jit, static_argnames=("fs", "use_kernels"))
def _estimate_batch_jit(keys, weights, probs, member, table, *, fs,
                        use_kernels):
    if use_kernels:
        from repro.kernels.segquery import segment_query_slab
        enc = tuple((_KERNEL_KIND[f.kind], float(f.param)) for f in fs)
        return segment_query_slab(keys, weights, probs, member, table, enc)
    from .estimators import estimate_many
    from .predicates import predicate_matrix
    return estimate_many(fs, weights, probs, member,
                         predicate_matrix(keys, table))


def multisketch_query_many(sk: MultiSketch, fs, predicates,
                           b_quantum: int = 16,
                           use_kernels: Optional[bool] = None):
    """Host-facing batched query: encode predicates, pad B up to a
    ``b_quantum`` bucket (with never-matching rows, so same-bucket batches
    share one compiled executable), run the fused estimate, slice back.
    Returns float numpy [|F|, B].

    B == 1 skips the bucketing and runs the one-row table directly — a
    single query is its own jit-cache bucket (one fixed shape, so traces
    stay bounded) and must not pay a ``b_quantum``-wide estimate; this is
    the single-query fast path every engine ``query`` routes through."""
    import numpy as np

    from .predicates import encode_predicates, pad_table
    table = encode_predicates(predicates)
    b = table.shape[0]
    # exactly B == 1: an empty (B=0) table still takes the bucketed path,
    # which degrades to a padded all-never batch and an empty [:, :0] slice
    bpad = 1 if b == 1 else max(b_quantum, -(-b // b_quantum) * b_quantum)
    out = multisketch_estimate_batch(sk, tuple(fs), pad_table(table, bpad),
                                     use_kernels=use_kernels)
    return np.asarray(out)[:, :b]


def multisketch_estimate_batch(sk: MultiSketch, fs, predicates,
                               use_kernels: Optional[bool] = None
                               ) -> jnp.ndarray:
    """Batched HT estimates Q(f_i, H_b) -> [|F|, B] from ONE slab pass.

    fs: sequence of StatFn; predicates: SegmentPredicate(s) or an encoded
    int32 wire table [B, PRED_COLS] (core.predicates). The kernel path
    (default when every f has a seeds-kernel encoding) is a single Pallas
    launch for the whole B x |F| batch; combo objectives or
    use_kernels=False take the bit-compatible XLA path (one contribution
    matrix + one matmul).
    """
    from .predicates import encode_predicates
    fs = tuple(fs)
    table = jnp.asarray(encode_predicates(predicates), jnp.int32)
    uk = True if use_kernels is None else use_kernels
    uk = uk and all(f.kind in _KERNEL_KIND for f in fs)
    return _estimate_batch_jit(sk.keys, sk.weights, sk.probs, sk.member,
                               table, fs=fs, use_kernels=uk)

"""Metric-space clustering engine (the second serving tier, paper §7).

The counterpart of ``launch.query.SegmentQueryEngine`` for query-indexed
METRIC objectives: instead of key predicates, a query is a candidate
center set C and the answer is the HT estimate of its clustering cost
Sum_x min_{c in C} d(x,c)^mu (or ball coverage). The engine keeps a
device-RESIDENT sampled point slab:

  * a ``MultiSketch`` over point keys whose weights are the anchor-based
    universal upper-bound probabilities (core.metric_domains) — absorbing
    a chunk is the jit'd donated streaming fold, exact under merge;
  * a coords slab [cap, dim] ALIGNED slot-by-slot with the sketch
    (realigned on device after every fold — one argsort + gather), so the
    fused service-cost kernel (kernels.servicecost) reads coordinates and
    HT weights from the same resident arrays;
  * anchor normalizers frozen at the first chunk, keeping ppswor seeds
    comparable across chunks (coordination under a fixed normalization).

``service_costs`` answers a Q-batch of candidate sets x the slab in ONE
compiled program (a host batch's Q bucketed to a quantum so jit traces
stay bounded; a device-built table is padded inside the program).

On top rides the paper's optimization meta-algorithm — compute a sample
once, then optimize over estimated costs:

  * :func:`local_search` — swap-based k-median/k-means local search where
    ALL candidate swaps of a round (1 + k * n_cand sets) are scored by one
    fused Q-batch; pass ``scorer=exact_scorer(X)`` to run the identical
    search against ground-truth costs (the small-instance oracle
    cross-check);
  * :func:`kcenter` — sample-based greedy 2-approx k-center (jit'd
    farthest-point on the member slots) with fused ball-coverage
    validation.
"""
from __future__ import annotations

from math import prod
from typing import Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costs import (CostTable, ball_query, cost_table,
                              encode_cost_queries, estimate_bucketed,
                              exact_service_costs, pad_cost_table,
                              swap_centers, swap_cost_table)
from repro.core.funcs import SUM
from repro.core.metric_domains import (anchor_upper_weights,
                                       farthest_point_anchors)
from repro.core.multi_sketch import (MultiSketchSpec, multisketch_absorb,
                                     multisketch_empty, pad_chunk)
from repro.telemetry import trace


def _sorted_lookup(cand_keys, cand_coords, queries):
    """(hit [n] bool, rows [n, dim]) — each query key's coords among the
    candidate (key, coord) rows; the shared sort+searchsorted+gather core
    of every realignment path. Negative query keys never hit."""
    order = jnp.argsort(cand_keys)
    sk = cand_keys[order]
    sc = cand_coords[order]
    pos = jnp.clip(jnp.searchsorted(sk, queries), 0, sk.shape[0] - 1)
    hit = (sk[pos] == queries) & (queries >= 0)
    return hit, sc[pos]


@jax.jit
def _align_coords(new_keys, cand_keys, cand_coords):
    """coords for each slab slot, looked up among candidate (key, coord)
    rows — the device-side realignment after a donated fold."""
    hit, rows = _sorted_lookup(cand_keys, cand_coords, new_keys)
    return jnp.where(hit[:, None], rows, 0.0)


@jax.jit
def _align_coords_delta(new_keys, old_keys, old_coords, chunk_keys,
                        chunk_coords):
    """Delta-aware realignment (the coords twin of the incremental merged-
    slab fold): a slot whose key did not move REUSES its coords row
    directly; only MOVED slots (shifted by compaction or newly inserted
    from the chunk) are re-gathered, and their lookup sorts the old slab
    and the chunk separately ([cap] + [chunk] argsorts instead of one
    [cap+chunk] argsort — the delta is usually much smaller than the
    candidate union). Bit-identical to ``_align_coords`` over the
    concatenated candidates: a re-absorbed key must present the same
    coordinates (ClusterEngine.absorb contract), so source order is free.
    """
    same = (new_keys == old_keys) & (new_keys >= 0)
    moved = jnp.where(same, -1, new_keys)    # unmoved slots skip the gather
    ohit, orows = _sorted_lookup(old_keys, old_coords, moved)
    chit, crows = _sorted_lookup(chunk_keys, chunk_coords, moved)
    looked = jnp.where(ohit[:, None], orows,
                       jnp.where(chit[:, None], crows, 0.0))
    return jnp.where(same[:, None], old_coords, looked)


class ClusterReplica(NamedTuple):
    """Portable snapshot of a ClusterEngine's resident state (deep
    copies): the hand-off unit of the scale-out replication contract —
    see ``ClusterEngine.handoff``."""

    sketch: object            # MultiSketch slab
    coords: object            # [cap, dim] aligned coords
    anchor_coords: object     # frozen anchors (None pre-first-absorb)
    eps: object               # frozen distance regularizer
    norm: object              # frozen per-anchor column sums
    next_key: int
    epoch: int
    config: dict              # constructor kwargs of the source engine


class ClusterEngine:
    """Resident sampled point slab + fused batched service-cost queries.

    ``k`` is the slab sample-size budget (the bottom-k parameter over the
    anchor upper-bound weights); per §7 a target per-query sample of size
    k_q needs k ≈ 2^mu k_q x (anchor overhead). Points are unit-weight
    (clustering over a point set, the paper's metric data model).
    """

    def __init__(self, dim: int, k: int = 64, mu: float = 2.0,
                 n_anchors: int = 8, scheme: str = "ppswor", seed: int = 0,
                 chunk: int = 256, q_quantum: int = 16,
                 use_kernels: Optional[bool] = None):
        self.dim = int(dim)
        self.k = int(k)
        self.mu = float(mu)
        self.n_anchors = int(n_anchors)
        self.chunk = int(chunk)
        self.q_quantum = int(q_quantum)
        self.use_kernels = use_kernels
        self._handed_out = False  # sample() gave away live slab buffers
        self.spec = MultiSketchSpec(objectives=((SUM, self.k),),
                                    scheme=scheme, seed=seed)
        self._sketch = multisketch_empty(self.spec)
        self._coords = jnp.zeros((self.spec.cap, self.dim), jnp.float32)
        self._anchor_coords = None   # [m, dim] frozen at first absorb
        self._eps = None             # frozen distance regularizer
        self._norm = None            # frozen per-anchor column sums
        self._epoch = 0
        self._next_key = 0

    @classmethod
    def fit(cls, X, **kw) -> "ClusterEngine":
        """One-shot engine over a full point set."""
        X = np.asarray(X, np.float32)
        eng = cls(dim=X.shape[1], **kw)
        eng.absorb(X)
        return eng

    # -- resident state ----------------------------------------------------
    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def anchors(self):
        return self._anchor_coords

    @property
    def overflow(self) -> bool:
        """Saturation health flag (mirrors SegmentQueryEngine.merge_stats
        ['overflow']): True iff the resident slab is full, i.e. compaction
        may have truncated the sample and cost-estimate cv silently
        degrades — serving tiers should surface it per response."""
        from repro.core.multi_sketch import multisketch_overflow
        return bool(multisketch_overflow(self._sketch))

    def absorb(self, points, keys=None):
        """Fold a chunk of points into the resident slab (donated device
        fold + coords realignment). ``keys`` default to a running global
        index; re-absorbing a key must present the same coordinates."""
        P = jnp.asarray(points, jnp.float32).reshape(-1, self.dim)
        b = P.shape[0]
        if self._anchor_coords is None:
            a_idx, _ = farthest_point_anchors(P, min(self.n_anchors, b))
            self._anchor_coords = P[a_idx]
            _, self._eps, self._norm = anchor_upper_weights(
                P, self._anchor_coords, self.mu)
        v, _, _ = anchor_upper_weights(P, self._anchor_coords, self.mu,
                                       eps=self._eps, norm=self._norm)
        if keys is None:
            keys = np.arange(self._next_key, self._next_key + b,
                             dtype=np.int32)
            self._next_key += b
        else:
            # keep the default-key counter ahead of explicit ids, so a later
            # default-keyed absorb can never alias different points
            keys = np.asarray(keys, np.int32)
            self._next_key = max(self._next_key, int(keys.max()) + 1)
        keys, v, act = pad_chunk(np.asarray(keys, np.int32),
                                 np.asarray(v, np.float32),
                                 np.ones((b,), bool), self.chunk)
        Ppad = jnp.pad(P, ((0, keys.shape[0] - b), (0, 0)))
        # a handed-out sample() may ALIAS the live slab; re-point the engine
        # at fresh buffers first, so the donated fold cannot invalidate the
        # caller's copy (same guard as SegmentQueryEngine.absorb)
        if self._handed_out:
            self._sketch = jax.tree.map(jnp.copy, self._sketch)
            self._handed_out = False
        # the fold donates the resident slab buffers — snapshot the old keys
        # first; old coords are engine-owned and not part of the sketch
        old_keys = jnp.copy(self._sketch.keys)
        old_coords = self._coords
        self._sketch = multisketch_absorb(self._sketch, keys, v, act,
                                          spec=self.spec,
                                          use_kernels=self.use_kernels)
        self._coords = _align_coords_delta(
            self._sketch.keys, old_keys, old_coords,
            jnp.asarray(keys, jnp.int32), Ppad)
        self._epoch += 1

    def sample(self):
        """(coords [cap, dim], probs [cap], member [cap]) — the resident
        slab the fused kernel consumes. The arrays stay valid across later
        ``absorb`` calls (the next fold re-points the engine instead of
        donating the handed-out buffers)."""
        self._handed_out = True
        return self._coords, self._sketch.probs, self._sketch.member

    def total_count(self) -> float:
        """HT estimate of the number of absorbed points."""
        return float(jnp.sum(jnp.where(
            self._sketch.member,
            1.0 / jnp.maximum(self._sketch.probs, 1e-30), 0.0)))

    # -- replica hand-off (scale-out follower promotion) ---------------------
    def handoff(self) -> "ClusterReplica":
        """Deep-copied portable replica of the resident state — the
        cluster tier's leg of the scale-out replication contract
        (launch.pool.ShardedEnginePool): ship it to a follower host and
        ``from_handoff`` promotes it to a serving engine.

        The FROZEN anchor normalizers (anchor coords, eps, per-anchor
        column sums) ride along with the slab: they are what keep ppswor
        seeds comparable across chunks, so a follower promoted WITHOUT
        them would re-freeze its own normalization on its first chunk and
        silently break sample coordination (arXiv 0906.4560) with every
        other replica of this stream. With them, the promoted engine
        serves bit-identical ``service_costs`` AND keeps absorbing
        bit-identically to the source."""
        cp = lambda x: None if x is None else jnp.copy(x)  # noqa: E731
        return ClusterReplica(
            sketch=jax.tree.map(jnp.copy, self._sketch),
            coords=jnp.copy(self._coords),
            anchor_coords=cp(self._anchor_coords),
            eps=cp(self._eps), norm=cp(self._norm),
            next_key=self._next_key, epoch=self._epoch,
            config={"dim": self.dim, "k": self.k, "mu": self.mu,
                    "n_anchors": self.n_anchors,
                    "scheme": self.spec.scheme, "seed": self.spec.seed,
                    "chunk": self.chunk, "q_quantum": self.q_quantum})

    @classmethod
    def from_handoff(cls, replica: "ClusterReplica",
                     use_kernels: Optional[bool] = None) -> "ClusterEngine":
        """Promote a handed-off replica to a serving engine (follower
        promotion). See ``handoff`` for the coordination contract."""
        eng = cls(use_kernels=use_kernels, **replica.config)
        eng._sketch = jax.tree.map(jnp.copy, replica.sketch)
        eng._coords = jnp.copy(replica.coords)
        cp = lambda x: None if x is None else jnp.copy(x)  # noqa: E731
        eng._anchor_coords = cp(replica.anchor_coords)
        eng._eps = cp(replica.eps)
        eng._norm = cp(replica.norm)
        eng._next_key = int(replica.next_key)
        eng._epoch = int(replica.epoch)
        return eng

    # -- fused batched queries ---------------------------------------------
    def service_costs(self, queries) -> np.ndarray:
        """HT clustering-cost / ball-density estimates for a Q-batch of
        service-cost queries -> float numpy [Q]. ONE fused launch over the
        slab regardless of Q and Cmax (kernels.servicecost tiles Q inside
        the launch, so its VMEM footprint is bounded for any batch); Q pads
        to ``q_quantum`` with null rows. A table whose fields are all
        device arrays (``swap_cost_table``) goes to the launch as it is and
        is padded inside it, one compiled program per Q; any other is
        encoded and padded on the host, so same-bucket batches share one
        program, and is copied over by the launch.

        Under a profiler capture it records the spans ``cluster.score``
        (the call), ``.prep`` (the bucket size, and a host table's encoding
        and padding), ``.dispatch`` (the launch, with a host table's copy
        to the device) and ``.wait`` (the read-back, which blocks on the
        kernel), and the counters ``cluster.score.sets`` (Q),
        ``.table_bytes`` (the table's five fields padded to the bucket,
        from their shapes) and ``.upload_bytes`` (those of them copied from
        host memory; 0 for a device table)."""
        with trace.span("cluster.score"):
            with trace.span("cluster.score.prep"):
                table, q, q_pad = self._table(queries)
            with trace.span("cluster.score.dispatch"):
                est = estimate_bucketed(
                    self._coords, self._sketch.probs, self._sketch.member,
                    table, q_pad, self.use_kernels)
            with trace.span("cluster.score.wait"):
                out = np.asarray(est)[:q]
        trace.count("cluster.score.sets", q)
        trace.count("cluster.score.table_bytes",
                    lambda: q_pad * sum(x.dtype.itemsize * prod(x.shape[1:])
                                        for x in table))
        trace.count("cluster.score.upload_bytes",
                    lambda: sum(x.nbytes for x in table
                                if not isinstance(x, jax.Array)))
        return out

    def _table(self, queries):
        """(the table to launch, Q, its ``q_quantum`` bucket): a table whose
        fields are all device arrays as it is, any other encoded and padded
        to the bucket on the host."""
        table = encode_cost_queries(queries)
        q = table.mu.shape[0]
        q_pad = max(self.q_quantum, -(-q // self.q_quantum) * self.q_quantum)
        if not all(isinstance(x, jax.Array) for x in table):
            table = pad_cost_table(table, q_pad)
        return table, q, q_pad

    def clustering_cost(self, centers, mu: Optional[float] = None) -> float:
        """Estimated Sum_x min_{c in centers} d(x,c)^mu for ONE set."""
        from repro.core.costs import cost_query
        return float(self.service_costs(
            cost_query(centers, self.mu if mu is None else mu))[0])

    def ball_density(self, center, r: float) -> float:
        """Estimated |{x : d(x, center-set) <= r}| for ONE set."""
        return float(self.service_costs(ball_query(center, r))[0])


# ---------------------------------------------------------------------------
# the optimization meta-algorithm (sample once, optimize over estimates)
# ---------------------------------------------------------------------------

class ClusterResult(NamedTuple):
    centers: np.ndarray     # [k, dim]
    est_cost: float         # scorer cost of the returned set
    history: List[float]    # accepted cost per round (history[0] = init)
    rounds: int             # swap rounds taken


def exact_scorer(X, point_weights=None) -> Callable[[CostTable], np.ndarray]:
    """Ground-truth scorer over the FULL point set — the oracle the
    sample-based search is cross-checked against on small instances."""
    X = jnp.asarray(X, jnp.float32)

    def score(table: CostTable) -> np.ndarray:
        return np.asarray(exact_service_costs(X, table,
                                              point_weights=point_weights))
    return score


def _candidate_pool(engine: ClusterEngine, n_cand: int) -> np.ndarray:
    """Deterministic candidate center locations: member slots strided
    evenly across the slab. Slab order is retention priority (sampling
    weight desc); the anchor upper-bound weights grow with distance from
    the anchors, so a PREFIX would be all outliers — the stride covers the
    whole weight range, cluster cores included."""
    # private reads (host copies only) — don't trip the hand-out guard
    cand = np.asarray(engine._coords)[np.asarray(engine._sketch.member)]
    m = cand.shape[0]
    if m == 0:
        raise ValueError("empty sample — absorb points first")
    if m <= n_cand:
        return cand
    return cand[np.unique(np.linspace(0, m - 1, n_cand).astype(int))]


def local_search(engine: ClusterEngine, k: int, mu: Optional[float] = None,
                 rounds: int = 16, n_cand: int = 32, tol: float = 1e-3,
                 scorer: Optional[Callable] = None) -> ClusterResult:
    """Sample-based swap local search for k-median (mu=1) / k-means (mu=2).

    Candidates are the engine's member slots; every round scores the
    current set plus ALL k x n_cand single swaps as ONE service-cost
    Q-batch (one fused launch via the engine scorer), accepts the best
    improving swap, and stops when no swap improves by ``tol``
    relatively. ``scorer`` defaults to the engine's fused HT estimator;
    pass :func:`exact_scorer` to run the identical search on ground-truth
    costs.

    The round's table is built on the device (``swap_cost_table``) from
    the candidates, uploaded once a search, and the current set, uploaded
    once a round, so the engine scores it without a host copy; a round
    rebuilds only its centers (``swap_centers``). A search of no rounds
    runs once what a round runs (``swap_centers``, the engine's one program
    for the table, and the read of one set's rows, as a scorer reads its
    sets), so callers warm a centre count's shapes with it.

    Under a profiler capture it records the spans ``cluster.search`` (the
    call), ``.seed`` (candidates, farthest-point init, first score),
    ``.round`` (one swap round), ``.build`` (the current set's upload) and
    ``.encode`` (the device build of the round's cost table).
    """
    mu = engine.mu if mu is None else float(mu)
    if scorer is None:
        scorer = engine.service_costs
    with trace.span("cluster.search"):
        with trace.span("cluster.search.seed"):
            cand = _candidate_pool(engine, n_cand)
            ncand = cand.shape[0]
            k = min(k, ncand)
            cand_dev = jnp.asarray(cand)
            # deterministic k-center init over the candidate pool
            init_idx, _ = farthest_point_anchors(cand_dev, k)
            cur = cand[np.asarray(init_idx)]                  # [k, dim]
            history = [float(np.asarray(
                scorer(cost_table(cur[None], mu)))[0])]
            # the rounds share every field but the centers
            table = swap_cost_table(jnp.asarray(cur), cand_dev, mu)
            if not rounds:
                # a search of no rounds is how callers warm a centre
                # count: run the rest of what a round runs once too
                engine.service_costs(table)
                table.centers[0], table.cvalid[0]             # one set
            fields = table._replace(centers=None)
            del table
        for _ in range(rounds):
            with trace.span("cluster.search.round"):
                with trace.span("cluster.search.build"):
                    cur_dev = jnp.asarray(cur)
                with trace.span("cluster.search.encode"):
                    # row 0: current set; row 1 + i*ncand + j: swap
                    # center i -> cand j
                    table = fields._replace(
                        centers=swap_centers(cur_dev, cand_dev))
                scores = np.asarray(scorer(table))
                del table
                best = int(np.argmin(scores[1:])) + 1
                if scores[best] < scores[0] * (1.0 - tol):
                    i, j = divmod(best - 1, ncand)
                    cur = cur.copy()
                    cur[i] = cand[j]
                    history.append(float(scores[best]))
                else:
                    break
    return ClusterResult(centers=cur, est_cost=history[-1],
                         history=history, rounds=len(history) - 1)


class KCenterResult(NamedTuple):
    centers: np.ndarray   # [k, dim]
    radius: float         # max sample-point distance to the centers
    coverage_est: float   # HT estimate of points within ``radius``
    total_est: float      # HT estimate of |X| (coverage should match)


def kcenter(engine: ClusterEngine, k: int) -> KCenterResult:
    """Sample-based greedy k-center (2-approx farthest-point on the member
    slots, one jit'd fori_loop) + fused ball-coverage validation: at the
    returned radius the estimated coverage should match the estimated
    total count (every point served within ``radius``)."""
    pts = jnp.asarray(
        np.asarray(engine._coords)[np.asarray(engine._sketch.member)])
    k = min(k, pts.shape[0])
    idx, d_min = farthest_point_anchors(pts, k)
    centers = np.asarray(pts[idx])
    radius = float(jnp.max(d_min))
    cov = engine.ball_density(centers, radius * (1 + 1e-5))
    return KCenterResult(centers=centers, radius=radius, coverage_est=cov,
                         total_est=engine.total_count())

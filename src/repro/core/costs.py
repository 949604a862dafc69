"""Service-cost objective wire format for the metric/clustering domain.

The paper's second application domain (§7) indexes objectives by METRIC
queries instead of key predicates: for a candidate center set C and
exponent mu, the service cost of a point x is

    f_C(x)     = min_{c in C} d(x, c)^mu          (k-median mu=1, k-means mu=2)
    f_{C,r}(x) = 1[min_{c in C} d(x, c) <= r]     (ball density / coverage)

and Sum(f_C; X) is the clustering cost of C (resp. the number of points C
covers within radius r). A candidate center set is RUNTIME data — the
optimizer proposes thousands of them — so unlike ``core.predicates`` the
wire format is a pytree of arrays, not a static row encoding:

  centers float32 [Q, Cmax, dim]  candidate sets, zero-padded to Cmax
  cvalid  bool    [Q, Cmax]       slot c of set q holds a real center
  mu      float32 [Q]             distance exponent (cost mode, mu > 0)
  param   float32 [Q]             radius r (ball mode)
  mode    int32   [Q]             MODE_COST | MODE_BALL

A row whose ``cvalid`` is all-False estimates exactly 0 in both modes —
the padding element for Q-bucket quantization: ``pad_cost_table`` adds it
on the host, ``estimate_bucketed`` inside the launch (a device table).

``service_cost_values`` is the vectorized oracle shared by the XLA
estimate path and the kernel tests; the fused Pallas kernel
(kernels.servicecost) computes the same function in-VMEM with Q x Cmax
centers on sublanes and slab slots on lanes. Distances use the shared
quadratic expansion  d2(x,c) = |x|^2 + |c|^2 - 2 x.c  clamped at 0, so
both paths agree to float tolerance.

HT estimation (paper Eq. 2/5): Q(f_C, X) is estimated from a sampled slab
(MultiSketch or MetricSample — member/probs fields) as
sum_{x in S} f_C(x) / p_x, routed through ``core.estimators.estimate_many``
with the real-valued matrix ``service_cost_values`` standing in for the
boolean segment matrix.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

MODE_COST = 0
MODE_BALL = 1


class CostTable(NamedTuple):
    """Array wire format for a batch of Q service-cost queries."""

    centers: jnp.ndarray  # float32 [Q, Cmax, dim]
    cvalid: jnp.ndarray   # bool    [Q, Cmax]
    mu: jnp.ndarray       # float32 [Q]
    param: jnp.ndarray    # float32 [Q]
    mode: jnp.ndarray     # int32   [Q]


@dataclasses.dataclass(frozen=True, eq=False)
class ServiceCostQuery:
    """One service-cost query: a center set + mode parameters."""

    centers: np.ndarray   # [m, dim]
    mu: float = 1.0
    mode: int = MODE_COST
    radius: float = 0.0


def cost_query(centers, mu: float = 1.0) -> ServiceCostQuery:
    """Clustering-cost query: Sum over x of min_c d(x, c)^mu."""
    c = np.atleast_2d(np.asarray(centers, np.float32))
    return ServiceCostQuery(centers=c, mu=float(mu))


def ball_query(centers, radius: float) -> ServiceCostQuery:
    """Ball-density query: # points within ``radius`` of the set (a single
    center gives the classic ball |B(q, r)|)."""
    c = np.atleast_2d(np.asarray(centers, np.float32))
    return ServiceCostQuery(centers=c, mode=MODE_BALL, radius=float(radius))


CostQueries = Union[ServiceCostQuery, Sequence[ServiceCostQuery], CostTable]


def encode_cost_queries(queries: CostQueries, cmax: Optional[int] = None
                        ) -> CostTable:
    """-> CostTable padded to a common Cmax. Accepts a single query, a
    sequence (ragged set sizes fine), or an already-encoded table."""
    if isinstance(queries, CostTable):
        return queries
    if isinstance(queries, ServiceCostQuery):
        queries = [queries]
    qs = list(queries)
    if not qs:
        raise ValueError("empty service-cost query batch")
    dims = {q.centers.shape[1] for q in qs}
    if len(dims) != 1:
        raise ValueError(f"mixed center dims {sorted(dims)} in one batch")
    dim = dims.pop()
    need = max(q.centers.shape[0] for q in qs)
    cm = need if cmax is None else int(cmax)
    if cm < need:
        raise ValueError(f"cmax={cm} < largest set size {need}")
    qn = len(qs)
    centers = np.zeros((qn, cm, dim), np.float32)
    cvalid = np.zeros((qn, cm), bool)
    mu = np.zeros((qn,), np.float32)
    param = np.zeros((qn,), np.float32)
    mode = np.zeros((qn,), np.int32)
    for i, q in enumerate(qs):
        m = q.centers.shape[0]
        centers[i, :m] = np.asarray(q.centers, np.float32)
        cvalid[i, :m] = True
        mu[i] = q.mu
        param[i] = q.radius
        mode[i] = q.mode
    return CostTable(centers=centers, cvalid=cvalid, mu=mu, param=param,
                     mode=mode)


def cost_table(center_sets, mu: float = 1.0) -> CostTable:
    """Encode a batch of center sets (sequence of [m_i, dim] arrays, or one
    [Q, m, dim] tensor) as cost-mode queries sharing one mu."""
    sets = (list(center_sets) if not hasattr(center_sets, "shape")
            else [center_sets[i] for i in range(center_sets.shape[0])])
    return encode_cost_queries([cost_query(c, mu) for c in sets])


def swap_cost_table(cur, cand, mu) -> CostTable:
    """Cost table of one swap round, built where ``cur`` and ``cand`` live:
    row 0 is the current set ``cur`` [k, dim] and row 1 + i*n + j is ``cur``
    with center i replaced by candidate j of ``cand`` [n, dim], every slot
    valid, one cost-mode ``mu``. Bit for bit what ``cost_table`` encodes
    from the same sets.

    Only ``centers`` depends on the sets: a search builds the table once
    and rebuilds its centers each round with ``swap_centers``."""
    centers = swap_centers(cur, cand)
    q, k = centers.shape[:2]
    return CostTable(centers, *(jnp.asarray(x) for x in (
        np.ones((q, k), bool), np.full((q,), mu, np.float32),
        np.zeros((q,), np.float32), np.full((q,), MODE_COST, np.int32))))


@jax.jit
def swap_centers(cur, cand):
    """The centers of ``swap_cost_table(cur, cand, mu)``: copies of ``cur``
    and ``cand`` selected by slot."""
    k, n = cur.shape[0], cand.shape[0]
    swap = jnp.repeat(jnp.eye(k, dtype=bool), n, axis=0)   # row i*n + j: i
    sets = jnp.where(swap[:, :, None], jnp.tile(cand, (k, 1))[:, None],
                     cur[None])
    return jnp.concatenate([cur[None], sets])


_DTYPES = CostTable(np.float32, bool, np.float32, np.float32, np.int32)


def pad_cost_table(table: CostTable, q_pad: int) -> CostTable:
    """-> the table as numpy, padded on the host to ``q_pad`` rows with null
    queries (no valid centers -> estimate exactly 0) so same-bucket batches
    share one compiled executable. A table already on the device is padded
    inside the launch instead (``estimate_bucketed``)."""
    q = table.mu.shape[0]
    host = CostTable(*(np.asarray(x, dt) for x, dt in zip(table, _DTYPES)))
    if q >= q_pad:
        return host
    return CostTable(*(np.concatenate(
        [x, np.zeros((q_pad - q,) + x.shape[1:], x.dtype)]) for x in host))


def sq_dists(centers, points) -> jnp.ndarray:
    """Squared distances [m, c] via the shared quadratic expansion — the ONE
    distance formula of both the XLA oracle and the Pallas kernel, both
    contracted at HIGHEST precision (bf16 operands would be amplified by
    the expansion's cancellation)."""
    ctr = jnp.asarray(centers, jnp.float32)
    pts = jnp.asarray(points, jnp.float32)
    dots = jax.lax.dot_general(ctr, pts, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    cn2 = jnp.sum(ctr * ctr, axis=1)
    pn2 = jnp.sum(pts * pts, axis=1)
    return jnp.maximum(cn2[:, None] + pn2[None, :] - 2.0 * dots, 0.0)


def service_cost_values(points, table: CostTable) -> jnp.ndarray:
    """Evaluate a cost table against points: [Q, Cmax, dim] x [c, dim]
    -> float32 [Q, c] of f-values (min-dist^mu, or the ball indicator).

    The reference implementation of the wire semantics; the servicecost
    kernel computes the same function in-VMEM.
    """
    pts = jnp.asarray(points, jnp.float32)
    ctr = jnp.asarray(table.centers, jnp.float32)
    qn, cm, dim = ctr.shape
    d2 = sq_dists(ctr.reshape(qn * cm, dim), pts)            # [Q*Cmax, c]
    d2 = jnp.where(jnp.asarray(table.cvalid, bool).reshape(-1)[:, None],
                   d2, jnp.float32(jnp.inf))
    mind2 = jnp.min(d2.reshape(qn, cm, -1), axis=1)          # [Q, c]
    finite = jnp.isfinite(mind2)
    mu = jnp.asarray(table.mu, jnp.float32)[:, None]
    r = jnp.asarray(table.param, jnp.float32)[:, None]
    cost = jnp.where(mind2 > 0,
                     jnp.power(jnp.maximum(mind2, 1e-38), 0.5 * mu), 0.0)
    ball = (mind2 <= r * r).astype(jnp.float32)
    out = jnp.where(jnp.asarray(table.mode, jnp.int32)[:, None] == MODE_BALL,
                    ball, cost)
    return jnp.where(finite, out, 0.0)


def estimate_service_costs(points, probs, member, queries: CostQueries,
                           point_weights=None,
                           use_kernels: Optional[bool] = None,
                           interpret=None) -> jnp.ndarray:
    """Batched HT estimates of Q clustering costs / ball densities -> [Q].

    points/probs/member: the sampled slab (coords [c, dim] aligned with the
    MultiSketch probs/member fields, or a MetricSample restriction);
    queries: ServiceCostQuery batch or encoded CostTable. The kernel path
    (default) is ONE fused Pallas launch for the whole Q x Cmax batch;
    use_kernels=False takes the bit-compatible XLA path (the shared oracle
    matrix + one estimate_many matmul). ``point_weights``: optional per-slot
    data weights (multiplicities) for weighted point sets.
    """
    table = encode_cost_queries(queries)
    uk = True if use_kernels is None else use_kernels
    if uk:
        from repro.kernels.servicecost import service_cost_slab
        return service_cost_slab(points, probs, member, table,
                                 point_weights=point_weights,
                                 interpret=interpret)
    return _estimate_xla_jit(
        jnp.asarray(points, jnp.float32), jnp.asarray(probs, jnp.float32),
        jnp.asarray(member, bool),
        CostTable(*(jnp.asarray(x) for x in table)),
        point_weights if point_weights is None
        else jnp.asarray(point_weights, jnp.float32))


@partial(jax.jit, static_argnums=(4, 5))
def estimate_bucketed(points, probs, member, table: CostTable, q_pad: int,
                      use_kernels: Optional[bool] = None) -> jnp.ndarray:
    """``estimate_service_costs`` of ``table``'s Q rows as if padded to
    ``q_pad`` with null rows, in ONE compiled program -> [q_pad], the
    padded rows exactly 0; a table already ``q_pad`` rows long is
    estimated as it is.

    The kernel path pads the table inside the program, where the kernel's
    own layout pads it again (Q to its tile, Cmax, dim and the validity
    column), so XLA can fuse the two and a device table is not copied
    whole before the launch; the kernel sees what a host-padded table
    gives it. The XLA path pads its estimates instead: a pad of its input
    would be fused into the estimate's contraction and change its
    rounding."""
    def pad(x):
        return jnp.pad(x, [(0, q_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1))
    if use_kernels is None or use_kernels:
        from repro.kernels.servicecost import _service_cost_jit
        return _service_cost_jit(points, probs, member,
                                 CostTable(*map(pad, table)), None, None)
    return pad(_estimate_xla_jit(points, probs, member, table, None))


@jax.jit
def _estimate_xla_jit(points, probs, member, table, point_weights):
    from .estimators import estimate_many
    from .funcs import SUM
    values = service_cost_values(points, table)               # [Q, c]
    pw = (jnp.ones(points.shape[:1], jnp.float32) if point_weights is None
          else point_weights)
    # SUM(pw) * ht is exactly the per-slot HT weight; the real-valued
    # f_C matrix rides the (float-cast) segment axis of estimate_many.
    return estimate_many((SUM,), pw, probs, member, values)[0]


def exact_service_costs(points, queries: CostQueries,
                        point_weights=None) -> jnp.ndarray:
    """Ground-truth costs over the FULL point set (validation / the exact
    scorer of launch.cluster): -> [Q]."""
    table = encode_cost_queries(queries)
    pts = jnp.asarray(points, jnp.float32)
    values = service_cost_values(pts, CostTable(*(jnp.asarray(x)
                                                  for x in table)))
    pw = (jnp.ones(pts.shape[:1], jnp.float32) if point_weights is None
          else jnp.asarray(point_weights, jnp.float32))
    return jnp.matmul(values, pw, precision=jax.lax.Precision.HIGHEST)

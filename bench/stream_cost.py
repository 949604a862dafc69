"""HBM bytes the key-value stream's Pallas kernels need for one call,
computed from their shapes, and the least time the chip could take.

The counts are what each call's algorithm needs at the logical
(unpadded) sizes: every input read once and every output written once.
These kernels do no matrix work worth the name: they are bound by memory
and by the vector unit, and ``peaks.py`` has no published vector-unit
peak, so each roofline holds the bytes against ``hbm_bytes_per_s`` alone.

    fused_seeds(n, nf)         reads keys int32, weights float32, active
                               bool [n]; writes seeds and f-values
                               float32 [nf, n]
    block_select(nf, n, k)     reads seeds float32 [nf, n]; writes the k
                               smallest per row, values float32 and
                               indices int32
    retention_priority(n)      reads keys int32, weights float32, member
                               and keep bool [n]; writes priority float32
    segment_query(c, b, nf)    reads a slab's keys int32, weights and
                               probs float32, member bool [c] and b wire
                               rows of 6 int32; writes answers float32
                               [nf, b]
"""
from __future__ import annotations


def fused_seeds(n: int, nf: int) -> float:
    return 9.0 * n + 8.0 * nf * n


def block_select(nf: int, n: int, k: int) -> float:
    return 4.0 * nf * n + 8.0 * nf * k


def retention_priority(n: int) -> float:
    return 14.0 * n


def segment_query(c: int, b: int, nf: int) -> float:
    return 13.0 * c + 24.0 * b + 4.0 * nf * b


def least_seconds(nbytes: float, peak: dict) -> float:
    return nbytes / peak["hbm_bytes_per_s"]

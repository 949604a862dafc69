"""Operations and HBM bytes a Pallas kernel of the hot path needs for one
call, computed from its shapes, and the least time the chip could take
for it.

The counts are what the call's algorithm needs, at the logical (unpadded)
sizes: every input read once and every output written once, and the
arithmetic of the result, not of the kernel's chosen method.

    service_cost(c, q, cm, d)     reads the [c, d] coordinates, probs and
                                  member, the [q, cm, d] centres; writes
                                  [q]; 2 q cm c d operations for the
                                  distance contractions

Operations are held against the bf16 matrix peak, the only published
matrix rate; a float32 contraction at HIGHEST precision takes several
bf16 passes, so its share of that peak stays well under 100%.
"""
from __future__ import annotations


def service_cost(c: int, q: int, cm: int, d: int) -> dict:
    return {"ops": 2.0 * q * cm * c * d,
            "bytes": 4.0 * c * d + 8.0 * c + 4.0 * q * cm * d + 4.0 * q}


def least_seconds(cost: dict, peak: dict) -> tuple:
    """(seconds, bound): the larger of operations over the bf16 peak and
    bytes over the HBM bandwidth, and which of the two it is."""
    t_ops = cost["ops"] / peak["bf16_flops"]
    t_mem = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops > t_mem else (t_mem, "memory")

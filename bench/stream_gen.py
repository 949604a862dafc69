"""Load generator of the key-value stream cells, made from ``--seed`` alone.

Copies of the bring-up smoke's laws (``chip_smoke.py`` ``zipf_keys``,
``stream_chunk``, ``make_predicates``) and of the chaos tier's open-loop
schedule (``tests/faults.py`` ``poisson_arrivals``), kept here so that a
change to the program cannot change the yardstick. Chunk ``i`` of a seed
is the same array in every run and in the reference. Predicates are the
program's int32 wire rows (``core/predicates.py``): a key range is
``[lo, hi, 0, 0, 0, 0]``, a coordinated hash fraction q under ``salt`` is
``[0, int(q 2^31) - 1, 0, 0, salt, 1]``.
"""
from __future__ import annotations

import numpy as np

_PERM_MULT = 0x9E3779B1          # odd: a bijection on [0, 2^m)
NEVER_TABLE = np.array([[1, 0, 0, 0, 0, 0]], np.int32)   # lo > hi: no key


def rng_of(seed: int, *stream: int) -> np.random.Generator:
    """A generator from any whole-number seed and a stream number."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1)]
                                 + [int(s) for s in stream])


def zipf_keys(rng, n: int, ids_log2: int, s: float) -> np.ndarray:
    """n keys, Zipf(s) by rank over 2^ids_log2 ids (continuous inverse-CDF
    approximation of the bounded law), ranks scattered over the id space
    by a multiplicative bijection."""
    N = float(1 << ids_log2)
    u = rng.random(n)
    x = (1.0 - u * (1.0 - N ** (1.0 - s))) ** (1.0 / (1.0 - s))
    rank = np.minimum(x.astype(np.int64), (1 << ids_log2)) - 1
    return ((rank * _PERM_MULT) & ((1 << ids_log2) - 1)).astype(np.int32)


def stream_chunk(seed: int, i: int, events: dict):
    """Chunk ``i``: (keys int32 [n], weights float32 [n]); keys Zipf over
    the id space, weights Pareto(alpha) + 1."""
    rng = rng_of(seed, 1, i)
    n = 1 << int(events["chunk_log2"])
    keys = zipf_keys(rng, n, int(events["ids_log2"]), float(events["zipf_s"]))
    weights = (rng.pareto(float(events["pareto_alpha"]), n)
               + 1.0).astype(np.float32)
    return keys, weights


def predicates(rng, n: int, ids_log2: int, hash_seed: int,
               hashed: bool = True) -> np.ndarray:
    """n predicates [n, 6]: key ranges 2^(m-10) to 2^(m-1) wide over the
    2^m ids, and with ``hashed`` coordinated hash fractions of 2% to 90%
    (4 salts) in every other row. No salt equals the sketch's hash seed:
    that fraction would select keys by the sampling randomness itself."""
    span = 1 << ids_log2
    width = (2.0 ** rng.uniform(ids_log2 - 10, ids_log2 - 1, n)).astype(
        np.int64)
    lo = rng.integers(0, span - width)
    salt = hash_seed + 1 + rng.integers(0, 4, n)
    top = (rng.uniform(0.02, 0.9, n) * 2 ** 31).astype(np.int64) - 1
    rows = np.zeros((n, 6), np.int64)
    rows[:, 0], rows[:, 1] = lo, lo + width - 1
    if hashed:
        h = np.arange(n) % 2 == 1
        rows[h, 0], rows[h, 1], rows[h, 4], rows[h, 5] = 0, top[h], salt[h], 1
    return rows.astype(np.int32)


def poisson_arrivals(rng, rate: float, n: int) -> np.ndarray:
    """Open-loop due times (s from the window's start) of n requests."""
    return np.cumsum(rng.exponential(1.0 / rate, n))

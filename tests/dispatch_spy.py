"""Dispatch spies: counts of the engine's merge-work dispatches, and of
the calls through a module's jitted functions (``spy_jit_calls``).

A test helper: the engine tests use it to assert the zero-merge
serving claim. A query phase under absorb-time maintenance must dispatch
NO merge work (``full == inc == 0``), because every chunk was already
folded into the cached merged slab at absorb time.

The spy wraps the two query-path merge entry points in ``launch.query``:
``_full_remerge`` (the full stacked re-merge, itself a fold into a fresh
empty slab) and ``multisketch_absorb_slabs`` (the incremental delta
fold). Both are resolved through the module globals, so patching the
module attributes captures every engine-internal call; while a spied
full re-merge delegates, the inner fold is un-spied so it counts as one
"full", not full+inc. Note ``gc_apply``/``add_shard`` also route through
``multisketch_absorb_slabs`` — scope the context manager around the
phase being measured (the query loop), not the whole run, to count
query-time dispatches only.
"""
import collections
from contextlib import contextmanager

import jax

from repro.launch import query as Q

_JITTED = type(jax.jit(abs))


@contextmanager
def spy_merge_dispatch():
    """Context manager yielding a live ``{"full": n, "inc": n}`` counter
    of merge dispatches issued while the context is active."""
    counts = {"full": 0, "inc": 0}
    real_full = Q._full_remerge
    real_into = Q.multisketch_absorb_slabs

    def spy_full(*a, **k):
        counts["full"] += 1
        Q.multisketch_absorb_slabs = real_into
        try:
            return real_full(*a, **k)
        finally:
            Q.multisketch_absorb_slabs = spy_into

    def spy_into(*a, **k):
        counts["inc"] += 1
        return real_into(*a, **k)

    Q._full_remerge = spy_full
    Q.multisketch_absorb_slabs = spy_into
    try:
        yield counts
    finally:
        Q._full_remerge = real_full
        Q.multisketch_absorb_slabs = real_into


@contextmanager
def spy_jit_calls(*modules):
    """Context manager yielding a live ``{"<module>.<name>": n}`` count of
    the calls made, while the context is active, through every jitted
    function that ``modules`` hold as attributes. A call of a compiled
    program from inside another is not a call through the attribute, so
    a warm launch counts once."""
    counts = collections.Counter()
    real = [(mod, name, fn) for mod in modules
            for name, fn in vars(mod).items() if isinstance(fn, _JITTED)]

    def spy(key, fn):
        def call(*a, **k):
            counts[key] += 1
            return fn(*a, **k)
        return call

    for mod, name, fn in real:
        setattr(mod, name, spy(f"{mod.__name__}.{name}", fn))
    try:
        yield counts
    finally:
        for mod, name, fn in real:
            setattr(mod, name, fn)

"""Where JAX keeps its persistent compilation cache.

A cold process recompiles every kernel and fold program of the serving
path; the persistent cache lets a second process (or a second compile of
the same program in one process) load the executable instead. The
cache's directory is part of its key, so it must be a fixed path.
"""
from __future__ import annotations

import contextlib
import os

import jax
from jax.experimental.compilation_cache import compilation_cache as _cc

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when it is set: JAX reads it itself
    and nothing else is set here. Otherwise the cache lives in
    ``<checkout>/.jax_cache``. Call from a program's entry point, never at
    import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def compile_cache_disabled():
    """Keep the persistent cache off inside the block.

    For compiles against a described (not attached) device: their
    executables would be written to the cache but could never be loaded.
    """
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()                  # forget the memoized on/off verdict
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        _cc.reset_cache()

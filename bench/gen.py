"""Load generator of the benchmark, made from ``--seed`` alone.

The clustering points of a run come from here: the same seed gives the
same points. They are made on the device in one jitted call. The law is
a copy of the bring-up smoke's (``chip_smoke.py`` ``mixture_points``),
kept here so that a change to the program cannot change the yardstick.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def root_key(seed: int, stream: int) -> jax.Array:
    """A JAX key from any whole-number seed (more than 32 bits is fine)."""
    state = np.random.SeedSequence([int(seed) & ((1 << 64) - 1),
                                    int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32))


@partial(jax.jit, static_argnames=("n", "dim", "comps"))
def _mixture(key, *, n, dim, comps):
    km, kl, kx = jax.random.split(key, 3)
    means = 4.0 * jax.random.normal(km, (comps, dim), jnp.float32)
    lab = jax.random.randint(kl, (n,), 0, comps)
    return means[lab] + jax.random.normal(kx, (n, dim), jnp.float32)


def mixture_points(seed: int, n: int, dim: int, comps: int) -> jax.Array:
    """[n, dim] float32 points on the device: ``comps`` Gaussian
    components with N(0, 16) means and unit spread."""
    return _mixture(root_key(seed, 2), n=int(n), dim=int(dim),
                    comps=int(comps))

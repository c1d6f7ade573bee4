"""Random1B/10B corpus (arXiv:2212.02635, App. D.1): ``modes`` Gaussian
modes, mode i with mean e_i and per-coordinate standard deviation ``std``.

Copied from ``src/repro/data/synthetic.py`` (``gaussian_mixture_points``)
so that a change to the program cannot change the benchmark's data; made on
the device in one jitted program over a 31-bit seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _points(n: int, d: int, modes: int, std: float, seed):
    km, kx = jax.random.split(jax.random.key(seed))
    mode = jax.random.randint(km, (n,), 0, modes)
    x = jax.random.normal(kx, (n, d)) * std
    return x.at[jnp.arange(n), mode % d].add(1.0)


def make(config: dict, seed: int) -> jax.Array:
    """The (n, d) float32 corpus of ``config`` for ``seed``, on the device."""
    return _points(config["n"], config["d"], config["modes"], config["std"],
                   seed)

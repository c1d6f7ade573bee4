"""MNIST-shaped corpus: ``classes`` random unit centres in d dimensions,
each point its class's centre plus Gaussian noise of ``spread`` per
coordinate.  It stands in for the real digits, which a run cannot fetch.

Copied from ``src/repro/data/synthetic.py`` (``mnist_like_points``) so that
a change to the program cannot change the benchmark's data; made on the
device in one jitted program over a 31-bit seed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _points(n: int, d: int, classes: int, spread: float, seed):
    kc, km, kx = jax.random.split(jax.random.key(seed), 3)
    centers = jax.random.normal(kc, (classes, d))
    centers = centers / jnp.linalg.norm(centers, axis=-1, keepdims=True)
    label = jax.random.randint(km, (n,), 0, classes)
    return centers[label] + spread * jax.random.normal(kx, (n, d))


def make(config: dict, seed: int) -> jax.Array:
    """The (n, d) float32 corpus of ``config`` for ``seed``, on the device."""
    return _points(config["n"], config["d"], config["classes"],
                   config["spread"], seed)

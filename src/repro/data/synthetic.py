"""Deterministic synthetic datasets mirroring the paper's evaluation data.

The paper evaluates on MNIST (dense, 10 classes), Wikipedia (weighted sets),
Amazon2m (dense + co-purchase sets, 47 classes) and Random1B/10B (Gaussian
mixture, 100 modes, d=100, sigma=0.1).  This module generates shape- and
distribution-faithful stand-ins at any scale:

  * ``gaussian_mixture_points``  — the Random{1,10}B generator, verbatim
    (Appendix D.1): mode i has mean e_i and per-coordinate std 0.1.
  * ``mnist_like_points``        — c well-separated classes in d dims with
    class-conditional spread, unit-normalized (cosine geometry like MNIST).
  * ``products_like_points``     — Amazon2m analogue: dense embedding +
    a padded "co-purchase" set biased to the same category.
  * ``wikipedia_like_sets``      — weighted string-set analogue (Zipfian
    vocabulary, per-class topical skew).
  * ``token_stream_batch``       — deterministic, *seekable* LM token batches:
    batch t is a pure function of (seed, t), so training restarts resume the
    stream exactly (fault-tolerance substrate).

Everything is jit-friendly and reproducible from integer seeds.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.similarity.measures import PointFeatures


def gaussian_mixture_points(n: int, *, d: int = 100, modes: int = 100,
                            std: float = 0.1, seed: int = 0
                            ) -> Tuple[PointFeatures, np.ndarray]:
    """Appendix D.1 Random1B/10B generator (scaled to n points)."""
    x, mode = _gaussian_mixture(n, d, modes, std, seed)
    return PointFeatures(dense=x), np.asarray(mode)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _gaussian_mixture(n: int, d: int, modes: int, std: float, seed):
    # one program on the device (op-by-op dispatch compiles each op apart)
    key = jax.random.key(seed)
    km, kx = jax.random.split(key)
    mode = jax.random.randint(km, (n,), 0, modes)
    x = jax.random.normal(kx, (n, d)) * std
    return x.at[jnp.arange(n), mode % d].add(1.0), mode


def mnist_like_points(n: int = 20_000, *, d: int = 64, classes: int = 10,
                      spread: float = 0.15, seed: int = 0
                      ) -> Tuple[PointFeatures, np.ndarray]:
    """Clustered dense points with cosine-separable classes."""
    key = jax.random.key(seed)
    kc, km, kx = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (classes, d))
    centers = centers / jnp.linalg.norm(centers, axis=-1, keepdims=True)
    label = jax.random.randint(km, (n,), 0, classes)
    x = centers[label] + spread * jax.random.normal(kx, (n, d))
    return PointFeatures(dense=x), np.asarray(label)


def products_like_points(n: int = 20_000, *, d: int = 100, classes: int = 47,
                         nnz: int = 16, universe: int = 100_000,
                         dup_frac: float = 0.0,
                         seed: int = 0) -> Tuple[PointFeatures, np.ndarray]:
    """Amazon2m analogue: dense embedding + co-purchase set per point.

    Co-purchase sets draw ~80% of their elements from a per-class pool
    (making Jaccard informative for the class) and ~20% background noise.
    """
    key = jax.random.key(seed)
    kc, km, kx, kp, kn, kb = jax.random.split(key, 6)
    centers = jax.random.normal(kc, (classes, d))
    centers = centers / jnp.linalg.norm(centers, axis=-1, keepdims=True)
    label = jax.random.randint(km, (n,), 0, classes)
    dense = centers[label] + 0.4 * jax.random.normal(kx, (n, d))

    pool_size = 64
    class_pool = jax.random.randint(kp, (classes, pool_size), 0, universe)
    pick = jax.random.randint(kn, (n, nnz), 0, pool_size)
    from_pool = class_pool[label[:, None], pick]
    noise = jax.random.randint(kb, (n, nnz), 0, universe)
    coin = jax.random.uniform(jax.random.fold_in(kb, 1), (n, nnz)) < 0.8
    idx = jnp.where(coin, from_pool, noise).astype(jnp.int32)
    if dup_frac > 0:
        # near-duplicate injection (co-listed product variants): point i
        # copies a random earlier point with a few elements resampled, so
        # high-similarity (>=0.5) pairs exist — the regime the paper's
        # r-threshold graphs (Figs 2/3) measure.
        kd = jax.random.fold_in(key, 7)
        is_dup = jax.random.uniform(jax.random.fold_in(kd, 0), (n,)) < dup_frac
        src_pt = jax.random.randint(jax.random.fold_in(kd, 1), (n,), 0, n)
        keep_el = jax.random.uniform(jax.random.fold_in(kd, 2),
                                     (n, nnz)) < 0.8
        idx = jnp.where(is_dup[:, None],
                        jnp.where(keep_el, idx[src_pt], idx), idx)
        jitter = 0.08 * jax.random.normal(jax.random.fold_in(kd, 3), (n, d))
        dense = jnp.where(is_dup[:, None], dense[src_pt] + jitter, dense)
        label = jnp.where(is_dup, label[src_pt], label)
    feats = PointFeatures(
        dense=dense, set_idx=idx,
        set_w=jnp.ones((n, nnz), jnp.float32),
        set_mask=jnp.ones((n, nnz), bool))
    return feats, np.asarray(label)


def wikipedia_like_sets(n: int = 20_000, *, classes: int = 20, nnz: int = 32,
                        universe: int = 200_000, dup_frac: float = 0.0,
                        seed: int = 0) -> Tuple[PointFeatures, np.ndarray]:
    """Weighted-set points (word multiset analogue) with topical classes."""
    key = jax.random.key(seed)
    km, kp, kn, kb, kw = jax.random.split(key, 5)
    label = jax.random.randint(km, (n,), 0, classes)
    pool_size = 128
    class_pool = jax.random.randint(kp, (classes, pool_size), 0, universe)
    pick = jax.random.randint(kn, (n, nnz), 0, pool_size)
    from_pool = class_pool[label[:, None], pick]
    noise = jax.random.randint(kb, (n, nnz), 0, universe)
    coin = jax.random.uniform(jax.random.fold_in(kb, 1), (n, nnz)) < 0.75
    idx = jnp.where(coin, from_pool, noise).astype(jnp.int32)
    if dup_frac > 0:
        # near-duplicate articles (redirects / forks): J ~ 0.6 pairs.
        kd = jax.random.fold_in(key, 9)
        is_dup = jax.random.uniform(jax.random.fold_in(kd, 0), (n,)) < dup_frac
        src_pt = jax.random.randint(jax.random.fold_in(kd, 1), (n,), 0, n)
        keep_el = jax.random.uniform(jax.random.fold_in(kd, 2),
                                     (n, nnz)) < 0.8
        idx = jnp.where(is_dup[:, None],
                        jnp.where(keep_el, idx[src_pt], idx), idx)
        label = jnp.where(is_dup, label[src_pt], label)
    # Zipf-ish positive weights (word frequencies).
    w = jnp.exp(jax.random.normal(kw, (n, nnz)) * 0.5) \
        / (1.0 + (idx.astype(jnp.float32) % 97.0) / 10.0)
    feats = PointFeatures(dense=None, set_idx=idx, set_w=w.astype(jnp.float32),
                          set_mask=jnp.ones((n, nnz), bool))
    return feats, np.asarray(label)


def token_stream_batch(step: int, *, batch: int, seq_len: int,
                       vocab: int, seed: int = 0) -> jax.Array:
    """Deterministic seekable token batch: a pure function of (seed, step).

    Tokens follow a mixed bigram process so the LM loss actually decreases —
    enough structure for the ~100M-model end-to-end training example.
    """
    key = jax.random.fold_in(jax.random.key(seed), step)
    k0, k1, k2 = jax.random.split(key, 3)
    base = jax.random.randint(k0, (batch, seq_len), 0, vocab)
    # inject learnable structure: with p=0.85, token[t] = (token[t-1]*31+7) % vocab
    coin = jax.random.uniform(k1, (batch, seq_len)) < 0.85

    def step_fn(prev, xs):
        b, c = xs
        nxt = jnp.where(c, (prev * 31 + 7) % vocab, b)
        return nxt, nxt

    first = base[:, 0]
    _, rest = jax.lax.scan(
        step_fn, first, (base[:, 1:].T, coin[:, 1:].T))
    return jnp.concatenate([first[:, None], rest.T], axis=1).astype(jnp.int32)

"""The corpus generators, each found by its name: the same seed gives the
same corpus, another seed another, at the shape the configuration asks."""

import glob
import json
import os

import numpy as np
import pytest

from bench import harness

# MNIST at its published width; its configuration waits for the program
MNIST = dict(name="mnist", generator="mnist_like", n=512, d=784, classes=10,
             spread=0.036)
CONFIGS = [dict(json.load(open(p)), n=512) for p in sorted(glob.glob(
    os.path.join(harness.ROOT, "bench", "configs", "*.json")))] + [MNIST]


def _make(name):
    return harness.load_module(harness.ROOT, "generators", name).make


@pytest.mark.parametrize("cfg", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_same_seed_same_corpus(cfg):
    make = _make(cfg["generator"])
    seed = harness.data_seed(2**33 + 11)
    a, b = np.asarray(make(cfg, seed)), np.asarray(make(cfg, seed))
    assert a.shape == (cfg["n"], cfg["d"]) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, np.asarray(make(cfg, seed ^ 1)))


def test_gaussian_mixture_modes_sit_on_the_axes():
    x = np.asarray(_make("gaussian_mixture")(
        dict(n=512, d=100, modes=100, std=0.1), 5))
    # each point is e_i plus noise of 0.1 a coordinate: one coordinate near 1
    top = np.sort(x, axis=1)
    assert np.all(top[:, -1] > 0.5) and np.all(top[:, -2] < 0.6)


def test_mnist_like_classes_are_cosine_separable():
    x = np.asarray(_make("mnist_like")(MNIST, 5), np.float64)
    u = x / np.linalg.norm(x, axis=1, keepdims=True)
    cos = u @ u.T
    # nearest neighbours share a centre at cosine about
    # 1 / (1 + d spread^2) = 0.50; two centres lie near 0
    np.fill_diagonal(cos, -1)
    assert np.median(cos.max(axis=1)) > 0.4
    assert abs(np.median(cos)) < 0.1

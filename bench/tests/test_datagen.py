"""The copied generators give the arrays they gave when copied."""

import hashlib

import numpy as np

from bench import datagen


def _digest(x: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()[:16]


def test_mnist_like_pinned():
    x = datagen.mnist_like(300, seed=0)
    assert x.shape == (300, 784) and x.dtype == np.float32
    assert _digest(x) == MNIST_300


def test_run_rng_takes_large_seeds():
    a = datagen.run_rng(2**40 + 3, "order").permutation(10)
    b = datagen.run_rng(2**40 + 3, "order").permutation(10)
    assert np.array_equal(a, b)
    assert 0 <= datagen.fit_seed(2**40 + 3) < 2**31


# sha256 prefix of the array, equal to that of
# repro.core.datasets at the commit that added the copy.
MNIST_300 = "3c9ccea045b1df9a"

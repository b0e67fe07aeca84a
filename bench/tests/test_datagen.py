"""The copied generators give the arrays they gave when copied."""

import hashlib

import numpy as np
import pytest

from bench import datagen


def _digest(x: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(x).tobytes()).hexdigest()[:16]


def test_mnist_like_pinned():
    x = datagen.mnist_like(300, seed=0)
    assert x.shape == (300, 784) and x.dtype == np.float32
    assert _digest(x) == MNIST_300


def test_scrna_like_pinned():
    x = datagen.scrna_like(300, seed=0, d=1000)
    assert x.shape == (300, 1000) and x.dtype == np.float32
    assert _digest(x) == SCRNA_300


def test_unknown_data_set_names_those_there_are():
    config = {"dataset": "no_such_set", "data_seed": 0, "d": 4}
    with pytest.raises(LookupError, match="no_such_set") as e:
        datagen.dataset(config, 3)
    assert "'mnist_like'" in str(e.value)
    assert "'scrna_like'" in str(e.value)


def test_run_rng_takes_large_seeds():
    a = datagen.run_rng(2**40 + 3, "order").permutation(10)
    b = datagen.run_rng(2**40 + 3, "order").permutation(10)
    assert np.array_equal(a, b)
    assert 0 <= datagen.fit_seed(2**40 + 3) < 2**31


# sha256 prefixes of the arrays, equal to those of
# repro.core.datasets at the commit that added each copy.
MNIST_300 = "3c9ccea045b1df9a"
SCRNA_300 = "0e770faeb477ada0"

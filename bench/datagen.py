"""Seeded data of the benchmark's deployments, on the host.

A configuration's ``dataset`` names the file ``datasets/<dataset>.py``,
whose ``generate(n, seed, d)`` returns the data set's first ``n`` rows as
a float32 ``[n, d]`` array.  Each such file is a copy of a generator of
the program (it says which, and at which commit), kept here so that a
change to the program's own files cannot move the yardstick;
``tests/test_datagen.py`` in this directory pins a few values of each.
A data set's generator is also an attribute of this module:
``datagen.mnist_like`` is ``datasets/mnist_like.py``'s ``generate``.
"""

from __future__ import annotations

import numpy as np

from bench import harness


def generator(name: str):
    """The ``generate`` of ``datasets/<name>.py``; ``LookupError`` naming
    the data sets there are where it has none."""
    return harness.load("datasets", name, "generate")


def __getattr__(name: str):
    if name.startswith("_"):
        raise AttributeError(name)
    try:
        return generator(name)
    except LookupError as e:
        raise AttributeError(str(e)) from None


def dataset(config: dict, n: int) -> np.ndarray:
    """The first ``n`` rows of a configuration's data set.  The data set
    is fixed by the configuration (``data_seed``), as a deployment's data
    is; a run's seed never changes it."""
    gen = generator(config["dataset"])
    return gen(n, seed=int(config["data_seed"]), d=int(config["d"]))


def run_rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator for one use of a run's ``--seed``; any
    whole number, however large, is a valid seed."""
    salt = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([int(seed) & (2**64 - 1), salt])


def fit_seed(seed: int) -> int:
    """The solver's seed for a run: a 31-bit number drawn from ``--seed``
    (the solver's PRNG key takes a 32-bit seed)."""
    return int(run_rng(seed, "fit").integers(0, 2**31 - 1))

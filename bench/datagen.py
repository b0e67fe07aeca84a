"""Seeded data of the benchmark's deployments, on the host.

``mnist_like`` is a copy of the generator in ``src/repro/core/datasets.py``
(at the commit that added this benchmark), kept here so that a change to
the program's own files cannot move the yardstick.  It returns the same
array as the original for the same arguments; ``tests/test_datagen.py``
in this directory pins a few values.
"""

from __future__ import annotations

import numpy as np


def mnist_like(n: int, seed: int = 0, d: int = 784, modes: int = 10,
               zdim: int = 10) -> np.ndarray:
    """Copy of ``repro.core.datasets.mnist_like``: a 10-mode mixture on a
    10-d manifold embedded in 784-d, plus a noise floor, scaled to
    [-1, 1]."""
    rng = np.random.default_rng(seed)
    zc = rng.standard_normal((modes, zdim)) * 4.0
    w = rng.dirichlet(np.ones(modes) * 0.5)
    z = zc[rng.choice(modes, size=n, p=w)] + rng.standard_normal((n, zdim))
    q, _ = np.linalg.qr(rng.standard_normal((d, zdim)))
    x = z @ q.T + 0.05 * rng.standard_normal((n, d))
    return (x / np.abs(x).max()).astype(np.float32)


GENERATORS = {"mnist_like": mnist_like}


def dataset(config: dict, n: int) -> np.ndarray:
    """The first ``n`` rows of a configuration's data set.  The data set
    is fixed by the configuration (``data_seed``), as a deployment's data
    is; a run's seed never changes it."""
    gen = GENERATORS[config["dataset"]]
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

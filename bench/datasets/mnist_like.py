"""The statistical twin of MNIST: a copy of ``mnist_like`` in
``src/repro/core/datasets.py`` at commit 5699e8e, the commit that added
this benchmark.  It returns the same array as the original for the same
arguments."""

from __future__ import annotations

import numpy as np


def generate(n: int, seed: int = 0, d: int = 784, modes: int = 10,
             zdim: int = 10) -> np.ndarray:
    """A 10-mode mixture on a 10-d manifold embedded in 784-d, plus a
    noise floor, scaled to [-1, 1]."""
    rng = np.random.default_rng(seed)
    zc = rng.standard_normal((modes, zdim)) * 4.0
    w = rng.dirichlet(np.ones(modes) * 0.5)
    z = zc[rng.choice(modes, size=n, p=w)] + rng.standard_normal((n, zdim))
    q, _ = np.linalg.qr(rng.standard_normal((d, zdim)))
    x = z @ q.T + 0.05 * rng.standard_normal((n, d))
    return (x / np.abs(x).max()).astype(np.float32)

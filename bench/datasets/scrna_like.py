"""The statistical twin of the 10x Genomics PBMC scRNA-seq counts: a copy
of ``scrna_like`` in ``src/repro/core/datasets.py`` at commit d45f0ff.
It returns the same array as the original for the same arguments."""

from __future__ import annotations

import numpy as np


def generate(n: int, seed: int = 0, d: int = 1000,
             modes: int = 8) -> np.ndarray:
    """Sparse non-negative expression counts: log1p of a zero-inflated
    gamma-Poisson mixture of ``modes`` cell types, 85% dropout."""
    rng = np.random.default_rng(seed)
    base_rate = rng.gamma(0.3, 1.0, size=(modes, d))
    z = rng.integers(0, modes, size=n)
    lam = base_rate[z] * rng.gamma(2.0, 0.5, size=(n, 1))
    counts = rng.poisson(lam).astype(np.float32)
    mask = rng.uniform(size=(n, d)) < 0.85
    counts[mask] = 0.0
    return np.log1p(counts).astype(np.float32)

"""The copied operation and byte counts."""

import pytest

from bench import intensity


def test_counts_one_tile():
    got = intensity.gstats_intensity(m=128, n=1000, d=784, k=5, tm=128)
    assert got["flops"] == 2.0 * 128 * 1000 * 784 + 10.0 * 128 * 1000
    fused = (128 * 784 + 1 * 1000 * 784) * 4 + 3 * 128 * 5 * 4
    assert got["bytes_fused"] == fused
    assert got["bytes_materialised"] == fused + 2 * 128 * 1000 * 4
    assert got["intensity_fused"] == pytest.approx(got["flops"] / fused)


def test_reference_set_is_read_once_per_tile():
    one = intensity.gstats_intensity(m=256, n=4096, d=128, tm=256)
    two = intensity.gstats_intensity(m=256, n=4096, d=128, tm=128)
    assert two["bytes_fused"] - one["bytes_fused"] == 4096 * 128 * 4

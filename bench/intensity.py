"""Operations and bytes of one g-statistics dispatch, counted from its
shapes: the yardstick a later ``<kernel>_roofline`` metric divides by.

A copy of ``benchmarks/roofline.py::gstats_intensity`` (at the commit
that added this benchmark), without its ridge point: that file's peak is
the chip's bf16 peak, which does not bound these f32 kernels.  No
metric reads this yet (PERF.md, Open questions).
"""

from __future__ import annotations


def gstats_intensity(m: int, n: int, d: int, k: int = 1, tm: int = 128,
                     dtype_bytes: int = 4) -> dict:
    """``m`` candidate arms x ``n`` references x ``d`` features, ``k``
    stat columns (1 for BUILD, k medoids for SWAP), candidate tiles of
    ``tm`` rows.  ``fused``: the streaming kernel, whose distance block
    never leaves VMEM (operands, the reference set re-read once per
    candidate tile, plus three ``[m, k]`` outputs); ``materialised``: the
    same with the ``[m, n]`` block written and read back."""
    tiles = -(-m // tm)
    kp = max(int(k), 1)
    operand_bytes = float(m * d + tiles * n * d) * dtype_bytes
    out_bytes = 3.0 * m * kp * dtype_bytes
    block_bytes = 2.0 * m * n * dtype_bytes
    flops = 2.0 * m * n * d + 10.0 * m * n
    b_fused = operand_bytes + out_bytes
    b_mat = b_fused + block_bytes
    return {"flops": flops, "bytes_fused": b_fused,
            "bytes_materialised": b_mat,
            "intensity_fused": flops / b_fused,
            "intensity_materialised": flops / b_mat}

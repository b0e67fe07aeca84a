"""Pallas TPU kernel: fused BUILD-step arm statistics.

This is the paper's hot loop (≥98 % of wall clock is distance evaluation).
One program computes, for a [TM]-tile of candidate arms against the whole
reference batch (B ≤ 512 resident in VMEM):

    d(x, y_j)                                  — MXU (or VPU for L1)
    g = (d − d_near_j) ∧ 0                     — Eq. 6 clamp, in VMEM
    Σ_j g,  Σ_j g²,  Σ_j g·g_lead              — streaming arm statistics

and writes only the three [TM] stat vectors back to HBM.  The [TM, B]
distance tile never leaves VMEM — on a v5e this turns an HBM-bound
O(n·B) tensor round-trip into three O(n) vectors (arithmetic intensity
rises from ~1 flop/byte to ~B flops/byte on the output side).

VMEM: the pipeline buffers the x tile, the resident [B, D] batch and
the vectors, and the fp32 matmul needs split copies of the x tile, all
under the compiler's 16 MiB scoped limit.  At TM=128 and a round batch
of B <= 128 the rule admits padded D up to 3840 (``ops.gstats_fit``;
compiled for a v5e chip in ``tests/test_tpu_compile.py``); wider inputs
take the jnp statistics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pairwise import EXACT, dist_tile


def _kernel(x_ref, y_ref, dn_ref, w_ref, lg_ref, sums_ref, sq_ref, cross_ref,
            *, metric):
    d = dist_tile(x_ref, y_ref, metric)        # [TM, B]
    dn = dn_ref[0, :][None, :]                            # [1, B]
    w = w_ref[0, :][None, :]
    g = jnp.where(jnp.isinf(dn), d, jnp.minimum(d - dn, 0.0)) * w
    sums_ref[0, :] = jnp.sum(g, axis=1)
    sq_ref[0, :] = jnp.sum(g * g, axis=1)
    cross_ref[0, :] = jnp.dot(g, lg_ref[0, :], precision=EXACT)


@functools.partial(jax.jit, static_argnames=("metric", "tm", "interpret"))
def build_g_kernel(x, y, dnear_b, w, lead_g, *, metric: str, tm: int = 128,
                   interpret: bool = False):
    """Pre-padded entry point.

    x: [m, d] candidate arms; y: [B, d] reference batch; dnear_b, w,
    lead_g: [B].  Returns (sums[m], sqsums[m], cross[m]).
    """
    m, d = x.shape
    b = y.shape[0]
    assert m % tm == 0 and d % 128 == 0 and b % 128 == 0, (m, d, b)
    grid = (m // tm,)
    vec = lambda: pl.BlockSpec((1, b), lambda i: (0, 0))
    out = lambda: pl.BlockSpec((1, tm), lambda i: (0, i))
    sums, sq, cross = pl.pallas_call(
        functools.partial(_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            vec(), vec(), vec(),
        ],
        out_specs=[out(), out(), out()],
        out_shape=[jax.ShapeDtypeStruct((1, m), jnp.float32)] * 3,
        interpret=interpret,
    )(x, y, dnear_b[None, :], w[None, :], lead_g[None, :])
    return sums[0], sq[0], cross[0]

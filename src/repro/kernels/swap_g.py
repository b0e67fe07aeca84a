"""Pallas TPU kernel: fused SWAP-step (FastPAM1) arm statistics.

One program computes, for a [TM]-tile of candidate points x against the
resident reference batch, the statistics of ALL k medoid-arms (m, x) at
once — the FastPAM1 sharing (Appendix 1.1) executed inside VMEM:

    d(x, y_j)                                   — MXU / VPU
    base = min(d, d₁) − d₁                      — Eq. 12 common term
    corr = min(d, d₂) − min(d, d₁)              — Eq. 12 cluster term
    Σg   [TM, K] = Σ base  ⊕  corr  @ onehot    — MXU one-hot matmul
    Σg²  [TM, K] = Σ base² ⊕ (2·base·corr + corr²) @ onehot
    Σg·g_lead [TM, K]                            — leader control variate

The [TM, B] base/corr tiles never touch HBM; only three [TM, K] stat
blocks are written.  ``onehot`` is the padding-weighted cluster-assignment
one-hot [B, K] (K padded to a lane multiple), so the reduction over C_m is
a [TM, B] x [B, K] systolic matmul.

``swap_g_from_cache_kernel`` is the BanditPAM++ PIC variant: the distance
tile is read from a resident cached column block (warm rounds and
carried-statistic repairs) instead of being recomputed — the d/base/corr
pipeline after the distance pass is byte-identical.  Its ``B`` is the
caller's block width: one bandit round-batch for warm rounds, or up to
the capped PIC ring width for the carried-statistic repair
(``ops.swap_g_stats_cached`` splits widths past its VMEM budget into
additive chunks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pairwise import EXACT, dist_tile


def swap_stats_vals(d, d1, d2, oh, lg):
    """Pure fused-stats tile math: [TM, B] distances + per-reference
    vectors -> the three [TM, K] stat blocks.  Shared by the one-shot
    kernels here and the streaming megakernel (``stream_g``), so every
    SWAP surface reduces one tile with byte-identical op order."""
    d1 = d1[None, :]
    d2 = d2[None, :]
    w = jnp.sign(jnp.sum(oh, axis=1))[None, :]            # recover {0,1} mask
    base = (jnp.minimum(d, d1) - d1) * w
    corr = jnp.minimum(d, d2) - jnp.minimum(d, d1)
    dot = lambda a: jax.lax.dot_general(
        a, oh, (((1,), (0,)), ((), ())), precision=EXACT,
        preferred_element_type=jnp.float32)
    sums = jnp.sum(base, 1, keepdims=True) + dot(corr)
    sq = jnp.sum(base * base, 1, keepdims=True) + dot(
        2.0 * base * corr + corr * corr)
    cross = (jnp.dot(base, lg, precision=EXACT)[:, None]
             + dot(corr * lg[None, :]))
    return sums, sq, cross


def _stats_from_d(d, d1_ref, d2_ref, oh_ref, lg_ref,
                  sums_ref, sq_ref, cross_ref):
    """Shared fused-stats body, given the [TM, B] distance tile ``d``."""
    sums, sq, cross = swap_stats_vals(d, d1_ref[0, :], d2_ref[0, :],
                                      oh_ref[...], lg_ref[0, :])
    sums_ref[...] = sums
    sq_ref[...] = sq
    cross_ref[...] = cross


def _kernel(x_ref, y_ref, d1_ref, d2_ref, oh_ref, lg_ref,
            sums_ref, sq_ref, cross_ref, *, metric):
    d = dist_tile(x_ref, y_ref, metric)        # [TM, B]
    _stats_from_d(d, d1_ref, d2_ref, oh_ref, lg_ref,
                  sums_ref, sq_ref, cross_ref)


def _kernel_cached(d_ref, d1_ref, d2_ref, oh_ref, lg_ref,
                   sums_ref, sq_ref, cross_ref):
    # BanditPAM++ PIC warm path: the distance tile comes straight from the
    # resident cache block — no MXU distance pass, stats only.
    _stats_from_d(d_ref[...], d1_ref, d2_ref, oh_ref, lg_ref,
                  sums_ref, sq_ref, cross_ref)


@functools.partial(jax.jit, static_argnames=("metric", "tm", "interpret"))
def swap_g_kernel(x, y, d1_b, d2_b, onehot_w, lead_g, *, metric: str,
                  tm: int = 128, interpret: bool = False):
    """Pre-padded entry point.

    x: [m, d]; y: [B, d]; d1_b, d2_b, lead_g: [B]; onehot_w: [B, K]
    (cluster one-hot with the {0,1} padding weights folded in; lead_g must
    also be w-masked).  Returns (sums, sqsums, cross) each [m, K] — arm
    (med j, cand i) lives at [i, j]; the ops wrapper transposes/crops.
    """
    m, d = x.shape
    b, kp = onehot_w.shape
    assert m % tm == 0 and d % 128 == 0 and b % 128 == 0 and kp % 128 == 0
    grid = (m // tm,)
    vec = lambda: pl.BlockSpec((1, b), lambda i: (0, 0))
    out = lambda: pl.BlockSpec((tm, kp), lambda i: (i, 0))
    sums, sq, cross = pl.pallas_call(
        functools.partial(_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            vec(), vec(),
            pl.BlockSpec((b, kp), lambda i: (0, 0)),
            vec(),
        ],
        out_specs=[out(), out(), out()],
        out_shape=[jax.ShapeDtypeStruct((m, kp), jnp.float32)] * 3,
        interpret=interpret,
    )(x, y, d1_b[None, :], d2_b[None, :], onehot_w, lead_g[None, :])
    return sums, sq, cross


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def swap_g_from_cache_kernel(dxy, d1_b, d2_b, onehot_w, lead_g, *,
                             tm: int = 128, interpret: bool = False):
    """PIC warm-round / carry-repair entry point: identical statistics to
    ``swap_g_kernel`` but fed from a resident cached distance block.

    dxy: [m, B] precomputed distances (a slice of the PIC column cache);
    d1_b, d2_b, lead_g: [B]; onehot_w: [B, K] (w-folded, lead_g w-masked).
    Returns (sums, sqsums, cross) each [m, K].
    """
    m, b = dxy.shape
    kp = onehot_w.shape[1]
    assert m % tm == 0 and b % 128 == 0 and kp % 128 == 0
    grid = (m // tm,)
    vec = lambda: pl.BlockSpec((1, b), lambda i: (0, 0))
    out = lambda: pl.BlockSpec((tm, kp), lambda i: (i, 0))
    sums, sq, cross = pl.pallas_call(
        _kernel_cached,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, b), lambda i: (i, 0)),
            vec(), vec(),
            pl.BlockSpec((b, kp), lambda i: (0, 0)),
            vec(),
        ],
        out_specs=[out(), out(), out()],
        out_shape=[jax.ShapeDtypeStruct((m, kp), jnp.float32)] * 3,
        interpret=interpret,
    )(dxy, d1_b[None, :], d2_b[None, :], onehot_w, lead_g[None, :])
    return sums, sq, cross

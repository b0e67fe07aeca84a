"""Pallas TPU kernels: streaming g-stats megakernel family.

The one-shot kernels (``build_g``, ``swap_g``) hold the WHOLE reference
batch resident in VMEM, which caps B at a round-batch.  These kernels
lift that cap the way memory-efficient attention does for KV length: the
grid's minor axis **walks reference tiles** (``tb`` columns each) while
the output block for the current candidate tile stays VMEM-resident, so
per-arm statistics and top-2 reductions accumulate **online** and the
``[m, r]`` distance matrix never exists in HBM at any r — one dispatch
covers the full reference set (r = n for the exact fallback passes).

Pipelining: ``pallas_call`` double-buffers every operand whose BlockSpec
index changes along the grid — here the [tb, d] reference tile and its
per-reference vectors — so the next tile's DMA overlaps the current
tile's MXU/VPU work; no hand-rolled ``make_async_copy`` needed.  The
output BlockSpecs are invariant along the minor axis, which keeps the
accumulator block in VMEM across the whole reference walk (one HBM
write-back per candidate tile).

Accumulation-order contract (bit-parity with the jnp engine paths): a
tile's stats are reduced with the exact op order of the one-shot kernels
(row-sum / one-hot ``dot_general`` over the tb axis), then tiles are
added in walk order.  With ``tb`` pinned to the engine's historical
``_EXACT_CHUNK`` (see ``repro.core.tuning.REF_TILE``) this reproduces
the chunked ``lax.scan`` ledgers bit-for-bit; see docs/design.md #8.

VMEM: both operand tiles and every per-reference vector are
double-buffered, the [tm, tb] temporaries and the fp32 matmul's operand
splits need compiler scratch, all under the compiler's 16 MiB scoped
limit.  At tb=512 and k <= 128 the rule (``vmem.gstats_bytes``) admits
padded d up to 1920 at tm=128, 1152 at tm=256 and 512 at tm=512, each
compiled for a v5e chip.  The tuner (``repro.core.tuning``) picks the
largest tm that fits; past d=1920 the stats backend takes the jnp walk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pairwise import EXACT, dist_tile
from .swap_g import swap_stats_vals


def _build_kernel(x_ref, y_ref, dn_ref, w_ref, lg_ref,
                  sums_ref, sq_ref, cross_ref, *, metric):
    j = pl.program_id(1)
    d = dist_tile(x_ref, y_ref, metric)         # [TM, TB]
    dn = dn_ref[0, :][None, :]
    w = w_ref[0, :][None, :]
    g = jnp.where(jnp.isinf(dn), d, jnp.minimum(d - dn, 0.0)) * w

    @pl.when(j == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)
        cross_ref[...] = jnp.zeros_like(cross_ref)

    sums_ref[0, :] += jnp.sum(g, axis=1)
    sq_ref[0, :] += jnp.sum(g * g, axis=1)
    cross_ref[0, :] += jnp.dot(g, lg_ref[0, :], precision=EXACT)


@functools.partial(jax.jit,
                   static_argnames=("metric", "tm", "tb", "interpret"))
def stream_build_g_kernel(x, y, dnear, w, lead_g, *, metric: str,
                          tm: int = 128, tb: int = 512,
                          interpret: bool = False):
    """Pre-padded streaming BUILD stats over the full reference set.

    x: [m, d] candidate arms; y: [r, d] references (r unbounded — the
    grid walks it in ``tb``-tiles); dnear, w, lead_g: [r].  Returns
    (sums[m], sqsums[m], cross[m]) — Σ over ALL r references.
    """
    m, d = x.shape
    r = y.shape[0]
    assert m % tm == 0 and r % tb == 0 and d % 128 == 0, (m, r, d)
    grid = (m // tm, r // tb)
    vec = lambda: pl.BlockSpec((1, tb), lambda i, j: (0, j))
    out = lambda: pl.BlockSpec((1, tm), lambda i, j: (0, i))
    sums, sq, cross = pl.pallas_call(
        functools.partial(_build_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tb, d), lambda i, j: (j, 0)),
            vec(), vec(), vec(),
        ],
        out_specs=[out(), out(), out()],
        out_shape=[jax.ShapeDtypeStruct((1, m), jnp.float32)] * 3,
        interpret=interpret,
    )(x, y, dnear[None, :], w[None, :], lead_g[None, :])
    return sums[0], sq[0], cross[0]


def _swap_kernel(x_ref, y_ref, d1_ref, d2_ref, oh_ref, lg_ref,
                 sums_ref, sq_ref, cross_ref, *, metric):
    j = pl.program_id(1)
    d = dist_tile(x_ref, y_ref, metric)         # [TM, TB]
    sums, sq, cross = swap_stats_vals(d, d1_ref[0, :], d2_ref[0, :],
                                      oh_ref[...], lg_ref[0, :])

    @pl.when(j == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)
        cross_ref[...] = jnp.zeros_like(cross_ref)

    sums_ref[...] += sums
    sq_ref[...] += sq
    cross_ref[...] += cross


@functools.partial(jax.jit,
                   static_argnames=("metric", "tm", "tb", "interpret"))
def stream_swap_g_kernel(x, y, d1, d2, onehot_w, lead_g, *, metric: str,
                         tm: int = 128, tb: int = 512,
                         interpret: bool = False):
    """Pre-padded streaming SWAP (FastPAM1) stats over the full reference
    set: same per-tile math as ``swap_g_kernel`` (via
    ``swap_stats_vals``), accumulated along the reference walk.

    x: [m, d]; y: [r, d]; d1, d2, lead_g: [r]; onehot_w: [r, K]
    (w-folded; lead_g w-masked).  Returns (sums, sqsums, cross), [m, K].
    """
    m, d = x.shape
    r, kp = onehot_w.shape
    assert m % tm == 0 and r % tb == 0 and d % 128 == 0 and kp % 128 == 0
    grid = (m // tm, r // tb)
    vec = lambda: pl.BlockSpec((1, tb), lambda i, j: (0, j))
    out = lambda: pl.BlockSpec((tm, kp), lambda i, j: (i, 0))
    sums, sq, cross = pl.pallas_call(
        functools.partial(_swap_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tb, d), lambda i, j: (j, 0)),
            vec(), vec(),
            pl.BlockSpec((tb, kp), lambda i, j: (j, 0)),
            vec(),
        ],
        out_specs=[out(), out(), out()],
        out_shape=[jax.ShapeDtypeStruct((m, kp), jnp.float32)] * 3,
        interpret=interpret,
    )(x, y, d1[None, :], d2[None, :], onehot_w, lead_g[None, :])
    return sums, sq, cross


def _top2_kernel(x_ref, med_ref, mask_ref, d1_ref, d2_ref, a_ref, *,
                 metric):
    d = dist_tile(x_ref, med_ref, metric)       # [TM, KP]
    kp = d.shape[1]
    d = jnp.where(mask_ref[0, :][None, :] > 0.0, d, jnp.inf)
    d1 = jnp.min(d, axis=1)
    # First index attaining the min, via a min-reduce over masked column
    # ids (Mosaic-safe; matches jnp.argmin's first-occurrence tie rule).
    col = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    a = jnp.min(jnp.where(d == d1[:, None], col, kp), axis=1)
    d2 = jnp.min(jnp.where(col == a[:, None], jnp.inf, d), axis=1)
    d1_ref[0, :] = d1
    d2_ref[0, :] = d2
    a_ref[0, :] = a


@functools.partial(jax.jit, static_argnames=("metric", "tm", "interpret"))
def stream_top2_kernel(x, med, kmask, *, metric: str, tm: int = 128,
                       interpret: bool = False):
    """Pre-padded streaming nearest/second-nearest reduction.

    x: [n, d] points (the grid walks candidate tiles); med: [KP, d]
    medoid rows (resident — k is small); kmask: [KP] {0,1} marking real
    medoid columns.  Returns (d1[n], d2[n], assign[n] int32); the
    [n, k] distance matrix never exists in HBM.
    """
    n, d = x.shape
    kp = med.shape[0]
    assert n % tm == 0 and d % 128 == 0 and kp % 128 == 0, (n, d, kp)
    grid = (n // tm,)
    out = lambda dt: pl.BlockSpec((1, tm), lambda i: (0, i))
    d1, d2, a = pl.pallas_call(
        functools.partial(_top2_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i: (i, 0)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),
            pl.BlockSpec((1, kp), lambda i: (0, 0)),
        ],
        out_specs=[out(jnp.float32), out(jnp.float32), out(jnp.int32)],
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.int32)],
        interpret=interpret,
    )(x, med, kmask[None, :])
    return d1[0], d2[0], a[0]

"""Pallas TPU kernel: tiled pairwise dissimilarity.

TPU-native tiling (docs/design.md hardware adaptation #3):

* grid = (m/TM, r/TR); each program owns one [TM, TR] output tile.
* Feature dim D is resident in VMEM per tile (padded to a lane multiple of
  128).  What binds is the compiler's 16 MiB *scoped* VMEM limit, and the
  pipeline double-buffers both operand tiles: at TM=TR=128 a v5e compile
  needs 16.12 MiB at D=8192 and fits at D=7936 (``ops.DK_MAX``, from
  ``vmem.pairwise_bytes``).  For larger D the ops wrapper splits the
  feature axis into ``dk``-column chunks and accumulates the additive
  per-chunk core (squared distances / abs-sums / dot products) across
  kernel calls (``ops.pairwise_distance``).
* MXU metrics (l2 / l2sq / cosine) are one ``dot_general`` with rank-1
  corrections: the [TM, D]x[D, TR] contraction is exactly the systolic
  array's shape (multiples of 128 on every matmul dim).
* L1 has no matmul form; it runs on the VPU, one feature at a time into
  the [TM, TR] accumulator (``_l1_tile``), so no [TM, TR, chunk]
  broadcast temp exists at any tile size.

Zero-padding is free for every metric here: padded features contribute 0
to dots/norms/abs-sums, and padded rows/cols are cropped by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MXU_METRICS = ("l2", "l2sq", "cosine")
LANES = 128

# Precision of every in-kernel f32 matmul.  Mosaic's default is one bf16
# pass: on a v5e it moved l2 distances by up to ~1e-1 at d=784 and flipped
# nearest-medoid labels.  HIGHEST contracts in fp32.
EXACT = jax.lax.Precision.HIGHEST
L1_GROUP = 8


def _l1_tile(x_ref, y_ref) -> jnp.ndarray:
    """Σ_f |x[:, f] − y[:, f]| as a [TM, TR] tile, on the VPU.

    The feature axis is walked in lane-aligned 128-column chunks read from
    the refs (Mosaic slices refs at dynamic 128-multiples, never values).
    A chunk's y block is transposed once, so feature f is one x column
    (lane broadcast) against one y row (sublane broadcast).  The chunk is
    consumed in groups of ``L1_GROUP`` features: both operands are
    rotated by the same dynamic shift (x along lanes, yᵀ along sublanes)
    to bring the group to index 0, and the group is unrolled with static
    slices.  The same rotation on both sides pairs equal features in
    either rotation direction.  A fully unrolled chunk needs megabytes of
    compiler scratch; this walk keeps the [TM, TR] accumulator the only
    tile-sized temp."""
    tm, d = x_ref.shape
    tr = y_ref.shape[0]

    def chunk(c, acc):
        lo = pl.multiple_of(c * LANES, LANES)
        xc = x_ref[:, pl.ds(lo, LANES)].astype(jnp.float32)      # [TM, 128]
        yc = y_ref[:, pl.ds(lo, LANES)].astype(jnp.float32).T    # [128, TR]

        def group(g, acc):
            shift = (LANES - g * L1_GROUP) % LANES
            xg = pltpu.roll(xc, shift, 1)
            yg = pltpu.roll(yc, shift, 0)
            # tracecheck: ignore[TRC002] -- trace-constant unroll of the
            # L1_GROUP static feature slices; the group walk is a fori_loop.
            for f in range(L1_GROUP):
                acc = acc + jnp.abs(xg[:, f:f + 1] - yg[f:f + 1, :])
            return acc

        return jax.lax.fori_loop(0, LANES // L1_GROUP, group, acc)

    return jax.lax.fori_loop(0, d // LANES, chunk,
                             jnp.zeros((tm, tr), jnp.float32))


def dist_tile(x_ref, y_ref, metric: str) -> jnp.ndarray:
    """In-VMEM distance tile from refs [TM, D] x [TR, D] -> [TM, TR]
    (f32 accum; D a lane multiple).

    ``"dot"`` is an internal metric (the raw MXU contraction) used by the
    ops wrapper to accumulate cosine similarities across feature chunks
    when D exceeds the VMEM tile budget; it is not registry-facing.
    """
    if metric == "l1":
        return _l1_tile(x_ref, y_ref)
    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    if metric in ("l2", "l2sq", "cosine", "dot"):
        xy = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                 precision=EXACT,
                                 preferred_element_type=jnp.float32)
        if metric == "dot":
            return xy
        if metric == "cosine":
            xn = jax.lax.rsqrt(jnp.maximum(jnp.sum(x * x, -1), 1e-30))
            yn = jax.lax.rsqrt(jnp.maximum(jnp.sum(y * y, -1), 1e-30))
            return 1.0 - xy * xn[:, None] * yn[None, :]
        d = jnp.maximum(jnp.sum(x * x, -1)[:, None]
                        + jnp.sum(y * y, -1)[None, :] - 2.0 * xy, 0.0)
        return jnp.sqrt(d) if metric == "l2" else d
    raise ValueError(f"unknown metric {metric}")


def _kernel(x_ref, y_ref, o_ref, *, metric):
    o_ref[...] = dist_tile(x_ref, y_ref, metric)


@functools.partial(jax.jit,
                   static_argnames=("metric", "tm", "tr", "interpret"))
def pairwise_kernel(x: jnp.ndarray, y: jnp.ndarray, *, metric: str,
                    tm: int = 128, tr: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Pre-padded entry point: shapes must already be tile-aligned."""
    m, d = x.shape
    r = y.shape[0]
    assert m % tm == 0 and r % tr == 0 and d % 128 == 0, (m, r, d)
    grid = (m // tm, r // tr)
    return pl.pallas_call(
        functools.partial(_kernel, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tr, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tr), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, r), jnp.float32),
        interpret=interpret,
    )(x, y)

"""VMEM accounting behind every Pallas dispatch rule.

Mosaic grants each kernel a *scoped* VMEM allocation of 16 MiB by
default (the TPU v5e compile error reads "Scoped allocation with size
20.05M and limit 16.00M").  The physical VMEM is larger, but the scoped
limit is what a kernel without ``vmem_limit_bytes`` is held to, and no
kernel here raises it.  Three things fill it:

* the pipeline's buffers: two for every BlockSpec'd operand and output
  (the double buffer that overlaps the next tile's DMA with the current
  tile's compute), each laid out in (8, 128) f32 tiles.  This alone is
  the compiler's figure for ``pairwise_kernel`` at tm=tr=128, d=8192
  with more than one reference tile (16.12 MiB);
* compiler scratch for the [tm, tb] temporaries (distance tile,
  accumulator, g terms and the one-hot products): up to 4.6 tiles' worth
  beyond the buffers (l1 SWAP at tm=128, tb=512).  ``TEMP_TILES``
  covers it;
* the fp32-precision matmul (``pairwise.EXACT``) of a [tm, d] tile
  against a [tb=512, d] reference tile keeps split copies of the
  [tm, d] operand: 16 bytes per element (``MXU_SPLIT_BYTES``), linear in
  tm and d across the compiles (l2 at tm=128..512, d=512..2048).

The figures come from compiling for a described v5e with the limit
lowered until the compile is refused.

The tile tuner (``repro.core.tuning``), the feature-chunk width
``ops.DK_MAX`` and the stats backend's jnp fallback
(``repro.core.engine.PallasStatsBackend``) all decide through ``fits()``
on these counts, so a shape the compiler would refuse takes the jnp path
instead.  ``tests/test_tpu_compile.py`` compiles the largest admitted
shapes for a described v5e chip.
"""

from __future__ import annotations

SCOPED_VMEM_BYTES = 16 * 1024 * 1024
LANES = 128
SUBLANES = 8
TEMP_TILES = 5
MXU_SPLIT_BYTES = 16


def _up(v: int, mult: int) -> int:
    return -(-max(v, 1) // mult) * mult


def block_bytes(*blocks) -> int:
    """Pipeline bytes of f32 ``(rows, cols)`` blocks: each is padded to
    whole (8, 128) tiles and double-buffered."""
    return 2 * 4 * sum(_up(r, SUBLANES) * _up(c, LANES) for r, c in blocks)


def _temps(rows: int, cols: int) -> int:
    return TEMP_TILES * 4 * _up(rows, SUBLANES) * _up(cols, LANES)


def pairwise_bytes(tm: int, tr: int, d: int) -> int:
    """``pairwise_kernel``: x [tm, d], y [tr, d] -> out [tm, tr]."""
    return block_bytes((tm, d), (tr, d), (tm, tr)) + _temps(tm, tr)


def gstats_bytes(tm: int, tb: int, d: int, k: int) -> int:
    """The g-statistics kernels, one-shot (``tb`` = the resident batch
    B) or streaming (``tb`` = the reference tile), at feature width ``d``
    and ``k`` medoids: the largest of the BUILD, SWAP and top-2
    footprints, so one rule admits the family.  The wrappers pad the
    batch and the medoid count to whole lanes, and so does this count.

    BUILD: x, y and three [1, tb] vectors in, three [1, tm] sums out.
    SWAP: x, y, three vectors and the [tb, kp] one-hot in, three
    [tm, kp] blocks out.  top-2: x and the [kp, d] medoid rows in."""
    tb, kp = _up(tb, LANES), _up(k, LANES)
    build = block_bytes((tm, d), (tb, d), *[(1, tb)] * 3, *[(1, tm)] * 3)
    swap = block_bytes((tm, d), (tb, d), *[(1, tb)] * 3, (tb, kp),
                       *[(tm, kp)] * 3)
    top2 = block_bytes((tm, d), (kp, d), (1, kp), *[(1, tm)] * 3)
    split = MXU_SPLIT_BYTES * _up(tm, SUBLANES) * _up(d, LANES)
    return max(max(build, swap) + _temps(tm, tb) + split,
               top2 + _temps(tm, kp))


def cached_swap_bytes(tm: int, b: int, k: int) -> int:
    """``swap_g_from_cache_kernel``: dxy [tm, b] and the [b, kp] one-hot
    in, three [tm, kp] blocks out (b and k padded to whole lanes)."""
    b, kp = _up(b, LANES), _up(k, LANES)
    return (block_bytes((tm, b), *[(1, b)] * 3, (b, kp), *[(tm, kp)] * 3)
            + _temps(tm, b))


def fits(nbytes: int) -> bool:
    return nbytes <= SCOPED_VMEM_BYTES


def max_feature_width(bytes_at, limit: int = 1 << 16) -> int:
    """Largest lane-multiple d <= ``limit`` with ``fits(bytes_at(d))``
    (0 if none)."""
    return max((d for d in range(LANES, limit + 1, LANES)
                if fits(bytes_at(d))), default=0)

"""StatsBackend — the one seam between the bandit drivers and the
g-statistics compute paths.

Before this layer existed the Pallas kernels (``repro.kernels.ops``) were
exercised only by tests and benchmarks while the real fit path ran
pure-jnp statistics.  ``StatsBackend`` unifies the three g-statistics
paths behind one contract so the drivers are backend-agnostic and the
kernels power the actual fit:

* ``"jnp"``    — jit'd XLA math (``_build_g`` / ``_swap_batch_stats``);
  works for every registered metric, including user callables and
  ``"precomputed"``.
* ``"pallas"`` — the fused TPU kernels (``kernels.ops.build_g_stats`` /
  ``swap_g_stats`` for fresh rounds, ``swap_g_stats_cached`` for rounds
  served from the device-resident PIC column cache).  Kernel-implemented
  metrics only; interpret-mode on CPU.
* cache-served — both backends read warm rounds from a resident distance
  block via the ``*_from_d`` methods (the Pallas side uses the dedicated
  cached-stats kernel for SWAP; BUILD stats from a resident block are
  distance-free vector math and share the jnp formula).

Selection is by name (``backend="auto" | "pallas" | "jnp"`` on
``BanditPAM`` / ``repro.api.KMedoids``); the registry is open so an
out-of-tree backend (a GPU Triton port, say) is one ``register_stats_backend``
call.  ``"auto"`` picks Pallas for kernel-implemented metrics on a real
accelerator and jnp everywhere else — interpret-mode Pallas on CPU is
correct but slow, so it must be requested explicitly.

``FitContext`` carries every piece of per-fit state (RNG key, the fixed
reference permutation, the device-resident PIC cache buffer and its
high-water mark) that historically leaked onto the ``BanditPAM`` instance,
making ``fit`` re-entrant.

The shared g-statistics math (``_build_g``, ``_swap_terms``,
``_swap_batch_stats``), the medoid cache, and the exact loss live here so
``core.banditpam``, ``core.pam``, and ``core.distributed`` all draw from
one definition.  Backends are collective-free by contract: the sharded
driver (``core.distributed``) calls ``pairwise`` + ``*_stats_from_d`` on
shard-local blocks inside ``shard_map`` and composes the cross-shard
``psum`` itself, so every registered backend reaches the distributed path
unchanged.  The single-device PIC programs call ``align_rows(data)`` once
per program, outside every loop, and read each fresh column block as
``pairwise(aligned, aligned[idx], rows=n)``; unaligned operands (the
sharded fit's shard blocks, predict's row chunks) are padded inside
``pairwise`` as before.  See docs/design.md for the numbered hardware
adaptations.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .distances import EXACT, get_metric, pairwise
from .pic_cache import (PicCache, cache_read_or_write, carry_valid,  # noqa: F401
                        fresh_positions, make_cache,  # noqa: F401
                        resolve_cache_rounds)  # noqa: F401
from .tuning import (REF_TILE, TileConfig, observe as observe_tiles,  # noqa: F401
                     resolve_tile_config)  # noqa: F401

_EXACT_CHUNK = 512  # reference-chunk size for exact fallback passes

# The streaming kernels' reference-tile width must share the exact-pass
# chunk boundaries — that alignment is what makes the tile-walk
# accumulation order reproduce the chunked-scan ledgers bit-for-bit
# (docs/design.md #8).
assert REF_TILE == _EXACT_CHUNK, (REF_TILE, _EXACT_CHUNK)


# ---------------------------------------------------------------------------
# Shared cache / loss helpers — streaming forms (design.md #8): the
# distance block is reduced per row-tile as it is produced, so no
# ``[n, k]`` / ``[n, chunk]`` matrix is ever resident.  Small inputs
# (n <= one tile) take the single-block branch, which is byte-identical
# to the historical materialised path.
# ---------------------------------------------------------------------------

def _stream_rows(data: jnp.ndarray, tile: int, fn, init, axis: int = -1):
    """Walk ``data`` ([n, d], n > tile) in row tiles, writing each
    ``fn(xt)`` strip (a pytree of arrays with a ``tile``-long ``axis``)
    into the matching ``init`` output buffer at the tile's row offset.

    No padded copy of the input is ever formed — each step slices one
    [tile, d] strip, so the whole walk's temp footprint is one strip plus
    one [tile, ·] result block.  The final tile is realigned to end at
    row n; rows in the overlap are recomputed and rewritten with the
    same bytes (every registered metric is row-independent, and the
    per-row reduction shape is tile-offset-invariant)."""
    n = data.shape[0]
    nt = -(-n // tile)

    def body(i, out):
        start = jnp.minimum(i * tile, n - tile)
        xt = jax.lax.dynamic_slice_in_dim(data, start, tile, 0)
        return jax.tree_util.tree_map(
            lambda o, r: jax.lax.dynamic_update_slice_in_dim(
                o, r, start, axis % o.ndim),
            out, fn(xt))

    return jax.lax.fori_loop(0, nt, body, init)


def _top2_block(dmat: jnp.ndarray):
    """Single-pass nearest/second-nearest reduction of one distance
    block: no ``.at[].set(inf)`` copy — the runner-up min masks the
    winner's column with ``where`` instead of duplicating the block."""
    assign = jnp.argmin(dmat, axis=1).astype(jnp.int32)
    d1 = jnp.min(dmat, axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, dmat.shape, 1)
    d2 = jnp.min(jnp.where(cols == assign[:, None], jnp.inf, dmat), axis=1)
    return d1, d2, assign


def _stream_top2_jnp(x, med_pts, *, metric: str, tile: int = _EXACT_CHUNK):
    """Streaming top-2 over row tiles: ``[n, d]`` x ``[k, d]`` ->
    (d1, d2, assign), [n] each, with only one [tile, k] block live."""
    n = x.shape[0]
    fn = get_metric(metric)
    if n <= tile:
        return _top2_block(fn(x, med_pts))
    init = (jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32))
    return _stream_rows(x, tile, lambda xt: _top2_block(fn(xt, med_pts)),
                        init)


@functools.partial(jax.jit, static_argnames=("metric", "tile"))
def medoid_cache(data: jnp.ndarray, medoids: jnp.ndarray, *, metric: str,
                 tile: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """d1 (nearest-medoid dist), d2 (second nearest), assignment; [n] each.
    One streaming top-2 pass — the hottest per-iteration helper holds a
    single [tile, k] block instead of ``[n, k]`` plus an inf-masked copy."""
    # tracecheck: ignore[TRC001] -- `tile` is in static_argnames: a host int
    # at trace time, never a traced value.
    t = _EXACT_CHUNK if tile is None else int(tile)
    with jax.named_scope("top2"):
        return _stream_top2_jnp(data, data[medoids], metric=metric, tile=t)


@functools.partial(jax.jit, static_argnames=("metric", "tile"))
def total_loss(data: jnp.ndarray, medoids: jnp.ndarray, *, metric: str,
               w=None, tile: Optional[int] = None) -> jnp.ndarray:
    """Sum of nearest-medoid dissimilarities.  ``w`` (optional bool [n])
    masks rows out of the sum — the batched multi-fit path scores padded
    datasets with it (``jnp.where``, not a multiply, so NaN rows from
    degenerate pad points cannot poison the loss).  The nearest-distance
    vector is reduced tile-by-tile; the final sum runs over the intact
    [n] vector so summation order (and the ledger's loss bits) match the
    historical materialised path."""
    # tracecheck: ignore[TRC001] -- `tile` is in static_argnames: a host int
    # at trace time, never a traced value.
    t = _EXACT_CHUNK if tile is None else int(tile)
    n = data.shape[0]
    fn = get_metric(metric)
    with jax.named_scope("top2"):
        med = data[medoids]
        if n <= t:
            dmin = jnp.min(fn(data, med), axis=1)
        else:
            dmin = _stream_rows(data, t,
                                lambda xt: jnp.min(fn(xt, med), axis=1),
                                jnp.zeros((n,), jnp.float32))
        if w is None:
            return jnp.sum(dmin)
        return jnp.sum(jnp.where(w, dmin, 0.0))


def _ref_chunks(n_ref: int, chunk: int) -> Tuple[np.ndarray, np.ndarray]:
    """Static index/weight tiling of [0, n_ref) into equal chunks."""
    n_chunks = -(-n_ref // chunk)
    idx = np.arange(n_chunks * chunk)
    w = (idx < n_ref).astype(np.float32)
    idx = np.minimum(idx, n_ref - 1)
    return idx.reshape(n_chunks, chunk), w.reshape(n_chunks, chunk)


def exact_build_means(be, data, dnear, *, metric: str) -> jnp.ndarray:
    """Exact BUILD objective over the full reference set (Algorithm 1
    lines 13–15 fallback): per-arm mean g, [n].  Routed through the
    backend's streaming g-stats contract — one dispatch that walks
    ``_EXACT_CHUNK``-aligned reference tiles and accumulates online, so
    the resident block stays bounded and no ``[n, chunk]`` distance
    matrix is ever materialised — the one definition shared by the
    single-device and sharded drivers."""
    n = data.shape[0]
    return be.stream_build_sums(data, dnear, metric=metric) / n


def exact_swap_means(be, data, d1, d2, assign, k: int, *, metric: str
                     ) -> jnp.ndarray:
    """Exact SWAP objective over the flattened (medoid, candidate) arm
    set: per-arm mean g, [k·n]; same streaming backend-routed form as
    :func:`exact_build_means`."""
    n = data.shape[0]
    return be.stream_swap_sums(data, d1, d2, assign, k, metric=metric) / n


def _stream_build_sums_jnp(data, dnear, *, metric: str,
                           tile: int = _EXACT_CHUNK) -> jnp.ndarray:
    """jnp streaming BUILD g-sums, Σ_y g(x, y) over the whole dataset,
    [n].  The reference walk is the historical ``_ref_chunks`` scan (same
    tile boundaries, same per-tile op order, tiles added in walk order),
    row-tiled by ``lax.map`` so only a [tile, tile] block is live; inputs
    with n <= one tile take the single-row-block branch, which is the
    pre-streaming graph verbatim."""
    n = data.shape[0]
    idx_np, w_np = _ref_chunks(n, tile)
    idx, w = jnp.asarray(idx_np), jnp.asarray(w_np)
    fn = get_metric(metric)

    def walk(xt):
        def body(acc, iw):
            i, w_i = iw
            g = _build_g(fn(xt, data[i]), dnear[i]) * w_i[None, :]
            return acc + jnp.sum(g, axis=1), None
        out, _ = jax.lax.scan(body, jnp.zeros((xt.shape[0],), jnp.float32),
                              (idx, w))
        return out

    if n <= tile:
        return walk(data)
    return _stream_rows(data, tile, walk, jnp.zeros((n,), jnp.float32))


def _stream_swap_sums_jnp(data, d1, d2, assign, k: int, *, metric: str,
                          tile: int = _EXACT_CHUNK) -> jnp.ndarray:
    """jnp streaming SWAP g-sums over the flattened (medoid, candidate)
    arm set, [k·n]; same walk discipline as
    :func:`_stream_build_sums_jnp`."""
    n = data.shape[0]
    idx_np, w_np = _ref_chunks(n, tile)
    idx, w = jnp.asarray(idx_np), jnp.asarray(w_np)
    fn = get_metric(metric)

    def walk(xt):
        m = xt.shape[0]

        def body(acc, iw):
            i, w_i = iw
            s, _ = _swap_batch_stats(fn(xt, data[i]), d1[i], d2[i],
                                     assign[i], w_i, k)
            return acc + s, None
        out, _ = jax.lax.scan(body, jnp.zeros((k * m,), jnp.float32),
                              (idx, w))
        return out.reshape(k, m)

    if n <= tile:
        return walk(data).reshape(-1)
    return _stream_rows(data, tile, walk,
                        jnp.zeros((k, n), jnp.float32)).reshape(-1)


def stream_columns(be, data, refs, *, metric: str,
                   tile: int = _EXACT_CHUNK) -> jnp.ndarray:
    """Produce an ``[n, C]`` cache column block in row strips.

    The block itself IS the product here (warm/PIC caches store it), so
    its HBM footprint cannot be streamed away — but its *production* can
    be: each strip holds one [tile, C] distance block at a time instead
    of tracing a single [n, C] pairwise pass whose intermediates (e.g.
    the l2 cross-term matmul) scale with n.  Row strips are pinned to the
    ``_EXACT_CHUNK`` grid like every other walk (docs/design.md #8)."""
    n = data.shape[0]
    if n <= tile:
        return be.pairwise(data, refs, metric=metric)
    return _stream_rows(data, tile,
                        lambda xt: be.pairwise(xt, refs, metric=metric),
                        jnp.zeros((n, refs.shape[0]), jnp.float32), axis=0)


# ---------------------------------------------------------------------------
# g-statistics math (the Eq. 6 / Eq. 12 forms shared by every caller)
# ---------------------------------------------------------------------------

def _build_g(dxy: jnp.ndarray, dnear_b: jnp.ndarray) -> jnp.ndarray:
    """Eq. 6 with the Eq. 4 special-case for the first assignment."""
    dn = dnear_b[None, :]
    return jnp.where(jnp.isinf(dn), dxy, jnp.minimum(dxy - dn, 0.0))


def _swap_terms(dxy: jnp.ndarray, d1_b: jnp.ndarray, d2_b: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    base = jnp.minimum(dxy, d1_b[None, :]) - d1_b[None, :]
    corr = jnp.minimum(dxy, d2_b[None, :]) - jnp.minimum(dxy, d1_b[None, :])
    return base, corr


def _swap_batch_stats(dxy, d1_b, d2_b, a_b, w, k, lead=None):
    """Per-arm (m·n + x) sums, square-sums (and optional leader cross-sums)
    over a reference batch.

    g = base + 1[assign==m]·corr  ⇒
      Σ g        = Σ base + Σ_{y∈C_m} corr
      Σ g²       = Σ base² + Σ_{y∈C_m} (2·base·corr + corr²)
      Σ g·g_lead = Σ base·g_lead + Σ_{y∈C_m} corr·g_lead
    The C_m-restricted sums are one-hot matmuls (MXU-shaped).
    """
    n = dxy.shape[0]
    base, corr = _swap_terms(dxy, d1_b, d2_b)
    # weights are {0,1} (padding mask), so w² = w and masking base once is
    # enough for every product below.
    base = base * w[None, :]
    onehot = jax.nn.one_hot(a_b, k, dtype=dxy.dtype) * w[:, None]   # [B, k]
    mm = lambda a, b: jnp.matmul(a, b, precision=EXACT)
    sums = jnp.sum(base, axis=1)[None, :] + mm(corr, onehot).T      # [k, n]
    sq_base = jnp.sum(base * base, axis=1)
    sq_cross = 2.0 * base * corr + corr * corr
    sqsums = sq_base[None, :] + mm(sq_cross, onehot).T
    if lead is None:
        return sums.reshape(-1), sqsums.reshape(-1)
    m_l, x_l = lead // n, lead % n
    g_lead = base[x_l] + onehot[:, m_l] * corr[x_l]                 # [B], w-masked
    cross = (mm(base, g_lead)[None, :]
             + mm(corr * g_lead[None, :], onehot).T)
    return sums.reshape(-1), sqsums.reshape(-1), cross.reshape(-1)


# ---------------------------------------------------------------------------
# Device-resident PIC cache primitives: extracted to
# ``repro.core.pic_cache`` (bounded width + round recycling) and
# re-exported from the top of this module for the drivers and historical
# importers.
def counted_dispatch(fn, dispatches: Dict[str, int], phase: str):
    """Wrap a compiled phase callable so every driver-level dispatch is
    COUNTED at the call site — ``FitReport.dispatches_by_phase`` is a
    measurement, not a self-reported constant.  A refactor that
    re-introduces a per-selection host loop shows up in the recorded
    count (and trips ``benchmarks/distributed_bench.py``'s single-
    dispatch BUILD assertion) instead of being silently papered over."""
    def call(*args, **kw):
        dispatches[phase] = dispatches.get(phase, 0) + 1
        return fn(*args, **kw)
    return call


@contextlib.contextmanager
def span(name: str):
    """Host span ``repro.<name>`` on the profiler's clock
    (``jax.profiler.TraceAnnotation``): it lands in a device trace beside
    the device's operations, and costs about a microsecond when no trace
    is being taken.  Usable as a decorator."""
    with jax.profiler.TraceAnnotation("repro." + name):
        yield


@contextlib.contextmanager
def phase(name: str, wall_by_phase: Dict[str, float],
          key: Optional[str] = None):
    """:func:`span` that also adds the phase's host wall-clock seconds to
    ``wall_by_phase[key]`` (``FitReport.wall_by_phase``; ``key`` defaults
    to ``name``), so a phase entered several times in one fit reports
    its sum.  The wall ends where the body ends, so a phase that must
    include its device work ends its body in ``block_until_ready`` or a
    host read."""
    key = name if key is None else key
    t0 = time.perf_counter()
    with span(name):
        yield
    wall_by_phase[key] = (wall_by_phase.get(key, 0.0)
                          + time.perf_counter() - t0)


def host_read(x):
    """The sanctioned device→host read point for the drivers.

    Every ledger/convergence read in ``fit`` funnels through this one
    explicit ``jax.device_get`` so the whole fit runs clean under
    ``jax.transfer_guard("disallow")`` (which bans only *implicit*
    transfers): scattered ``float()``/``np.asarray()`` syncs would each
    be a separate, invisible transfer — and TRC001 findings if they
    leaked into jit-reachable code.  Accepts any pytree; returns numpy
    leaves (Python scalars pass through unchanged).  Traced as the span
    ``repro.host_read``.
    """
    with span("host_read"):
        return jax.device_get(x)


@contextlib.contextmanager
def host_stage(reason: str):
    """Sanctioned host→device staging span (input upload, RNG chain
    head, context construction).  The ``reason`` is mandatory, mirroring
    the tracecheck suppression policy: every allowed transfer window
    names why it exists, and names the span ``repro.stage(<reason>)``.
    Inside the span the transfer guard is relaxed to "allow"; everything
    outside stays at the caller's level."""
    if not reason:
        raise ValueError("host_stage requires a non-empty reason")
    with span(f"stage({reason})"), jax.transfer_guard("allow"):
        yield


# ---------------------------------------------------------------------------
# StatsBackend implementations
# ---------------------------------------------------------------------------

class JnpStatsBackend:
    """Pure-XLA statistics: any registered metric, any device."""

    name = "jnp"

    def align_rows(self, data):
        """The data set as ``pairwise`` reads it best: unchanged here."""
        return data

    def pairwise(self, x, y, *, metric, rows=None):
        """``[m, d] × [r, d] → [m, r]``; ``rows`` crops the output to the
        logical rows of an ``align_rows`` operand (all of them here)."""
        # The jit'd entrypoint: inlined when already inside a trace, and
        # compiled (not op-by-op eager) for eager callers like the
        # chunked predict path.
        return pairwise(x, y, metric=metric)[:rows]

    # -- BUILD ----------------------------------------------------------
    def build_stats(self, data, ref_idx, dnear_b, w, lead, *, metric):
        """Fused fresh-round BUILD stats: (Σg, Σg², Σg·g_lead), [n] each."""
        return self.build_stats_from_d(
            get_metric(metric)(data, data[ref_idx]), dnear_b, w, lead)

    def build_stats_from_d(self, dxy, dnear_b, w, lead):
        """BUILD stats from a resident distance block (cache-served).
        ``lead=None`` skips the leader cross-sum (baseline="none")."""
        g = _build_g(dxy, dnear_b) * w[None, :]                     # [n, B]
        cross = (jnp.zeros((g.shape[0],), g.dtype) if lead is None
                 else jnp.matmul(g, g[lead], precision=EXACT))
        return jnp.sum(g, axis=1), jnp.sum(g * g, axis=1), cross

    # -- SWAP (FastPAM1 fused form) -------------------------------------
    def swap_stats(self, data, ref_idx, d1_b, d2_b, assign_b, w, k, lead,
                   *, metric):
        """Fused fresh-round SWAP stats, flattened over the (m, x) arm set."""
        return self.swap_stats_from_d(get_metric(metric)(data, data[ref_idx]),
                                      d1_b, d2_b, assign_b, w, k, lead)

    def swap_stats_from_d(self, dxy, d1_b, d2_b, assign_b, w, k, lead):
        """SWAP stats from a resident distance block (cache-served)."""
        if lead is None:
            s, q = _swap_batch_stats(dxy, d1_b, d2_b, assign_b, w, k)
            return s, q, jnp.zeros_like(s)
        return _swap_batch_stats(dxy, d1_b, d2_b, assign_b, w, k, lead=lead)

    # -- streaming contract (exact fallback / top-2 serving passes) ------
    def stream_build_sums(self, data, dnear, *, metric):
        """Σ_y g(x, y) over the WHOLE dataset, [n] — no [n, chunk] block."""
        return _stream_build_sums_jnp(data, dnear, metric=metric)

    def stream_swap_sums(self, data, d1, d2, assign, k, *, metric):
        """Flattened (medoid, candidate) arm g-sums over the whole
        dataset, [k·n] — no [n, chunk] block."""
        return _stream_swap_sums_jnp(data, d1, d2, assign, k, metric=metric)

    def top2(self, x, med_pts, *, metric):
        """(d1, d2, assign) of x against medoid rows, [n] each, without
        materialising the [n, k] distance matrix."""
        return _stream_top2_jnp(x, med_pts, metric=metric)


class PallasStatsBackend:
    """Fused Pallas kernels (``repro.kernels``): the distance tile and the
    arm statistics are computed in one VMEM-resident pass; cache-served
    SWAP rounds hit the dedicated ``swap_g_from_cache_kernel``.

    The leader control variate (``lead`` is an arm index) needs the leader
    arm's g-row over the batch — the kernels take it as an input instead
    of materialising the full g block — so it is derived from one extra
    pairwise row: a ledger-neutral O(B) add, since evaluation accounting
    lives in ``adaptive_search``'s ``count_fn``, not in the stats path.
    With ``lead=None`` (baseline="none", the default) that extra kernel
    launch is skipped entirely and the cross output is zeros.
    """

    name = "pallas"

    def __init__(self, interpret: Optional[bool] = None, tm: int = 128):
        self.interpret = interpret
        self.tm = tm

    def align_rows(self, data):
        """Zero-pad ``data`` to the pairwise kernel's row tile and
        feature lanes (``ops.align_rows``), once per program: every
        fresh PIC round then gathers its references from it, and
        ``pairwise(aligned, aligned[idx], rows=n)`` pads nothing."""
        from repro.kernels import ops
        if data.shape[-1] > ops.DK_MAX:
            # Past one kernel pass the wrapper re-chunks the features on
            # every call anyway, and takes cosine's row norms from the
            # operand as given, whose last bits zero columns would move.
            return data
        with jax.named_scope("prep"):
            return ops.align_rows(data)

    def pairwise(self, x, y, *, metric, rows=None):
        from repro.kernels import ops
        return ops.pairwise_distance(x, y, metric=metric, rows=rows,
                                     interpret=self.interpret)

    # -- BUILD ----------------------------------------------------------
    def build_stats(self, data, ref_idx, dnear_b, w, lead, *, metric):
        from repro.kernels import ops
        if not ops.gstats_fit(self.tm, ref_idx.shape[0], data.shape[1], 1):
            return JnpStatsBackend.build_stats(self, data, ref_idx, dnear_b,
                                               w, lead, metric=metric)
        y = data[ref_idx]
        if lead is None:
            lead_g = None
        else:
            dl = ops.pairwise_distance(data[lead][None, :], y, metric=metric,
                                       interpret=self.interpret)[0]
            lead_g = jnp.where(jnp.isinf(dnear_b), dl,
                               jnp.minimum(dl - dnear_b, 0.0)) * w
        return ops.build_g_stats(data, y, dnear_b, w, lead_g, metric=metric,
                                 tm=self.tm, interpret=self.interpret)

    def build_stats_from_d(self, dxy, dnear_b, w, lead):
        # No distance pass to fuse — cache-served BUILD stats are plain
        # vector math, shared with the jnp backend.
        return JnpStatsBackend.build_stats_from_d(self, dxy, dnear_b, w,
                                                  lead)

    # -- SWAP -----------------------------------------------------------
    def _swap_lead_g(self, dl, d1_b, d2_b, assign_b, m_l):
        base_l = jnp.minimum(dl, d1_b) - d1_b
        corr_l = jnp.minimum(dl, d2_b) - jnp.minimum(dl, d1_b)
        return base_l + (assign_b == m_l).astype(dl.dtype) * corr_l

    def swap_stats(self, data, ref_idx, d1_b, d2_b, assign_b, w, k, lead,
                   *, metric):
        from repro.kernels import ops
        if not ops.gstats_fit(self.tm, ref_idx.shape[0], data.shape[1], k):
            return JnpStatsBackend.swap_stats(self, data, ref_idx, d1_b, d2_b,
                                              assign_b, w, k, lead,
                                              metric=metric)
        n = data.shape[0]
        y = data[ref_idx]
        if lead is None:
            lead_g = None
        else:
            m_l, x_l = lead // n, lead % n
            dl = ops.pairwise_distance(data[x_l][None, :], y, metric=metric,
                                       interpret=self.interpret)[0]
            lead_g = self._swap_lead_g(dl, d1_b, d2_b, assign_b, m_l)
        s, q, c = ops.swap_g_stats(data, y, d1_b, d2_b, assign_b, w, k,
                                   lead_g, metric=metric, tm=self.tm,
                                   interpret=self.interpret)
        return s.reshape(-1), q.reshape(-1), c.reshape(-1)

    def swap_stats_from_d(self, dxy, d1_b, d2_b, assign_b, w, k, lead):
        from repro.kernels import ops
        if not ops.cached_fit(self.tm, dxy.shape[1], k):
            return JnpStatsBackend.swap_stats_from_d(self, dxy, d1_b, d2_b,
                                                     assign_b, w, k, lead)
        n = dxy.shape[0]
        if lead is None:
            lead_g = None
        else:
            m_l, x_l = lead // n, lead % n
            lead_g = self._swap_lead_g(dxy[x_l], d1_b, d2_b, assign_b, m_l)
        s, q, c = ops.swap_g_stats_cached(dxy, d1_b, d2_b, assign_b, w, k,
                                          lead_g, tm=self.tm,
                                          interpret=self.interpret)
        return s.reshape(-1), q.reshape(-1), c.reshape(-1)

    # -- streaming contract ---------------------------------------------
    def _stream_ok(self, n: int, d: int, k: int, metric: str) -> bool:
        # The streaming kernels hold both operand tiles feature-resident
        # (g-statistics are not additive across feature chunks): inputs
        # whose tiles the tuner cannot fit in scoped VMEM (kernels/vmem.py)
        # take the tiled jnp walk.
        from repro.kernels import ops
        cfg = resolve_tile_config(n, d, k, backend="pallas")
        return (metric in ops.KERNEL_METRICS
                and ops.gstats_fit(cfg.tm, cfg.tb, d, k))

    def stream_build_sums(self, data, dnear, *, metric):
        from repro.kernels import ops
        if not self._stream_ok(*data.shape, 1, metric):
            return _stream_build_sums_jnp(data, dnear, metric=metric)
        s, _, _ = ops.stream_build_g_stats(data, data, dnear, metric=metric,
                                           interpret=self.interpret)
        return s

    def stream_swap_sums(self, data, d1, d2, assign, k, *, metric):
        from repro.kernels import ops
        if not self._stream_ok(*data.shape, k, metric):
            return _stream_swap_sums_jnp(data, d1, d2, assign, k,
                                         metric=metric)
        s, _, _ = ops.stream_swap_g_stats(data, data, d1, d2, assign, None,
                                          k, metric=metric,
                                          interpret=self.interpret)
        return s.reshape(-1)

    def top2(self, x, med_pts, *, metric):
        from repro.kernels import ops
        if not self._stream_ok(*x.shape, med_pts.shape[0], metric):
            return _stream_top2_jnp(x, med_pts, metric=metric)
        return ops.stream_top2(x, med_pts, metric=metric,
                               interpret=self.interpret)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_BACKENDS: Dict[str, Any] = {}


def register_stats_backend(name: str, backend) -> None:
    """Register a stats backend instance under ``name`` (see the module
    docstring for the method contract)."""
    _BACKENDS[name] = backend


def get_stats_backend(name: str):
    if name not in _BACKENDS:
        raise KeyError(f"unknown stats backend {name!r}; "
                       f"have {sorted(_BACKENDS)}")
    return _BACKENDS[name]


def available_stats_backends():
    return sorted(_BACKENDS)


register_stats_backend("jnp", JnpStatsBackend())
register_stats_backend("pallas", PallasStatsBackend())


def resolve_stats_backend(backend: Optional[str], metric: str) -> str:
    """Normalise a ``backend=`` argument to a registered backend name.

    ``"auto"`` (or None) routes kernel-implemented metrics through Pallas
    only on TPU — the kernels are written against TPU tiling (128-lane
    padding, MXU-shaped contractions) and are not validated under other
    lowerings; interpret-mode Pallas on CPU is correct but orders of
    magnitude slower.  Everything else falls back to jnp (XLA compiles
    that well on every backend).  An explicit ``"pallas"`` with a metric
    the kernels don't implement is an error.
    """
    from repro.kernels.ops import KERNEL_METRICS
    if backend in (None, "auto"):
        if metric in KERNEL_METRICS and jax.default_backend() == "tpu":
            return "pallas"
        return "jnp"
    get_stats_backend(backend)  # raises KeyError for unknown names
    if backend == "pallas" and metric not in KERNEL_METRICS:
        raise ValueError(f"metric {metric!r} has no Pallas kernel "
                         f"(kernel metrics: {list(KERNEL_METRICS)}); "
                         f"use backend='jnp'")
    return backend


# ---------------------------------------------------------------------------
# FitContext — per-fit state, explicit instead of instance-resident
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FitContext:
    """Everything one ``BanditPAM.fit`` call threads between phases.

    Historically this state (``_pic`` / ``_perm`` / ``_dwarm`` /
    ``_free_rounds``) leaked onto the estimator instance, so a second
    ``fit`` inherited stale cache state and pre-fit attribute access
    crashed.  Holding it here makes the engine re-entrant: the instance
    carries configuration only.

    ``mode`` selects the cache regime:

    * ``"none"`` — no distance cache; every round is fresh.
    * ``"warm"`` — paper App 2.2: a fixed permutation plus an upfront warm
      block of its first ``free_rounds`` column batches (static; no
      write-through).
    * ``"pic"``  — BanditPAM++ permutation-invariant cache, device-resident
      and width-bounded: ``cache`` is a :class:`~repro.core.pic_cache.PicCache`
      ring of ``cache_width`` columns with round recycling; searches
      write fresh blocks through from inside the bandit loop, and rounds
      whose slot was recycled fall back to fresh recomputation.

    ``batch > 0`` marks a BATCHED context (``BanditPAM.fit_batch``): the
    array fields gain a leading ``[batch]`` fit axis (``cache.cols`` is
    ``[batch, n, W·B]``, ``perm_idx`` is ``[batch, W·B]``, ...) and the
    batch-only fields below are populated — per-fit validity masks for
    padded ragged datasets, per-fit logical n, per-fit ``log(1/δ)`` terms
    (δ depends on n, which is ragged), and the pre-tiled per-search
    reference-permutation layouts that the single-fit path would generate
    inside the search from its RNG chain (they must be data, not trace
    constants, once n is ragged).
    """

    mode: str                              # "none" | "warm" | "pic"
    backend: str                           # registered stats-backend name
    perm: Optional[jnp.ndarray] = None     # [n] fixed reference permutation
    perm_idx: Optional[jnp.ndarray] = None  # [W·B] tiled permutation prefix
    perm_w: Optional[jnp.ndarray] = None   # [W·B] {0,1} padding weights
    cache: Optional[PicCache] = None       # bounded PIC column ring ("pic");
    #                                        capacity W = cols.shape[1] // B
    dwarm: Optional[jnp.ndarray] = None    # [n, C] warm columns ("warm")
    free_rounds: int = 0                   # static warm-block rounds ("warm")
    warm_medoids: Optional[jnp.ndarray] = None  # [k] int32 BUILD bypass:
    #   when set, ``fit`` skips BUILD entirely and SWAP starts from these
    #   indices (the serving layer's incremental-refit entry; build ledger
    #   records 0 and the BUILD subkeys are never drawn)
    # -- batched multi-fit fields (leading [batch] axis when batch > 0) --
    batch: int = 0                         # fit count; 0 = single-fit context
    valid: Optional[jnp.ndarray] = None    # [batch, n] bool row-validity
    n_valid: Optional[jnp.ndarray] = None  # [batch] int32 logical n per fit
    log_build: Optional[jnp.ndarray] = None   # [batch] f32 log(1/δ_build)
    log_swap: Optional[jnp.ndarray] = None    # [batch] f32 log(1/δ_swap)
    spidx_build: Optional[jnp.ndarray] = None  # [batch, k, R·B] or
    #                                            [batch, R·B] search layouts
    spidx_swap: Optional[jnp.ndarray] = None   # [batch, T, R·B] or
    #                                            [batch, R·B]
    spw: Optional[jnp.ndarray] = None      # [batch, R·B] {0,1} weights

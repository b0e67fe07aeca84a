"""Jit'd public wrappers around the Pallas kernels: padding, cropping,
interpret-mode selection, and TPU deployment hooks.

Everything a wrapper adds around its ``pallas_call`` (operand padding,
one-hot, output crop) runs in the device scope ``prep``, so a device
trace tells the wrappers' own cost from the kernels'.

On this container (CPU) the kernels execute with ``interpret=True`` — the
kernel bodies run in Python for correctness validation; on a real TPU
backend the same code lowers to Mosaic.  ``install()`` re-registers the
``repro.core.distances`` metrics to the kernel-backed implementations for
TPU deployment.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import build_g as _build_g
from . import pairwise as _pairwise
from . import stream_g as _stream_g
from . import swap_g as _swap_g
from . import vmem as _vmem


# Metrics implemented by the Pallas kernels (the registry-facing names;
# the repro.api predict path and the repro.core.engine stats-backend
# resolution both key off this tuple).
KERNEL_METRICS = ("l2", "l2sq", "l1", "cosine")

# Feature-axis tile budget: the widest lane-multiple d whose 128x128
# pairwise tile fits the scoped VMEM limit (``vmem``).  Larger feature
# dims are split into dk-chunks whose additive cores (squared distances /
# abs-sums / dot products) accumulate exactly.
DK_MAX = _vmem.max_feature_width(lambda d: _vmem.pairwise_bytes(128, 128, d))


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(a: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def align_rows(x: jnp.ndarray, *, tm: int = 128) -> jnp.ndarray:
    """Zero-pad ``x`` (``[..., m, d]``) to whole ``tm``-row tiles and
    128-lane features: the ``x`` operand exactly as
    ``pairwise_distance`` pads it.  A program that reads the same data
    set in many kernel calls aligns it once and passes the result (with
    ``rows=m``), so no call pads it again."""
    return _pad_to(_pad_to(x, -1, 128), -2, tm)


def pairwise_distance(x: jnp.ndarray, y: jnp.ndarray, metric: str = "l2",
                      *, rows: Optional[int] = None, tm: int = 128,
                      tr: int = 128, dk: int = DK_MAX,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """[m, d] x [r, d] -> [m, r] via the tiled Pallas kernel.

    ``x`` may come aligned by ``align_rows`` (and ``y`` gathered from
    it): ``rows`` is then the logical row count ``m`` the output is
    cropped to, and the padding is already in place, so none is added.
    Within one kernel pass (``d <= dk``) the kernel sees the same tiles
    either way, so the result is bit-identical; past it, cosine's row
    norms also sum the zero columns, which can move their last bits.

    Feature dims up to ``dk`` are VMEM-resident in one kernel pass.  Past
    that budget the feature axis is split into ``dk``-column chunks and the
    *additive* per-chunk core is accumulated across kernel calls — exact
    for every metric here: squared distances and abs-sums are sums over
    feature chunks, ``l2`` is the root of the accumulated ``l2sq``, and
    ``cosine`` accumulates the raw MXU dot product (internal ``"dot"``
    tile) with the O((m+r)·d) row norms computed outside the kernel.
    """
    if interpret is None:
        interpret = _default_interpret()
    m = x.shape[0] if rows is None else rows
    r, d = y.shape[0], x.shape[1]
    if dk % 128 != 0:
        raise ValueError(f"dk must be a lane multiple of 128, got {dk}")
    with jax.named_scope("prep"):
        xp = align_rows(x, tm=tm)
        yp = _pad_to(_pad_to(y, 1, 128), 0, tr)
    if d <= dk:
        out = _pairwise.pairwise_kernel(xp, yp, metric=metric, tm=tm, tr=tr,
                                        interpret=interpret)
        with jax.named_scope("prep"):
            return out[:m, :r]

    core = {"l2": "l2sq", "l2sq": "l2sq", "l1": "l1",
            "cosine": "dot"}.get(metric)
    if core is None:
        raise ValueError(f"unknown metric {metric!r}")
    if xp.shape[1] <= dk:
        acc = _pairwise.pairwise_kernel(xp, yp, metric=core, tm=tm, tr=tr,
                                        interpret=interpret)
    else:
        # Wide features accumulate through a lax loop with an additive
        # carry (one kernel trace regardless of d), instead of the
        # historical Python loop that unrolled one kernel call per
        # dk-chunk into the jit.  The lane padding moves to the last
        # chunk's tail, where the zero features leave every partial sum
        # untouched.
        with jax.named_scope("prep"):
            xp = _pad_to(xp, 1, dk)
            yp = _pad_to(yp, 1, dk)
        n_ch = xp.shape[1] // dk

        def body(c, acc):
            with jax.named_scope("prep"):
                xs = jax.lax.dynamic_slice_in_dim(xp, c * dk, dk, 1)
                ys = jax.lax.dynamic_slice_in_dim(yp, c * dk, dk, 1)
            return acc + _pairwise.pairwise_kernel(
                xs, ys, metric=core, tm=tm, tr=tr, interpret=interpret)

        acc = jax.lax.fori_loop(
            0, n_ch, body,
            jnp.zeros((xp.shape[0], yp.shape[0]), jnp.float32))
    with jax.named_scope("prep"):
        acc = acc[:m, :r]
        if metric == "l2":
            return jnp.sqrt(acc)
        if metric == "cosine":
            xf = x[:m].astype(jnp.float32)
            yf = y.astype(jnp.float32)
            xn = jax.lax.rsqrt(jnp.maximum(jnp.sum(xf * xf, -1), 1e-30))
            yn = jax.lax.rsqrt(jnp.maximum(jnp.sum(yf * yf, -1), 1e-30))
            return 1.0 - acc * xn[:, None] * yn[None, :]
        return acc


def build_g_stats(x: jnp.ndarray, y: jnp.ndarray, dnear_b: jnp.ndarray,
                  w: jnp.ndarray, lead_g: Optional[jnp.ndarray] = None,
                  *, metric: str = "l2", tm: int = 128,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused BUILD statistics: (Σg, Σg², Σg·g_lead) per arm, [m] each."""
    if interpret is None:
        interpret = _default_interpret()
    m = x.shape[0]
    with jax.named_scope("prep"):
        if lead_g is None:
            lead_g = jnp.zeros_like(dnear_b)
        xp = _pad_to(_pad_to(x, 1, 128), 0, tm)
        yp = _pad_to(_pad_to(y, 1, 128), 0, 128)
        pad_b = yp.shape[0] - y.shape[0]
        dn = jnp.pad(dnear_b, (0, pad_b))
        wp = jnp.pad(w, (0, pad_b))               # padded refs get weight 0
        lg = jnp.pad(lead_g, (0, pad_b))
    sums, sq, cross = _build_g.build_g_kernel(xp, yp, dn, wp, lg,
                                              metric=metric, tm=tm,
                                              interpret=interpret)
    with jax.named_scope("prep"):
        return sums[:m], sq[:m], cross[:m]


def _swap_prep(d1_b, d2_b, assign_b, w, k, lead_g, pad_b, row_mult=128):
    """Shared SWAP-kernel operand prep: pad the per-reference vectors,
    w-mask the leader row, w-fold + lane-pad the cluster one-hot.
    ``row_mult`` is the reference-axis tile the one-hot must align to
    (128 for the batch-resident kernels, ``tb`` for the streaming walk)."""
    if lead_g is None:
        lead_g = jnp.zeros_like(d1_b)
    d1 = jnp.pad(d1_b, (0, pad_b))
    d2 = jnp.pad(d2_b, (0, pad_b))
    lg = jnp.pad(lead_g * w, (0, pad_b))      # leader row must be w-masked
    oh = jax.nn.one_hot(assign_b, k, dtype=jnp.float32) * w[:, None]
    oh = _pad_to(_pad_to(oh, 1, 128), 0, row_mult)
    return d1, d2, oh, lg


def swap_g_stats(x: jnp.ndarray, y: jnp.ndarray, d1_b: jnp.ndarray,
                 d2_b: jnp.ndarray, assign_b: jnp.ndarray, w: jnp.ndarray,
                 k: int, lead_g: Optional[jnp.ndarray] = None,
                 *, metric: str = "l2", tm: int = 128,
                 interpret: Optional[bool] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused SWAP (FastPAM1) statistics: (Σg, Σg², Σg·g_lead), each [k, m]
    for the flattened arm set (medoid m_i, candidate x_j)."""
    if interpret is None:
        interpret = _default_interpret()
    m = x.shape[0]
    with jax.named_scope("prep"):
        xp = _pad_to(_pad_to(x, 1, 128), 0, tm)
        yp = _pad_to(_pad_to(y, 1, 128), 0, 128)
        d1, d2, oh, lg = _swap_prep(d1_b, d2_b, assign_b, w, k, lead_g,
                                    yp.shape[0] - y.shape[0])
    sums, sq, cross = _swap_g.swap_g_kernel(xp, yp, d1, d2, oh, lg,
                                            metric=metric, tm=tm,
                                            interpret=interpret)
    with jax.named_scope("prep"):
        return sums[:m, :k].T, sq[:m, :k].T, cross[:m, :k].T


# Reference-axis tile budget for the cache-served SWAP kernel: one
# [128, CACHE_B_MAX] f32 distance tile is 1 MiB of VMEM.  The carried-
# statistic repair feeds the kernel the WHOLE capped PIC ring width
# (cache_width columns) as one batch; widths past the budget are split
# into additive chunks — Σg / Σg² / Σg·g_lead are sums over reference
# positions, so per-chunk results accumulate exactly.
CACHE_B_MAX = 2048


def swap_g_stats_cached(dxy: jnp.ndarray, d1_b: jnp.ndarray,
                        d2_b: jnp.ndarray, assign_b: jnp.ndarray,
                        w: jnp.ndarray, k: int,
                        lead_g: Optional[jnp.ndarray] = None,
                        *, tm: int = 128,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused SWAP statistics served from a PIC distance-cache block.

    Same contract as ``swap_g_stats`` but ``dxy`` ([m, B]) is a precomputed
    slice of the permutation-invariant column cache — this is the kernel
    behind warm (cached) bandit rounds and the carried-statistic repair of
    ``BanditPAM(reuse="pic")`` on TPU: zero fresh distance work, stats only.
    ``B`` may be the full capped cache width (``cache_width`` columns);
    past ``CACHE_B_MAX`` the reference axis is split into additive chunks
    so the resident tile stays VMEM-bounded.
    """
    if interpret is None:
        interpret = _default_interpret()
    m, b = dxy.shape

    def one(dxy_c, d1_c, d2_c, a_c, w_c, lg_c):
        with jax.named_scope("prep"):
            dp = _pad_to(_pad_to(dxy_c, 1, 128), 0, tm)
            d1, d2, oh, lg = _swap_prep(d1_c, d2_c, a_c, w_c, k, lg_c,
                                        dp.shape[1] - dxy_c.shape[1])
        return _swap_g.swap_g_from_cache_kernel(dp, d1, d2, oh, lg, tm=tm,
                                                interpret=interpret)

    if b <= CACHE_B_MAX:
        sums, sq, cross = one(dxy, d1_b, d2_b, assign_b, w, lead_g)
    else:
        sums = sq = cross = None
        # tracecheck: ignore[TRC002] -- trace-constant chunking over the
        # static cache width b (shape-derived); each chunk is one kernel
        # launch and the += merge order is fixed by the range().
        for lo in range(0, b, CACHE_B_MAX):
            hi = min(lo + CACHE_B_MAX, b)
            with jax.named_scope("prep"):
                chunk = (dxy[:, lo:hi], d1_b[lo:hi], d2_b[lo:hi],
                         assign_b[lo:hi], w[lo:hi],
                         None if lead_g is None else lead_g[lo:hi])
            part = one(*chunk)
            with jax.named_scope("prep"):
                if sums is None:
                    sums, sq, cross = part
                else:
                    sums, sq, cross = (sums + part[0], sq + part[1],
                                       cross + part[2])
    with jax.named_scope("prep"):
        return sums[:m, :k].T, sq[:m, :k].T, cross[:m, :k].T


# ---------------------------------------------------------------------------
# Streaming g-stats megakernel wrappers (kernels/stream_g.py)
# ---------------------------------------------------------------------------

def _stream_tiles(n, d, k, tm, tb):
    """Resolve (tm, tb) through the backend-aware tuner when unset.
    Lazy import: ``repro.core.tuning`` is dependency-free, but going
    through the package keeps kernel import standalone."""
    from repro.core import tuning
    if tm is None or tb is None:
        cfg = tuning.resolve_tile_config(n, d, k, backend="pallas")
        tm = cfg.tm if tm is None else tm
        tb = cfg.tb if tb is None else tb
    return tm, tb


def gstats_fit(tm: int, tb: int, d: int, k: int) -> bool:
    """Whether a g-statistics kernel (one-shot with a ``tb``-row batch,
    or streaming with ``tb``-row reference tiles) fits the scoped VMEM
    limit at feature width ``d`` and ``k`` medoids (``vmem.gstats_bytes``)."""
    return _vmem.fits(_vmem.gstats_bytes(tm, tb, d, k))


def cached_fit(tm: int, b: int, k: int) -> bool:
    """Whether the cache-served SWAP kernel fits at block width ``b``
    (split into ``CACHE_B_MAX`` chunks past it)."""
    return _vmem.fits(_vmem.cached_swap_bytes(tm, min(b, CACHE_B_MAX), k))


def _check_stream(tm: int, tb: int, d: int, k: int, what: str) -> None:
    if not gstats_fit(tm, tb, d, k):
        raise ValueError(
            f"{what} holds both operand tiles feature-resident; tm={tm}, "
            f"tb={tb}, d={d}, k={k} exceed the dk budget of "
            f"{_vmem.SCOPED_VMEM_BYTES} bytes of scoped VMEM (g-statistics "
            f"are not additive across feature chunks) — use the tiled jnp "
            f"streaming path for wider features")


def stream_build_g_stats(x: jnp.ndarray, yref: jnp.ndarray,
                         dnear: jnp.ndarray, w: Optional[jnp.ndarray] = None,
                         lead_g: Optional[jnp.ndarray] = None,
                         *, metric: str = "l2", tm: Optional[int] = None,
                         tb: Optional[int] = None,
                         interpret: Optional[bool] = None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Streaming BUILD statistics over an UNBOUNDED reference set: one
    dispatch walks ``yref`` in ``tb``-tiles and accumulates (Σg, Σg²,
    Σg·g_lead) online — the exact-fallback pass (yref = the whole
    dataset) without any ``[m, chunk]`` HBM block."""
    if interpret is None:
        interpret = _default_interpret()
    m, d = x.shape
    r = yref.shape[0]
    tm, tb = _stream_tiles(m, d, 1, tm, tb)
    _check_stream(tm, tb, d, 1, "stream_build_g_stats")
    with jax.named_scope("prep"):
        if w is None:
            w = jnp.ones((r,), jnp.float32)
        if lead_g is None:
            lead_g = jnp.zeros((r,), jnp.float32)
        xp = _pad_to(_pad_to(x, 1, 128), 0, tm)
        yp = _pad_to(_pad_to(yref, 1, 128), 0, tb)
        pad_r = yp.shape[0] - r
        dn = jnp.pad(dnear, (0, pad_r))
        wp = jnp.pad(w, (0, pad_r))               # padded refs get weight 0
        lg = jnp.pad(lead_g, (0, pad_r))
    sums, sq, cross = _stream_g.stream_build_g_kernel(
        xp, yp, dn, wp, lg, metric=metric, tm=tm, tb=tb, interpret=interpret)
    with jax.named_scope("prep"):
        return sums[:m], sq[:m], cross[:m]


def stream_swap_g_stats(x: jnp.ndarray, yref: jnp.ndarray, d1: jnp.ndarray,
                        d2: jnp.ndarray, assign: jnp.ndarray,
                        w: Optional[jnp.ndarray] = None, k: int = 1,
                        lead_g: Optional[jnp.ndarray] = None,
                        *, metric: str = "l2", tm: Optional[int] = None,
                        tb: Optional[int] = None,
                        interpret: Optional[bool] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Streaming SWAP (FastPAM1) statistics over an unbounded reference
    set; same contract as ``swap_g_stats`` ([k, m] outputs) with the
    reference walk replacing the resident batch."""
    if interpret is None:
        interpret = _default_interpret()
    m, d = x.shape
    r = yref.shape[0]
    tm, tb = _stream_tiles(m, d, k, tm, tb)
    _check_stream(tm, tb, d, k, "stream_swap_g_stats")
    with jax.named_scope("prep"):
        if w is None:
            w = jnp.ones((r,), jnp.float32)
        xp = _pad_to(_pad_to(x, 1, 128), 0, tm)
        yp = _pad_to(_pad_to(yref, 1, 128), 0, tb)
        d1p, d2p, oh, lg = _swap_prep(d1, d2, assign, w, k, lead_g,
                                      yp.shape[0] - r, row_mult=tb)
    sums, sq, cross = _stream_g.stream_swap_g_kernel(
        xp, yp, d1p, d2p, oh, lg, metric=metric, tm=tm, tb=tb,
        interpret=interpret)
    with jax.named_scope("prep"):
        return sums[:m, :k].T, sq[:m, :k].T, cross[:m, :k].T


def stream_top2(x: jnp.ndarray, med_pts: jnp.ndarray, *, metric: str = "l2",
                tm: Optional[int] = None,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Streaming nearest/second-nearest medoid reduction: ``[n, d]``
    points × ``[k, d]`` medoid rows → (d1[n], d2[n], assign[n] int32)
    with no ``[n, k]`` HBM block — the loss / assignment / serving pass.
    Ties resolve to the lowest medoid index (jnp.argmin's rule)."""
    if interpret is None:
        interpret = _default_interpret()
    n, d = x.shape
    k = med_pts.shape[0]
    tm, tb = _stream_tiles(n, d, k, tm, None)
    _check_stream(tm, tb, d, k, "stream_top2")
    with jax.named_scope("prep"):
        xp = _pad_to(_pad_to(x, 1, 128), 0, tm)
        mp = _pad_to(_pad_to(med_pts, 1, 128), 0, 128)
        kmask = jnp.pad(jnp.ones((k,), jnp.float32), (0, mp.shape[0] - k))
    d1, d2, a = _stream_g.stream_top2_kernel(xp, mp, kmask, metric=metric,
                                             tm=tm, interpret=interpret)
    with jax.named_scope("prep"):
        return d1[:n], d2[:n], a[:n]


def install(metrics=("l2", "l2sq", "cosine", "l1")) -> None:
    """Re-register core distance metrics to the kernel-backed paths
    (TPU deployment hook; a no-op semantically — same math)."""
    from repro.core import distances as core_distances

    for name in metrics:
        core_distances.register_metric(
            name, functools.partial(pairwise_distance, metric=name))

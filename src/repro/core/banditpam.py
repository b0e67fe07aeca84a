"""BanditPAM: the paper's algorithm — BUILD + SWAP driven by Algorithm 1.

Faithful to the paper:

* BUILD (Eq. 6): arms = candidate points, ``g_x(y) = (d(x,y) − d_near(y)) ∧ 0``
  against the cached nearest-medoid distance; the first assignment uses
  ``g_x(y) = d(x,y)`` (Eq. 4 with an empty medoid set).
* SWAP (Eq. 7 + Appendix Eq. 12 / FastPAM1): arms = (medoid m, candidate x)
  pairs.  One distance ``d(x,y)`` serves all k arms ``(·, x)`` via the cached
  ``d₁, d₂`` and cluster assignment — evaluated as a base term plus a
  one-hot matmul correction (``engine._swap_batch_stats`` / the fused
  Pallas kernels), which never materialises a ``[k, n, B]`` tensor.
* σ_x re-estimated from the first batch of every Algorithm 1 call (Eq. 11,
  Appendix 1.2), B = 100, δ = 1/(1000·|S_tar|) by default (§3.2).
* SWAP iterations repeat until the chosen swap no longer improves the exact
  loss, with a hard cap T (paper §4 Remark 1).

Device-resident driver (docs/design.md hardware adaptation #5): the
g-statistics are computed through a pluggable :class:`~repro.core.engine`
``StatsBackend`` (``backend="auto"/"pallas"/"jnp"``), and the control flow
is structured so the hot path never leaves the accelerator:

* BUILD is ONE jit dispatch: a ``lax.fori_loop`` over the k medoid
  selections with the ``adaptive_search`` while-loop inside and
  ``d_near`` / the medoid mask as loop carry — no per-medoid host sync,
  no per-medoid retrace.
* Each SWAP iteration is ONE fused device step (medoid-cache refresh +
  carried-moment repair + bandit search + candidate loss); only the
  accept/converge decision reads a scalar back on host.
* The BanditPAM++ PIC cache is a bounded-width device ring
  (``repro.core.pic_cache``, ``cache_width`` columns ≈ a few dozen
  round-batches by default — O(n·width) memory with width ≪ n) threaded
  through the search carry with stats-side write-through: each fresh
  distance column is stored by the very round that computes it, and the
  host never touches a distance column.  When a fit outgrows the ring,
  the oldest round's slots are recycled and any later read of a recycled
  round falls back to fresh recomputation — bit-identical blocks, so
  medoids/loss are unchanged and only the fresh/cached split moves.

``fused=False`` keeps the host-orchestrated driver (one dispatch per
medoid / per swap sub-step, host syncs between) built from the same
pieces — the in-run baseline ``benchmarks/core_bench.py`` measures the
fusion against.

Distance-evaluation accounting (the paper's headline metric) is algorithmic
and backend-independent: each bandit round pays ``#active-arms × B`` in
BUILD and ``#distinct-active-candidates × B`` in SWAP (FastPAM1 sharing),
cache (re)computation pays ``n·k``, and the d_near update after each BUILD
assignment pays ``n`` — exactly the ledger of the reference implementation.

Beyond the paper, ``BanditPAM(reuse="pic")`` enables the BanditPAM++
(Tiwari et al. 2023) SWAP-phase reuse engine:

* **PIC** — every search samples the SAME fixed reference permutation, and
  the distance columns it consumes are materialised once (write-through
  into the device cache); later searches replay those rounds for free.
* **Virtual arms** — per-arm Σg / Σg² from swap iteration *t* are carried
  into iteration *t+1* and repaired only where the accepted swap moved a
  reference point's (d1, d2, assign); per changed point that touches the
  shared base term plus at most the point's old and new cluster rows
  (``_carry_delta``).  A search seeded this way usually resolves its argmin
  from the carried exact prefix without sampling at all.

Under ``reuse="pic"`` the ledger splits into fresh vs cached: fresh pays
``n`` per newly materialised cache column (plus the ``n·k`` cache/loss
terms), cached tallies carried-prefix replays, warm rounds and delta
repairs.  ``reuse="none"`` reproduces the original ledger exactly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .adaptive import SearchResult, adaptive_search
from .distances import get_metric
from .engine import (_EXACT_CHUNK, _build_g, _ref_chunks, _swap_batch_stats,
                     _swap_terms, FitContext, cache_read_or_write,
                     counted_dispatch, exact_build_means, exact_swap_means,
                     get_stats_backend, host_read, host_stage, medoid_cache,
                     observe_tiles, phase, resolve_stats_backend,
                     resolve_tile_config, span, stream_columns, total_loss)
from .pic_cache import (PicCache, carry_valid, fresh_positions, make_cache,
                        resolve_batch_cache_rounds, resolve_cache_rounds)
from .report import BatchFitReport, FitReport

__all__ = ["BanditPAM", "BatchFitReport", "FitResult", "medoid_cache",
           "total_loss"]

# Re-exported for the siblings (pam, distributed) and external callers that
# historically imported the shared math from here; it now lives in engine.
_ = (SearchResult, _EXACT_CHUNK, _build_g, _ref_chunks, _swap_batch_stats,
     _swap_terms)


# ---------------------------------------------------------------------------
# BanditPAM++ carried-moment repair (virtual arms)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "backend"))
def _carry_delta(cols: jnp.ndarray, pidx: jnp.ndarray, pw: jnp.ndarray,
                 n_prefix: jnp.ndarray, d1o, d2o, ao, d1n, d2n, an,
                 sums: jnp.ndarray, sqsums: jnp.ndarray, *, k: int,
                 backend: str):
    """Re-validate carried SWAP arm statistics after an accepted swap.

    The carried Σg / Σg² (over the permutation prefix ``[0, n_prefix)``)
    were accumulated under the previous iteration's (d1, d2, assign).  The
    accepted swap changes ``g_{m,x}(y)`` only at reference points y whose
    (d1, d2, assign) moved — the virtual-arm decomposition
    ``g = base_x + 1[y∈C_m]·corr_x`` means each such point touches the
    shared base term plus at most its old and new cluster rows (the ≤2
    medoid rows invalidated by the swap); every other contribution is
    permutation-invariant and carried verbatim.  Both passes below read the
    PIC distance columns through the stats backend's cache-served path —
    on Pallas that is the ``swap_g_stats_cached`` kernel over the full
    capped cache width — so the whole update costs ZERO fresh distance
    evaluations.  Detection by exact comparison is safe: unchanged entries
    of ``medoid_cache`` are bit-identical recomputations.

    ``cols`` is the capped PIC ring ``[n, W·B]``; the caller guarantees
    ``n_prefix ≤ W·B`` (and passes 0 once recycling has invalidated the
    prefix — see ``pic_cache.carry_valid``), under which ring slots are
    the identity mapping of permutation positions.

    Returns (sums', sqsums', n_changed_positions).
    """
    with jax.named_scope("carry"):
        be = get_stats_backend(backend)
        width = cols.shape[1]
        in_prefix = (jnp.arange(width) < n_prefix).astype(jnp.float32)
        b1, b2, ba = d1o[pidx], d2o[pidx], ao[pidx]
        c1, c2, ca = d1n[pidx], d2n[pidx], an[pidx]
        changed = ((b1 != c1) | (b2 != c2) | (ba != ca)).astype(jnp.float32)
        w = pw * in_prefix * changed
        s_old, q_old, _ = be.swap_stats_from_d(cols, b1, b2, ba, w, k, None)
        s_new, q_new, _ = be.swap_stats_from_d(cols, c1, c2, ca, w, k, None)
        return (sums - s_old + s_new, sqsums - q_old + q_new,
                jnp.sum(w).astype(jnp.int32))


def _pic_operand(data, data_p, *, backend: str, mode: str):
    """The operand every fresh PIC round reads its column block from:
    ``data`` as the stats backend aligns it (``align_rows`` — the Pallas
    kernel's zero padding, nothing on jnp).  Each program makes it once,
    at its top and outside every loop, unless an outer program passed
    it down as ``data_p``.  None outside ``mode="pic"``, whose rounds
    go through the fused stats wrappers instead."""
    if mode != "pic" or data_p is not None:
        return data_p
    return get_stats_backend(backend).align_rows(data)


# ---------------------------------------------------------------------------
# BUILD
# ---------------------------------------------------------------------------

def _build_step(data, dnear, med_mask, key, cache, dwarm, perm,
                perm_idx=None, perm_w=None, valid=None, n_valid=None,
                log_term=None, *,
                backend: str, metric: str, batch_size: int, delta: float,
                sampling: str, baseline: str, mode: str, free_rounds: int = 0,
                data_p=None) -> SearchResult:
    """One BUILD medoid selection (one Algorithm 1 call).

    ``mode`` is the cache regime (see :class:`FitContext`).  Under
    ``"pic"`` the bounded :class:`PicCache` ring rides the search carry
    with write-through and comes back in ``SearchResult.aux``.

    The trailing optional args are the batched multi-fit lane state
    (``fit_batch``): an explicit pre-tiled reference layout
    (``perm_idx``/``perm_w`` — what the single-fit search would derive
    from ``key``/``perm`` at trace time, passed as data because the
    logical n is ragged), the row-validity mask (pad rows may never
    become medoids), and the traced per-fit budget/δ
    (``n_valid``/``log_term``).  All default to None → the historical
    single-fit trace, bit-identically.  ``data_p`` is the aligned PIC
    operand (``_pic_operand``) when an outer program made it.
    """
    n = data.shape[0]
    be = get_stats_backend(backend)
    B = batch_size
    # baseline="none" never reads the leader cross-sum; lead=None lets the
    # backends skip the leader-row work entirely (static at trace time).
    ld = (lambda lead: lead) if baseline == "leader" else (lambda lead: None)

    if mode == "pic":
        data_p = _pic_operand(data, data_p, backend=backend, mode=mode)

        def stats_fn(ref_idx, w, lead, rnd, aux):
            dxy, aux = cache_read_or_write(
                be, data_p, ref_idx, metric=metric, batch_size=B, rnd=rnd,
                b_eff=jnp.sum(w).astype(jnp.int32), cache=aux)
            with jax.named_scope("stats"):
                s, q, c = be.build_stats_from_d(dxy, dnear[ref_idx], w,
                                                ld(lead))
            return s, q, c, aux

        aux_init = cache
        free = cache.hw
        free_lo = jnp.maximum(cache.hw - cache.cols.shape[1] // B, 0)
    elif mode == "warm":
        def stats_fn(ref_idx, w, lead, rnd):
            # paper App 2.2 cache: warm rounds read precomputed distance
            # columns (same fixed permutation across every search call)
            with jax.named_scope("stats"):
                return jax.lax.cond(
                    rnd < free_rounds,
                    lambda _: be.build_stats_from_d(
                        jax.lax.dynamic_slice_in_dim(dwarm, rnd * B, B, 1),
                        dnear[ref_idx], w, ld(lead)),
                    lambda _: be.build_stats(data, ref_idx, dnear[ref_idx],
                                             w, ld(lead), metric=metric),
                    None)

        aux_init = None
        free = free_rounds
        free_lo = 0
    else:
        def stats_fn(ref_idx, w, lead, rnd):
            with jax.named_scope("stats"):
                return be.build_stats(data, ref_idx, dnear[ref_idx], w,
                                      ld(lead), metric=metric)

        aux_init = None
        free = 0
        free_lo = 0

    def exact_fn():
        return exact_build_means(be, data, dnear, metric=metric)

    active0 = jnp.logical_not(med_mask)
    if valid is not None:
        active0 = jnp.logical_and(active0, valid)
    return adaptive_search(key, stats_fn=stats_fn, exact_fn=exact_fn,
                           n_arms=n, n_ref=n, batch_size=B, delta=delta,
                           active_init=active0,
                           sampling=sampling, baseline=baseline, perm=perm,
                           perm_idx=perm_idx, perm_w=perm_w,
                           free_rounds=free, free_lo=free_lo,
                           aux_init=aux_init, n_ref_eff=n_valid,
                           log_term=log_term)


_build_step_jit = jax.jit(
    _build_step, static_argnames=("backend", "metric", "batch_size", "delta",
                                  "sampling", "baseline", "mode",
                                  "free_rounds"))


# ``donate_argnums=(2,)`` donates the PIC ring: the caller replaces
# ``ctx.cache`` with the returned buffers and never touches the old ones,
# so the O(n·width) cols block aliases in place instead of doubling the
# fit's resident footprint (graphcheck GRC005 pins the aliasing in the
# lowered program).  Under ``mode="none"`` the cache is a leafless None
# and the donation is a no-op.
@functools.partial(jax.jit,
                   static_argnames=("backend", "metric", "batch_size",
                                    "delta", "sampling", "baseline", "k",
                                    "mode", "free_rounds"),
                   donate_argnums=(2,))
def _build_fused(data, subkeys, cache, dwarm, perm, spidx=None, spw=None,
                 valid=None, n_valid=None, log_term=None, *, backend: str,
                 metric: str, batch_size: int, delta: float, sampling: str,
                 baseline: str, k: int, mode: str, free_rounds: int,
                 data_p=None):
    """The whole BUILD phase as ONE jit: ``fori_loop`` over the k medoid
    selections, with d_near / the medoid mask / the bounded device PIC
    cache as loop carry.  Returns per-step rounds and the fresh/cached
    ledger entries so the host never syncs mid-phase.  Under ``"pic"``
    the data set is aligned for the fresh rounds once, before the loop
    (``_pic_operand``; ``data_p`` when ``_build_batch`` made it).

    ``spidx``/``spw`` (batched multi-fit lanes): explicit pre-tiled
    reference layouts — ``[k, R·B]`` for per-selection permutations
    (``reuse="none"``, one per search key) or ``[R·B]`` for the one fixed
    PIC permutation shared by every search."""
    n = data.shape[0]
    B = batch_size
    dist = get_metric(metric)
    pic = mode == "pic"
    with jax.named_scope("build"):
        data_p = _pic_operand(data, data_p, backend=backend, mode=mode)

    def body(i, c):
        dnear, med_mask, medoids, cc, rounds_a, evals_a, cached_a = c
        if spidx is None:
            spidx_i = None
        else:
            spidx_i = spidx if spidx.ndim == 1 else spidx[i]
        sr = _build_step(data, dnear, med_mask, subkeys[i], cc, dwarm, perm,
                         spidx_i, spw, valid, n_valid, log_term,
                         backend=backend, metric=metric, batch_size=B,
                         delta=delta, sampling=sampling, baseline=baseline,
                         mode=mode, free_rounds=free_rounds, data_p=data_p)
        m = sr.best
        medoids = medoids.at[i].set(m)
        med_mask = med_mask.at[m].set(True)
        dnear = jnp.minimum(dnear, dist(data[m][None, :], data)[0])
        if pic:
            # Fresh cost = n per column this search computed
            # (materialisations serve every later search, recycled-slot
            # replays are paid again); the position COUNT is stored and
            # the host multiplies by n (a device-side uint32 product
            # would wrap at large n).  Warm rounds are tallied
            # separately as cached reads.
            cc2 = sr.aux
            fresh = fresh_positions(cc, cc2)
            cached_a = cached_a.at[i].set(sr.n_evals_cached)
            cc = cc2
        else:
            fresh = sr.n_evals
        evals_a = evals_a.at[i].set(fresh)
        rounds_a = rounds_a.at[i].set(sr.rounds)
        return (dnear, med_mask, medoids, cc, rounds_a, evals_a, cached_a)

    init = (jnp.full((n,), jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.bool_),
            jnp.zeros((k,), jnp.int32),
            cache,
            jnp.zeros((k,), jnp.int32),
            jnp.zeros((k,), jnp.uint32),
            jnp.zeros((k,), jnp.uint32))
    with jax.named_scope("build"):
        return jax.lax.fori_loop(0, k, body, init)


# ---------------------------------------------------------------------------
# SWAP (FastPAM1 fused form)
# ---------------------------------------------------------------------------

def _swap_search(data, d1, d2, assign, med_mask, key, cache, dwarm, perm,
                 init_sums, init_sqsums, init_rounds, s_pidx=None, s_pw=None,
                 valid=None, n_valid=None, log_term=None, *, backend: str,
                 metric: str, batch_size: int, delta: float, k: int,
                 sampling: str, baseline: str, early_stop: bool, mode: str,
                 free_rounds: int = 0, data_p=None) -> SearchResult:
    """One SWAP best-arm search over the (medoid, candidate) arm set.

    The trailing optional args are the batched multi-fit lane state (see
    ``_build_step``); ``s_pidx``/``s_pw`` is this search's pre-tiled
    reference layout, ``data_p`` the aligned PIC operand."""
    n = data.shape[0]
    be = get_stats_backend(backend)
    B = batch_size
    ld = (lambda lead: lead) if baseline == "leader" else (lambda lead: None)

    if mode == "pic":
        data_p = _pic_operand(data, data_p, backend=backend, mode=mode)

        def stats_fn(ref_idx, w, lead, rnd, aux):
            dxy, aux = cache_read_or_write(
                be, data_p, ref_idx, metric=metric, batch_size=B, rnd=rnd,
                b_eff=jnp.sum(w).astype(jnp.int32), cache=aux)
            with jax.named_scope("stats"):
                s, q, c = be.swap_stats_from_d(dxy, d1[ref_idx], d2[ref_idx],
                                               assign[ref_idx], w, k,
                                               ld(lead))
            return s, q, c, aux

        aux_init = cache
        free = cache.hw
        free_lo = jnp.maximum(cache.hw - cache.cols.shape[1] // B, 0)
    elif mode == "warm":
        def stats_fn(ref_idx, w, lead, rnd):
            with jax.named_scope("stats"):
                return jax.lax.cond(
                    rnd < free_rounds,
                    lambda _: be.swap_stats_from_d(
                        jax.lax.dynamic_slice_in_dim(dwarm, rnd * B, B, 1),
                        d1[ref_idx], d2[ref_idx], assign[ref_idx], w, k,
                        ld(lead)),
                    lambda _: be.swap_stats(data, ref_idx, d1[ref_idx],
                                            d2[ref_idx], assign[ref_idx], w,
                                            k, ld(lead), metric=metric),
                    None)

        aux_init = None
        free = free_rounds
        free_lo = 0
    else:
        def stats_fn(ref_idx, w, lead, rnd):
            with jax.named_scope("stats"):
                return be.swap_stats(data, ref_idx, d1[ref_idx], d2[ref_idx],
                                     assign[ref_idx], w, k, ld(lead),
                                     metric=metric)

        aux_init = None
        free = 0
        free_lo = 0

    def exact_fn():
        return exact_swap_means(be, data, d1, d2, assign, k, metric=metric)

    # Candidates that are already medoids (or pad rows of a batched
    # ragged fit) are not valid swap targets.
    cand_ok = jnp.logical_not(med_mask)
    if valid is not None:
        cand_ok = jnp.logical_and(cand_ok, valid)
    active0 = jnp.tile(cand_ok[None, :], (k, 1)).reshape(-1)

    def count_fn(active):
        # FastPAM1: one distance per (x, y) pair serves all k arms (·, x).
        any_x = jnp.any(active.reshape(k, n), axis=0)
        return jnp.sum(any_x.astype(jnp.uint32))

    return adaptive_search(key, stats_fn=stats_fn, exact_fn=exact_fn,
                           n_arms=k * n, n_ref=n, batch_size=B, delta=delta,
                           active_init=active0, count_fn=count_fn,
                           sampling=sampling, baseline=baseline,
                           stop_when_positive=early_stop, perm=perm,
                           perm_idx=s_pidx, perm_w=s_pw,
                           free_rounds=free, free_lo=free_lo,
                           init_sums=init_sums, init_sqsums=init_sqsums,
                           init_rounds=init_rounds, aux_init=aux_init,
                           n_ref_eff=n_valid, log_term=log_term)


_swap_search_jit = jax.jit(
    _swap_search, static_argnames=("backend", "metric", "batch_size",
                                   "delta", "k", "sampling", "baseline",
                                   "early_stop", "mode", "free_rounds"))


def _swap_iter(data, medoids, med_mask, key, cache, dwarm, perm, perm_idx,
               perm_w, carry, prev_loss, s_pidx=None, s_pw=None, valid=None,
               n_valid=None, log_term=None, *, backend: str, metric: str,
               batch_size: int, delta: float, k: int, sampling: str,
               baseline: str, early_stop: bool, mode: str, free_rounds: int,
               data_p=None):
    """One SWAP iteration as a single fused device step: medoid-cache
    refresh + carried-moment repair (``_carry_delta``) + bandit search +
    candidate loss + the accept decision against ``prev_loss``.  Only the
    accept/converge flag (one scalar read) is left to the host.

    The accept comparison runs ON DEVICE in f32 (it used to be a host
    f64 compare): the batched multi-fit driver must decide inside its
    per-lane ``while_loop``, and keeping one definition for both paths
    is what makes ``fit_batch`` ≡ loop-of-``fit`` hold bit-for-bit at
    accept margins.  The trailing optional args are the batched lane
    state (see ``_build_step``); under ``"pic"`` the fresh rounds' aligned
    operand is made here, before the search, unless ``_swap_batch``
    passed it as ``data_p``."""
    with jax.named_scope("swap"):
        n = data.shape[0]
        B = batch_size
        data_p = _pic_operand(data, data_p, backend=backend, mode=mode)
        d1, d2, assign = medoid_cache(data, medoids, metric=metric)
        n_changed = jnp.int32(0)
        init_sums = init_sqsums = None
        init_rounds = 0
        if carry is not None:
            # BanditPAM++ PIC: the previous search's per-arm moments stay
            # valid for every arm whose g is unchanged; _carry_delta repairs
            # only the contributions of reference points hit by the accepted
            # swap, from cached columns (zero fresh evals).  Once the ring
            # has recycled a round the carried prefix is no longer resident,
            # so the repair is skipped entirely (lax.cond — no wasted
            # O(n·W·B) pass) and the search starts cold — exact either way,
            # only the fresh/cached split moves.
            c_sums, c_sq, c_rounds, d1o, d2o, ao = carry
            resident = carry_valid(cache, B)

            def repair(_):
                return _carry_delta(cache.cols, perm_idx, perm_w, c_rounds * B,
                                    d1o, d2o, ao, d1, d2, assign, c_sums, c_sq,
                                    k=k, backend=backend)

            def cold(_):
                return (jnp.zeros_like(c_sums), jnp.zeros_like(c_sq),
                        jnp.int32(0))

            init_sums, init_sqsums, n_changed = jax.lax.cond(
                resident, repair, cold, None)
            init_rounds = jnp.where(resident, c_rounds, 0)
        sr = _swap_search(data, d1, d2, assign, med_mask, key, cache, dwarm,
                          perm, init_sums, init_sqsums, init_rounds,
                          s_pidx, s_pw, valid, n_valid, log_term,
                          backend=backend, metric=metric, batch_size=B,
                          delta=delta, k=k, sampling=sampling,
                          baseline=baseline, early_stop=early_stop,
                          mode=mode, free_rounds=free_rounds, data_p=data_p)
        if mode == "pic":
            cache2 = sr.aux
            fresh = fresh_positions(cache, cache2)
        else:
            cache2 = cache
            fresh = sr.n_evals
        m_idx = sr.best // n
        x_idx = sr.best % n
        cand = medoids.at[m_idx].set(x_idx)
        new_loss = total_loss(data, cand, metric=metric, w=valid)
        # The one accept rule (f32, on device) shared by the single-fit
        # driver and every fit_batch lane.
        accept = new_loss < prev_loss - 1e-7 * jnp.maximum(1.0,
                                                           jnp.abs(prev_loss))
        new_carry = (sr.sums, sr.sqsums, sr.rounds, d1, d2, assign)
        # The displaced medoid and the accepted-state mask are produced IN
        # TRACE so the host driver never does eager index arithmetic on
        # device arrays (which would be implicit transfers under the
        # transfer guard); the driver just selects cand/new_mask on accept.
        old_med = medoids[m_idx]
        new_mask = med_mask.at[old_med].set(False).at[x_idx].set(True)
        # fresh is a POSITION count and n_changed a point count under "pic";
        # the host driver multiplies both by n (uint32-safe).
        return (sr.best, new_loss, cand, new_mask, old_med, new_carry, cache2,
                fresh, sr.n_evals_cached, n_changed, sr.used_exact, accept)


# Donations: the PIC ring (arg 4) and the carried swap moments (arg 9)
# are consumed by each iteration and replaced by its outputs — the driver
# reassigns ``ctx.cache``/``carry`` and never reads the old buffers, so
# both alias in place.  First iterations pass ``carry=None`` (leafless,
# donation no-op) and trace separately from the steady state anyway.
_swap_iter_jit = jax.jit(
    _swap_iter, static_argnames=("backend", "metric", "batch_size", "delta",
                                 "k", "sampling", "baseline", "early_stop",
                                 "mode", "free_rounds"),
    donate_argnums=(4, 9))


# ---------------------------------------------------------------------------
# Batched multi-fit phase drivers (fit_batch)
# ---------------------------------------------------------------------------
#
# One jit per phase over a [batch] axis of independent padded fits.  The
# batch axis is lowered with ``lax.map`` (a scan over lanes), NOT vmap:
# vmap rewrites the per-lane GEMMs into batched contractions whose f32
# accumulation order differs from the single-fit trace (~1e-3 drift in
# d_near on CPU), which breaks the bit-parity invariant the differential
# harness pins.  Under lax.map every lane executes the same per-fit HLO
# as the single-fit jit, so medoids, losses, AND the fresh/cached ledger
# reproduce the loop of single fits exactly — while the whole batch is
# still one dispatch, one compilation, and no per-fit host sync.

# NOT donated: the stacked [B, n, width] ring rides the ``lax.map`` scan
# as per-lane xs/ys, and XLA materialises scan outputs by dynamic-update-
# slice into a fresh stacked buffer — the input ring cannot alias it
# (donating anyway just emits "donated buffers were not usable").  The
# single-fit drivers, whose cache is a plain argument/result pair, DO
# donate; graphcheck GRC005 pins that split (docs/design.md #10).
@functools.partial(jax.jit,
                   static_argnames=("backend", "metric", "batch_size",
                                    "delta", "sampling", "baseline", "k",
                                    "mode", "free_rounds"))
def _build_batch(data, subkeys, cache, spidx, spw, valid, n_valid, log_term,
                 *, backend: str, metric: str, batch_size: int, delta,
                 sampling: str, baseline: str, k: int, mode: str,
                 free_rounds: int):
    """BUILD for a [batch] of padded fits: ONE jit, ``lax.map`` over the
    per-fit ``_build_fused`` lanes.  Every array input carries a leading
    batch axis (``cache`` is a stacked :class:`PicCache` pytree or None).
    The PIC operand is aligned for all lanes at once, before the map.
    Returns stacked (med_mask, medoids, cache, rounds, fresh, cached)."""

    def lane(xs):
        (data_i, data_p_i, keys_i, cache_i, spidx_i, spw_i, valid_i, nv_i,
         lt_i) = xs
        (dnear, med_mask, medoids, cc, rounds_a, evals_a,
         cached_a) = _build_fused(
             data_i, keys_i, cache_i, None, None, spidx_i, spw_i, valid_i,
             nv_i, lt_i, backend=backend, metric=metric,
             batch_size=batch_size, delta=delta, sampling=sampling,
             baseline=baseline, k=k, mode=mode, free_rounds=free_rounds,
             data_p=data_p_i)
        del dnear  # not needed post-BUILD; keep the lane output lean
        return med_mask, medoids, cc, rounds_a, evals_a, cached_a

    with jax.named_scope("build"):
        data_p = _pic_operand(data, None, backend=backend, mode=mode)
        return jax.lax.map(
            lane, (data, data_p, subkeys, cache, spidx, spw, valid, n_valid,
                   log_term))


@functools.partial(jax.jit,
                   static_argnames=("backend", "metric", "batch_size",
                                    "delta", "k", "sampling", "baseline",
                                    "early_stop", "mode", "free_rounds",
                                    "max_swaps"))
def _swap_batch(data, medoids, med_mask, subkeys, cache, pidx_c, pw_c,
                spidx, spw, valid, n_valid, log_term, *, backend: str,
                metric: str, batch_size: int, delta, k: int, sampling: str,
                baseline: str, early_stop: bool, mode: str, free_rounds: int,
                max_swaps: int):
    """The whole SWAP phase for a [batch] of padded fits as ONE jit: each
    ``lax.map`` lane runs its own accept-driven ``while_loop`` over up to
    ``max_swaps`` fused ``_swap_iter`` steps, with the accept decision on
    device (the same f32 rule the single-fit driver reads back).

    ``pidx_c``/``pw_c`` are the per-fit carry-repair layouts over the PIC
    ring width (``_carry_delta``); ``spidx`` the search layouts —
    ``[batch, T, R·B]`` per-iteration permutations (``reuse="none"``) or
    ``[batch, R·B]`` the one fixed PIC permutation.  The moment carry is
    seeded with ZEROS on the first iteration instead of the single-fit
    driver's ``carry=None`` cold start — equivalent by construction
    (``_carry_delta`` over an empty prefix is the identity on zeros, and
    ``adaptive_search`` re-derives σ from the first batch whenever
    ``n_used == 0``), which keeps the while-loop carry a fixed pytree.

    Per lane returns (medoids, loss, converged, iters, fresh, cached,
    n_changed, exact_fallbacks, refresh, old[T], new[T], loss[T],
    accept[T]) — everything the host needs to assemble per-fit FitReports
    without a mid-phase sync; ``refresh`` is the ring's ``refresh_pos``
    over the whole fit.  The PIC operand is aligned for all lanes at once,
    before the map."""
    n = data.shape[1]
    kn = k * n
    T = max_swaps
    pic = mode == "pic"

    def lane(xs):
        (data_i, data_p_i, meds0, mask0, keys_i, cache_i, pidx_i, pw_i,
         spidx_i, spw_i, valid_i, nv_i, lt_i) = xs
        loss0 = total_loss(data_i, meds0, metric=metric, w=valid_i)
        if pic:
            carry0 = (jnp.zeros((kn,), jnp.float32),
                      jnp.zeros((kn,), jnp.float32), jnp.int32(0),
                      jnp.zeros((n,), jnp.float32),
                      jnp.zeros((n,), jnp.float32),
                      jnp.zeros((n,), jnp.int32))
        else:
            carry0 = None

        def cond(st):
            return jnp.logical_and(st[0] < T, jnp.logical_not(st[1]))

        def body(st):
            (t, done, meds, mask, loss, carry, cc, fresh_s, cached_s,
             nchg_s, exact_s, old_a, new_a, loss_a, acc_a) = st
            pidx_t = spidx_i if spidx_i.ndim == 1 else spidx_i[t]
            (best, new_loss, cand, new_mask, old, new_carry, cc2, fresh,
             cached, nchg, uexact, accept) = _swap_iter(
                 data_i, meds, mask, keys_i[t], cc, None, None, pidx_i,
                 pw_i, carry, loss, pidx_t, spw_i, valid_i, nv_i, lt_i,
                 backend=backend, metric=metric, batch_size=batch_size,
                 delta=delta, k=k, sampling=sampling, baseline=baseline,
                 early_stop=early_stop, mode=mode, free_rounds=free_rounds,
                 data_p=data_p_i)
            x_idx = best % n
            meds2 = jnp.where(accept, cand, meds)
            mask2 = jnp.where(accept, new_mask, mask)
            return (t + 1, jnp.logical_not(accept), meds2, mask2,
                    jnp.where(accept, new_loss, loss),
                    new_carry if pic else None, cc2,
                    fresh_s + fresh, cached_s + cached, nchg_s + nchg,
                    exact_s + uexact.astype(jnp.int32),
                    old_a.at[t].set(old), new_a.at[t].set(x_idx),
                    loss_a.at[t].set(new_loss), acc_a.at[t].set(accept))

        st0 = (jnp.int32(0), jnp.bool_(False), meds0, mask0, loss0,
               carry0, cache_i, jnp.uint32(0), jnp.uint32(0),
               jnp.int32(0), jnp.int32(0),
               jnp.zeros((T,), jnp.int32), jnp.zeros((T,), jnp.int32),
               jnp.zeros((T,), jnp.float32), jnp.zeros((T,), jnp.bool_))
        stf = jax.lax.while_loop(cond, body, st0)
        refresh = stf[6].refresh_pos if pic else jnp.uint32(0)
        return (stf[2], stf[4], stf[1], stf[0], stf[7], stf[8], stf[9],
                stf[10], refresh, stf[11], stf[12], stf[13], stf[14])

    with jax.named_scope("swap"):
        data_p = _pic_operand(data, None, backend=backend, mode=mode)
        return jax.lax.map(lane, (data, data_p, medoids, med_mask, subkeys,
                                  cache, pidx_c, pw_c, spidx, spw, valid,
                                  n_valid, log_term))


@functools.partial(jax.jit, static_argnames=("k", "T"))
def _batch_rng_chains(seeds, *, k: int, T: int):
    """Replicate every per-fit RNG chain in ONE dispatch: the exact
    PRNGKey/split sequence ``fit`` walks, vmapped over the seeds (split
    is an elementwise threefry application, so the vmapped bits are
    identical to the sequential ones).  Returns per-fit
    (ckey, build subkeys [k,2], swap subkeys [T,2], build perm-keys,
    swap perm-keys) — the perm-keys being the second-level
    ``split(sub)[1]`` that seeds each search's reference permutation."""

    def chain(seed):
        key = jax.random.PRNGKey(seed)
        key, ckey = jax.random.split(key)
        subs = []
        # tracecheck: ignore[TRC002] -- trace-constant unroll: k + T is a
        # static fit-shape bound, and the chain must replay the sequential
        # split order of the single-fit driver bit-for-bit.
        for _ in range(k + T):
            key, sub = jax.random.split(key)
            subs.append(sub)
        subs = jnp.stack(subs)
        # tracecheck: ignore[TRC005] -- vmap over key *derivation* only:
        # threefry split/fold_in are elementwise, so the vmapped bits equal
        # the sequential ones; no float reductions are vectorized here.
        pkeys = jax.vmap(lambda s: jax.random.split(s)[1])(subs)
        return ckey, subs[:k], subs[k:], pkeys[:k], pkeys[k:]

    # tracecheck: ignore[TRC005] -- same key-derivation exemption as above:
    # per-fit chains are integer threefry lanes, bit-stable under vmap.
    return jax.vmap(chain)(seeds)


@functools.partial(jax.jit, static_argnames=("n",))
def _batch_perms(keys, *, n: int):
    """[m, 2] keys -> [m, n] reference permutations, one dispatch (the
    vmapped sort matches ``jax.random.permutation`` row-for-row)."""
    # tracecheck: ignore[TRC005] -- vmapped argsort of per-row random bits:
    # each row's permutation matches jax.random.permutation(s, n) exactly
    # (locked by test_multifit bit-parity), no float accumulation involved.
    return jax.vmap(
        lambda s: jax.random.permutation(s, n).astype(jnp.int32))(keys)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

# Every solver in the repo now emits the unified FitReport; the old name
# remains importable as a thin alias.
FitResult = FitReport


class BanditPAM:
    """k-medoids via adaptive sampling; same medoids as PAM w.h.p.

    ``backend`` selects the g-statistics compute path
    (``repro.core.engine``): ``"auto"`` (kernels on accelerators, jnp on
    CPU), ``"pallas"``, ``"jnp"``, or any registered backend name.
    ``fused=False`` falls back to the host-orchestrated stepped driver
    (same math, one dispatch per sub-step) — the benchmark baseline.
    ``cache_width`` caps the ``reuse="pic"`` column ring (in reference
    columns, rounded down to round-batches; default a few dozen
    round-batches — see ``repro.core.pic_cache``).
    """

    def __init__(self, k: int, metric: str = "l2", batch_size: int = 100,
                 delta: Optional[float] = None, max_swaps: Optional[int] = None,
                 seed: int = 0, sampling: str = "permutation",
                 baseline: str = "none", swap_early_stop: bool = False,
                 cache_cols: int = 0, reuse: str = "none",
                 cache_width: Optional[int] = None,
                 backend: str = "auto", fused: bool = True):
        if reuse not in ("none", "pic"):
            raise ValueError(f"unknown reuse mode {reuse!r}")
        if reuse == "pic" and sampling != "permutation":
            raise ValueError('reuse="pic" requires sampling="permutation" '
                             "(the cache is keyed by a fixed permutation)")
        self.k = int(k)
        self.metric = metric
        self.batch_size = int(batch_size)
        self.delta = delta
        self.max_swaps = max_swaps if max_swaps is not None else 4 * self.k + 10
        self.seed = seed
        self.sampling = sampling
        self.baseline = baseline
        self.swap_early_stop = swap_early_stop
        self.cache_cols = cache_cols
        self.reuse = reuse
        # Width cap (in reference columns) of the PIC ring; None = auto
        # (a few dozen round-batches — O(n·width) memory, width ≪ n).
        self.cache_width = cache_width
        self.backend = backend
        self.fused = bool(fused)

    # -- per-fit context -------------------------------------------------
    def _make_context(self, data: jnp.ndarray, ckey: jax.Array, backend: str,
                      res: FitResult) -> FitContext:
        """Build the per-fit :class:`FitContext` (cache regime + buffers).

        All state lives on the context, never on the instance — ``fit`` is
        re-entrant and refitting the same estimator starts clean."""
        n = data.shape[0]
        be = get_stats_backend(backend)
        B = self.batch_size
        if self.reuse == "pic":
            perm = jax.random.permutation(ckey, n).astype(jnp.int32)
            n_rounds_max = -(-n // B)
            W = resolve_cache_rounds(n_rounds_max, B, self.cache_width)
            width = W * B
            perm_np = np.asarray(perm)
            # Prefix of adaptive_search's tiling at the capped width:
            # positions >= n are w=0 padding.
            perm_idx = jnp.asarray(np.tile(perm_np, -(-width // n))[:width])
            perm_w = jnp.asarray((np.arange(width) < n).astype(np.float32))
            cache = make_cache(n, B, W)
            if self.cache_cols > 0:
                # optional upfront warm block, same semantics as
                # reuse="none" (clamped to the ring capacity)
                warm = min(min(self.cache_cols, n) // B, W)
                if warm > 0:
                    cols = stream_columns(be, data,
                                          data[perm_idx[:warm * B]],
                                          metric=self.metric)
                    cache = PicCache(
                        cache.cols.at[:, :warm * B].set(cols),
                        jnp.int32(warm), jnp.uint32(warm * B),
                        cache.refresh_pos)
                    res.evals_by_phase["cache_warm"] = n * warm * B
            return FitContext(mode="pic", backend=backend, perm=perm,
                              perm_idx=perm_idx, perm_w=perm_w, cache=cache)
        if self.cache_cols > 0 and self.sampling == "permutation":
            # Paper App 2.2: one fixed reference permutation for every
            # search + a warm block of its first C columns, paid once.
            c = (min(self.cache_cols, n) // B) * B
            if c > 0:
                perm = jax.random.permutation(ckey, n).astype(jnp.int32)
                dwarm = stream_columns(be, data, data[perm[:c]],
                                       metric=self.metric)
                res.evals_by_phase["cache_warm"] = n * c
                return FitContext(mode="warm", backend=backend, perm=perm,
                                  dwarm=dwarm, free_rounds=c // B)
        return FitContext(mode="none", backend=backend)

    # -- BUILD ----------------------------------------------------------
    def _build(self, data: jnp.ndarray, key: jax.Array, ctx: FitContext,
               res: FitResult):
        n = data.shape[0]
        delta = self.delta if self.delta is not None else 1.0 / (1000.0 * n)
        # One subkey per medoid selection, split exactly as the legacy
        # host loop did, so trajectories are seed-compatible.
        subs = []
        for _ in range(self.k):
            key, sub = jax.random.split(key)
            subs.append(sub)
        subkeys = jnp.stack(subs)
        kw = dict(backend=ctx.backend, metric=self.metric,
                  batch_size=self.batch_size, delta=delta,
                  sampling=self.sampling, baseline=self.baseline,
                  mode=ctx.mode, free_rounds=ctx.free_rounds)
        if self.fused:
            phase = counted_dispatch(_build_fused, res.dispatches_by_phase,
                                     "build")
            (dnear, med_mask, medoids, cache, rounds_a, evals_a,
             cached_a) = phase(data, subkeys, ctx.cache, ctx.dwarm,
                               ctx.perm, k=self.k, **kw)
            ctx.cache = cache
            # One explicit ledger read for the whole phase — the fused
            # BUILD stays a single dispatch plus a single device_get.
            rounds_a, evals_a, cached_a = host_read(
                (rounds_a, evals_a, cached_a))
        else:
            # Stepped baseline: one dispatch + one host sync per medoid.
            step = counted_dispatch(_build_step_jit,
                                    res.dispatches_by_phase, "build")
            dist = get_metric(self.metric)
            dnear = jnp.full((n,), jnp.inf, jnp.float32)
            med_mask = jnp.zeros((n,), jnp.bool_)
            cache = ctx.cache
            meds, rounds_a, evals_a, cached_a = [], [], [], []
            for i in range(self.k):
                sr = step(data, dnear, med_mask, subkeys[i],
                          cache, ctx.dwarm, ctx.perm, **kw)
                m = int(sr.best)
                meds.append(m)
                med_mask = med_mask.at[m].set(True)
                dnear = jnp.minimum(dnear, dist(data[m][None, :], data)[0])
                if ctx.mode == "pic":
                    cache2 = sr.aux
                    evals_a.append(int(fresh_positions(cache, cache2)))
                    cached_a.append(int(sr.n_evals_cached))
                    cache = cache2
                else:
                    evals_a.append(int(sr.n_evals))
                rounds_a.append(int(sr.rounds))
            medoids = jnp.asarray(meds, jnp.int32)
            ctx.cache = cache
        res.build_rounds.extend(
            int(r) for r in np.asarray(rounds_a, np.int64))
        # Under "pic" the per-step entries are fresh POSITION counts; the
        # n· multiply happens here on host ints (no uint32 wrap).
        scale = n if ctx.mode == "pic" else 1
        res.evals_by_phase["build"] = (
            scale * int(np.asarray(evals_a, np.int64).sum()) + n * self.k)
        if ctx.mode == "pic":
            res.evals_by_phase["build_cached"] = int(
                np.asarray(cached_a, np.int64).sum())
        return medoids, med_mask, key

    # -- SWAP -----------------------------------------------------------
    def _swap(self, data: jnp.ndarray, medoids: jnp.ndarray,
              med_mask: jnp.ndarray, key: jax.Array, ctx: FitContext,
              res: FitResult):
        n = data.shape[0]
        delta = (self.delta if self.delta is not None
                 else 1.0 / (1000.0 * self.k * n))
        swap_evals = 0
        swap_cached = 0
        # The running loss stays DEVICE-resident between iterations
        # (prev_loss_d feeds the next step's accept rule without a
        # host→device re-upload); the host mirror only serves the report.
        prev_loss_d = total_loss(data, medoids, metric=self.metric)
        loss = float(host_read(prev_loss_d))
        converged = False
        carry = None  # (sums, sqsums, rounds, d1, d2, assign) of last search
        kw = dict(backend=ctx.backend, metric=self.metric,
                  batch_size=self.batch_size, delta=delta, k=self.k,
                  sampling=self.sampling, baseline=self.baseline,
                  early_stop=self.swap_early_stop, mode=ctx.mode,
                  free_rounds=ctx.free_rounds)
        step = counted_dispatch(
            _swap_iter_jit if self.fused else self._swap_iter_stepped,
            res.dispatches_by_phase, "swap")
        for _ in range(self.max_swaps):
            with span("swap.iter"):
                key, sub = jax.random.split(key)
                (best, new_loss_d, cand, new_mask, old_med, new_carry, cache,
                 fresh, cached, n_changed, used_exact, accept) = step(
                     data, medoids, med_mask, sub, ctx.cache, ctx.dwarm,
                     ctx.perm, ctx.perm_idx, ctx.perm_w, carry,
                     prev_loss_d, **kw)
                ctx.cache = cache
                # ONE explicit host read per iteration: every ledger
                # counter, the displaced medoid and the accept bit come
                # back in a single device_get, so the loop is one
                # dispatch + one sanctioned read under the transfer guard.
                (best_h, new_loss_h, old_h, fresh_h, cached_h, n_changed_h,
                 used_exact_h, accept_h) = host_read(
                     (best, new_loss_d, old_med, fresh, cached, n_changed,
                      used_exact, accept))
            # Under "pic", fresh counts POSITIONS and n_changed counts
            # repaired points; the n· multiplies run on host ints so the
            # ledger cannot wrap at large n.
            scale = n if ctx.mode == "pic" else 1
            swap_evals += 2 * n * self.k + scale * int(fresh_h)
            swap_cached += int(cached_h) + n * int(n_changed_h)
            res.swap_exact_fallbacks += int(used_exact_h)
            if ctx.mode == "pic":
                carry = new_carry
            # The accept rule is evaluated ON DEVICE in f32 (inside
            # _swap_iter) — the same comparison every fit_batch lane
            # makes — so the two drivers cannot diverge at fp margins.
            # On accept the driver only SELECTS the in-trace results
            # (cand/new_mask); the running loss stays device-resident.
            if bool(accept_h):
                x_idx = int(best_h) % n
                medoids = cand
                med_mask = new_mask
                res.swap_history.append((int(old_h), x_idx,
                                         float(new_loss_h)))
                loss = float(new_loss_h)
                prev_loss_d = new_loss_d
            else:
                converged = True
                break
        res.evals_by_phase["swap"] = swap_evals
        if ctx.mode == "pic":
            res.evals_by_phase["swap_cached"] = swap_cached
        return medoids, loss, converged

    def _swap_iter_stepped(self, data, medoids, med_mask, key, cache, dwarm,
                           perm, perm_idx, perm_w, carry, prev_loss, *,
                           backend, metric, batch_size, delta, k, sampling,
                           baseline, early_stop, mode, free_rounds):
        """Host-orchestrated SWAP iteration (benchmark baseline): the same
        sub-steps as ``_swap_iter`` but as separate dispatches with host
        round-trips between — the pre-refactor driver architecture."""
        n = data.shape[0]
        B = batch_size
        d1, d2, assign = medoid_cache(data, medoids, metric=metric)
        jax.block_until_ready(d1)
        init_sums = init_sqsums = None
        init_rounds = 0
        n_changed = 0
        if carry is not None:
            c_sums, c_sq, c_rounds, d1o, d2o, ao = carry
            if bool(carry_valid(cache, B)):
                # Host branch of the fused driver's lax.cond: the repair
                # only runs while the carried prefix is ring-resident.
                init_sums, init_sqsums, nc = _carry_delta(
                    cache.cols, perm_idx, perm_w, c_rounds * B,
                    d1o, d2o, ao, d1, d2, assign, c_sums, c_sq,
                    k=k, backend=backend)
                init_rounds = c_rounds
                n_changed = int(nc)
            else:
                init_sums = jnp.zeros_like(c_sums)
                init_sqsums = jnp.zeros_like(c_sq)
        sr = _swap_search_jit(data, d1, d2, assign, med_mask, key, cache,
                              dwarm, perm, init_sums, init_sqsums,
                              init_rounds, backend=backend, metric=metric,
                              batch_size=B, delta=delta, k=k,
                              sampling=sampling, baseline=baseline,
                              early_stop=early_stop, mode=mode,
                              free_rounds=free_rounds)
        if mode == "pic":
            cache2 = sr.aux
            fresh = int(fresh_positions(cache, cache2))
        else:
            cache2 = cache
            fresh = int(sr.n_evals)
        m_idx, x_idx = divmod(int(sr.best), n)
        cand = medoids.at[m_idx].set(x_idx)
        new_loss = total_loss(data, cand, metric=metric)
        # Same f32 accept rule as the fused step (see _swap_iter).
        accept = new_loss < prev_loss - 1e-7 * jnp.maximum(
            1.0, jnp.abs(prev_loss))
        new_carry = (sr.sums, sr.sqsums, sr.rounds, d1, d2, assign)
        old_med = medoids[m_idx]
        new_mask = med_mask.at[old_med].set(False).at[x_idx].set(True)
        return (int(sr.best), new_loss, cand, new_mask, old_med, new_carry,
                cache2, fresh, int(sr.n_evals_cached), n_changed,
                int(sr.used_exact), accept)

    # -- public ----------------------------------------------------------
    @span("fit")
    def fit(self, data, warm_start=None) -> FitResult:
        """Fit medoids; ``warm_start`` (optional ``[k]`` indices) skips
        BUILD and seeds SWAP from the given medoids.

        The warm path is the serving layer's incremental refit: BUILD's
        ``n·k + rounds`` evaluations are never paid (the build ledger
        entry records 0), the context key is still drawn first so a
        ``reuse="pic"`` ring fills identically to a cold fit, and the
        BUILD subkeys are simply not consumed — the SWAP chain is
        deterministic given (seed, warm_start) but intentionally distinct
        from the cold fit's chain.
        """
        with host_stage("fit staging: input upload"):
            data = jnp.asarray(data, jnp.float32)
        n = data.shape[0]
        if n <= self.k:
            raise ValueError("need n > k")
        backend = resolve_stats_backend(self.backend, self.metric)
        res = FitResult(medoids=np.zeros(self.k, np.int64), loss=np.inf,
                        n_swaps=0, converged=False, distance_evals=0)
        with host_stage("fit staging: RNG chain head + context upload"):
            key = jax.random.PRNGKey(self.seed)
            key, ckey = jax.random.split(key)
            ctx = self._make_context(data, ckey, backend, res)
            if warm_start is not None:
                ws = np.asarray(warm_start, np.int64).ravel()
                if ws.shape[0] != self.k or len(set(ws.tolist())) != self.k:
                    raise ValueError(
                        f"warm_start must be {self.k} distinct medoid "
                        f"indices, got {ws.tolist()}")
                if ws.min() < 0 or ws.max() >= n:
                    raise ValueError(f"warm_start indices out of range "
                                     f"[0, {n})")
                ctx.warm_medoids = jnp.asarray(ws, jnp.int32)
        with phase("build", res.wall_by_phase):
            if ctx.warm_medoids is not None:
                medoids = ctx.warm_medoids
                with host_stage("warm-start staging: medoid mask upload"):
                    med_mask = jnp.zeros((n,), jnp.bool_).at[medoids].set(
                        True)
                res.evals_by_phase["build"] = 0
            else:
                medoids, med_mask, key = self._build(data, key, ctx, res)
            jax.block_until_ready(medoids)
        with phase("swap", res.wall_by_phase):
            medoids, loss, converged = self._swap(data, medoids, med_mask,
                                                  key, ctx, res)
        # The ring's evicted-round replays: a position count, multiplied
        # by n on host ints (no uint32 wrap).
        refresh = ctx.cache.refresh_pos if ctx.mode == "pic" else 0
        medoids_h, refresh_h = host_read((medoids, refresh))
        res.medoids = np.asarray(medoids_h)
        res.refresh_evals = n * int(refresh_h)
        res.loss = loss
        res.n_swaps = len(res.swap_history)
        res.converged = converged
        res.distance_evals = sum(v for ph, v in res.evals_by_phase.items()
                                 if not ph.endswith("_cached"))
        res.cached_evals = sum(v for ph, v in res.evals_by_phase.items()
                               if ph.endswith("_cached"))
        # Feed the measured phase walls back to the tile tuner: the next
        # resolve for this (n, d, k, device, backend) shape class prefers
        # the fastest observed config over the VMEM heuristic.
        observe_tiles(n, data.shape[1], self.k,
                      resolve_tile_config(n, data.shape[1], self.k,
                                          backend=backend),
                      res.wall_by_phase, backend=backend)
        return res

    @span("fit")
    def fit_batch(self, datasets, seeds=None) -> BatchFitReport:
        """Fit a batch of INDEPENDENT datasets in one dispatch per phase.

        Args:
          datasets: a ``[B, n, d]`` array, or a list of ``[n_i, d]``
            arrays with ragged ``n_i`` (padded internally to the batch
            maximum; pad rows are masked out of every sum, can never
            become medoids, and carry zero reference weight).
          seeds: optional per-fit RNG seeds, length B; default: every fit
            uses ``self.seed`` (fits are still independent — they see
            different data).

        Each fit reproduces ``BanditPAM(seed=seeds[i]).fit(datasets[i])``
        bit-identically — same medoids, loss, and fresh/cached ledger —
        because every lane replays the single-fit trace: the per-fit RNG
        chain (context key, k BUILD subkeys, per-iteration SWAP subkeys,
        per-search reference permutations) is replicated host-side with
        the same ``jax.random`` ops, the per-fit budget/δ ride in as
        traced ``n_valid``/``log_term`` data, and the batch axis is a
        ``lax.map`` scan (see ``_build_batch``).  Requires
        ``sampling="permutation"`` and ``cache_cols=0``; under
        ``reuse="pic"`` the ring width is resolved from the LARGEST fit,
        so the ragged-parity guarantee holds as long as no fit recycles
        (the default width covers every fit that would not recycle
        solo — see docs/design.md).

        Returns a :class:`BatchFitReport`: per-fit :class:`FitReport`
        list plus batch-level ``dispatches_by_phase`` (one per phase,
        measured) and ``wall_by_phase``.
        """
        if self.sampling != "permutation":
            raise ValueError('fit_batch requires sampling="permutation" '
                             "(per-fit reference layouts are precomputed)")
        if self.cache_cols > 0:
            raise ValueError("fit_batch does not support cache_cols warm "
                             "blocks (ragged per-fit warm widths would "
                             "need per-fit traces); use reuse='pic'")
        if isinstance(datasets, (list, tuple)):
            arrs = [np.asarray(a, np.float32) for a in datasets]
        else:
            a = np.asarray(datasets, np.float32)
            if a.ndim != 3:
                raise ValueError(f"expected [B, n, d] batch or a list of "
                                 f"[n_i, d] arrays, got shape {a.shape}")
            arrs = [a[i] for i in range(a.shape[0])]
        if not arrs:
            raise ValueError("empty batch")
        if any(x.ndim != 2 for x in arrs):
            raise ValueError("every dataset must be [n_i, d]")
        if len({x.shape[1] for x in arrs}) != 1:
            raise ValueError("all datasets must share the feature dim")
        ns = [x.shape[0] for x in arrs]
        if min(ns) <= self.k:
            raise ValueError("need n > k in every dataset")
        if seeds is None:
            seeds = [self.seed] * len(arrs)
        seeds = [int(s) for s in seeds]
        if len(seeds) != len(arrs):
            raise ValueError(f"{len(seeds)} seeds for {len(arrs)} datasets")

        bf, n_max, dim = len(arrs), max(ns), arrs[0].shape[1]
        k, B, T = self.k, self.batch_size, self.max_swaps
        backend = resolve_stats_backend(self.backend, self.metric)
        pic = self.reuse == "pic"
        rb = -(-n_max // B) * B           # search-layout width (R·B)
        data = np.zeros((bf, n_max, dim), np.float32)
        valid = np.zeros((bf, n_max), bool)
        for i, x in enumerate(arrs):
            data[i, : ns[i]] = x
            valid[i, : ns[i]] = True

        # -- host-side replication of every per-fit RNG chain ------------
        # (jax.random keys/splits/permutations are deterministic bit ops,
        # identical inside and outside jit — and identical under vmap, so
        # the whole batch's chains are ONE dispatch plus one permutation
        # dispatch per distinct n, not ~70 tiny ops per fit)
        spw = np.zeros((bf, rb), np.float32)
        log_b = np.zeros((bf,), np.float32)
        log_s = np.zeros((bf,), np.float32)
        sp_build = None if pic else np.zeros((bf, k, rb), np.int32)
        sp_swap = None if pic else np.zeros((bf, T, rb), np.int32)
        sp_pic = np.zeros((bf, rb), np.int32) if pic else None
        if pic:
            wcap = resolve_batch_cache_rounds(ns, B, self.cache_width)
            pidx_c = np.zeros((bf, wcap * B), np.int32)
            pw_c = np.zeros((bf, wcap * B), np.float32)
        else:
            wcap, pidx_c, pw_c = 0, None, None

        with host_stage("fit_batch staging: per-fit RNG chain replication"):
            ckeys, bkeys, skeys, bpk, spk = _batch_rng_chains(
                jnp.asarray(seeds), k=k, T=T)
            bkeys, skeys = np.asarray(bkeys), np.asarray(skeys)

        def tiled(perm_np, width):
            return np.tile(perm_np, -(-width // perm_np.shape[-1])
                           )[..., :width]

        by_n: dict = {}
        for i, n_i in enumerate(ns):
            by_n.setdefault(n_i, []).append(i)
        with host_stage("fit_batch staging: per-fit reference permutations"):
            for n_i, idxs in by_n.items():
                ii = np.asarray(idxs)
                if pic:
                    # one fixed permutation per fit, from the context key
                    perms = np.asarray(_batch_perms(ckeys[ii], n=n_i))
                    sp_pic[ii] = tiled(perms, rb)
                    pidx_c[ii] = tiled(perms, wcap * B)
                    pw_c[ii] = np.arange(wcap * B) < n_i
                else:
                    # one permutation per search: k BUILD + T SWAP, batched
                    pkeys = jnp.concatenate(
                        [bpk[ii].reshape(-1, 2), spk[ii].reshape(-1, 2)])
                    perms = np.asarray(_batch_perms(pkeys, n=n_i))
                    g = len(ii)
                    sp_build[ii] = tiled(perms[:g * k].reshape(g, k, n_i),
                                         rb)
                    sp_swap[ii] = tiled(perms[g * k:].reshape(g, T, n_i),
                                        rb)
        for i, n_i in enumerate(ns):
            spw[i] = np.arange(rb) < n_i
        d_b = [self.delta if self.delta is not None
               else 1.0 / (1000.0 * n_i) for n_i in ns]
        d_s = [self.delta if self.delta is not None
               else 1.0 / (1000.0 * k * n_i) for n_i in ns]
        # bit-for-bit the expression adaptive_search folds at trace time,
        # jnp.float32(jnp.log(1.0 / d)): the reciprocal in f64, the cast
        # and the log in f32 — vectorised to two dispatches for the batch
        with host_stage("fit_batch staging: folded log(1/delta) terms"):
            log_b[:] = np.asarray(jnp.log(jnp.asarray(
                1.0 / np.asarray(d_b, np.float64), jnp.float32)))
            log_s[:] = np.asarray(jnp.log(jnp.asarray(
                1.0 / np.asarray(d_s, np.float64), jnp.float32)))

        # The batched FitContext: same container as the single-fit path,
        # leading [batch] axis on every array field (batch > 0).
        with host_stage("fit_batch staging: batched context + data upload"):
            ctx = FitContext(
                mode="pic" if pic else "none", backend=backend,
                perm_idx=None if pidx_c is None else jnp.asarray(pidx_c),
                perm_w=None if pw_c is None else jnp.asarray(pw_c),
                cache=(PicCache(
                    cols=jnp.zeros((bf, n_max, wcap * B), jnp.float32),
                    hw=jnp.zeros((bf,), jnp.int32),
                    fresh_pos=jnp.zeros((bf,), jnp.uint32),
                    refresh_pos=jnp.zeros((bf,), jnp.uint32)) if pic
                       else None),
                batch=bf, valid=jnp.asarray(valid),
                n_valid=jnp.asarray(ns, jnp.int32),
                log_build=jnp.asarray(log_b), log_swap=jnp.asarray(log_s),
                spidx_build=jnp.asarray(sp_pic if pic else sp_build),
                spidx_swap=jnp.asarray(sp_pic if pic else sp_swap),
                spw=jnp.asarray(spw))
            dataj = jnp.asarray(data)
            bkeys_j, skeys_j = jnp.asarray(bkeys), jnp.asarray(skeys)
        disp: dict = {}
        kw = dict(backend=backend, metric=self.metric, batch_size=B,
                  delta=self.delta, sampling=self.sampling,
                  baseline=self.baseline, k=k, mode=ctx.mode, free_rounds=0)

        wall: dict = {}
        with phase("build", wall):
            bphase = counted_dispatch(_build_batch, disp, "build")
            (med_mask, medoids, cache, rounds_a, evals_a, cached_a) = bphase(
                dataj, bkeys_j, ctx.cache, ctx.spidx_build, ctx.spw,
                ctx.valid, ctx.n_valid, ctx.log_build, **kw)
            jax.block_until_ready(medoids)
            ctx.cache = cache

        kw.pop("sampling")
        with phase("swap", wall):
            sphase = counted_dispatch(_swap_batch, disp, "swap")
            (meds_f, loss_f, conv, iters, fresh_s, cached_s, nchg_s,
             exact_s, refresh_s, old_a, new_a, loss_a, acc_a) = sphase(
                 dataj, medoids, med_mask, skeys_j, ctx.cache,
                 ctx.perm_idx, ctx.perm_w, ctx.spidx_swap, ctx.spw,
                 ctx.valid, ctx.n_valid, ctx.log_swap,
                 sampling=self.sampling, early_stop=self.swap_early_stop,
                 max_swaps=T, **kw)
            jax.block_until_ready(loss_f)

        # -- per-fit ledger assembly (host ints: no uint32 wrap) ---------
        # ONE explicit device→host read for the whole batch: every
        # medoid/loss/ledger array comes back in a single device_get, so
        # the batch driver mirrors the single-fit guard contract (one
        # dispatch per phase + sanctioned reads only).
        (meds_np, loss_np, conv_np, iters_np, rounds_np, bev_np, bca_np,
         fresh_np, cached_np, nchg_np, exact_np, refresh_np, old_np, new_np,
         la_np, acc_np) = host_read(
            (meds_f, loss_f, conv, iters, rounds_a, evals_a, cached_a,
             fresh_s, cached_s, nchg_s, exact_s, refresh_s, old_a, new_a,
             loss_a, acc_a))
        iters_np = np.asarray(iters_np, np.int64)
        rounds_np = np.asarray(rounds_np, np.int64)
        bev_np = np.asarray(bev_np, np.int64)
        bca_np = np.asarray(bca_np, np.int64)
        fresh_np, cached_np = (np.asarray(fresh_np, np.int64),
                               np.asarray(cached_np, np.int64))
        nchg_np, exact_np = (np.asarray(nchg_np, np.int64),
                             np.asarray(exact_np, np.int64))
        reports = []
        for i, n_i in enumerate(ns):
            scale = n_i if pic else 1
            res = FitReport(medoids=meds_np[i].astype(np.int64),
                            loss=float(loss_np[i]), n_swaps=0,
                            converged=bool(conv_np[i]), distance_evals=0)
            res.build_rounds = [int(r) for r in rounds_np[i]]
            res.evals_by_phase["build"] = (scale * int(bev_np[i].sum())
                                           + n_i * k)
            if pic:
                res.evals_by_phase["build_cached"] = int(bca_np[i].sum())
            it = int(iters_np[i])
            res.evals_by_phase["swap"] = (it * 2 * n_i * k
                                          + scale * int(fresh_np[i]))
            if pic:
                res.evals_by_phase["swap_cached"] = (
                    int(cached_np[i]) + n_i * int(nchg_np[i]))
                res.refresh_evals = n_i * int(refresh_np[i])
            res.swap_exact_fallbacks = int(exact_np[i])
            for t in range(it):
                if acc_np[i, t]:
                    res.swap_history.append((int(old_np[i, t]),
                                             int(new_np[i, t]),
                                             float(la_np[i, t])))
            res.n_swaps = len(res.swap_history)
            res.distance_evals = sum(
                v for ph, v in res.evals_by_phase.items()
                if not ph.endswith("_cached"))
            res.cached_evals = sum(
                v for ph, v in res.evals_by_phase.items()
                if ph.endswith("_cached"))
            reports.append(res)
        return BatchFitReport(reports=reports, medoids=meds_np,
                              loss=loss_np.astype(np.float64),
                              n_valid=np.asarray(ns, np.int64),
                              wall_by_phase=wall, dispatches_by_phase=disp)

    def fit_predict(self, data) -> np.ndarray:
        """Fit and return the in-sample cluster labels, [n] — the sklearn
        convention.  (The legacy ``(FitReport, labels)`` tuple return was
        FutureWarning-deprecated and is now removed; call :meth:`fit` for
        the full report — it carries the same medoids/ledger, and the
        facade ``repro.api.KMedoids`` fills ``report.labels``.)"""
        res = self.fit(data)
        data = jnp.asarray(data, jnp.float32)
        _, _, assign = medoid_cache(data, jnp.asarray(res.medoids,
                                                      jnp.int32),
                                    metric=self.metric)
        return np.asarray(assign)

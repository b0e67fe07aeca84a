"""The BanditPAM++ permutation-invariant column (PIC) cache — bounded
width, round recycling, and one layout for the single-device and
mesh-sharded drivers.

The cache stores whole distance columns ``d(·, y)`` for the reference
points consumed by the bandit rounds of one fit.  Because every search
walks the SAME fixed reference permutation, round ``r`` always consumes
the same reference slice, so its column block can be materialised once
and replayed by every later search (BanditPAM++, Tiwari et al. 2023).

Historically the device buffer was preallocated at full width
``[n, n_rounds_max·B]`` — O(n²) floats, which is exactly what stops
``reuse="pic"`` from scaling past ~10⁵ points per host.  This module
bounds it:

* **Bounded width** — the buffer holds at most ``W`` round-blocks
  (``cache_width`` columns, default a few dozen round-batches), so the
  footprint is O(n·W) with ``W ≪ n``.
* **Round recycling** — rounds land in ring slots ``r mod W``; when a
  search materialises a round past the capacity, the slot of the oldest
  resident round is recycled (evicted).  The resident window is always
  the trailing ``[hw − W, hw)`` of the ``hw`` rounds ever materialised.
* **Exact fallback** — a round outside the window is simply recomputed
  fresh (and NOT retained, so the window invariant survives): the
  replayed block is bit-identical to the evicted one, so medoids, loss,
  and the exactness of the ledger are unchanged — only the fresh/cached
  split shifts, which ``fresh_pos`` tracks precisely.

Ledger rule: ``fresh_pos`` accumulates the *effective* (non-padding)
reference positions of every round the fit computed fresh — first
materialisations and evicted-round replays alike — and a fresh
evaluation costs ``n`` per position (a full column, which is what makes
the position free for every arm of every later search that finds it
resident).  ``refresh_pos`` counts the replays alone: the part of
``fresh_pos`` that a wider ring would have served.  Window-served rounds
are tallied by ``adaptive_search`` as cached reads at the algorithmic
``count_fn·B`` rate.

The carried-moment reuse (virtual arms) reads the permutation *prefix*
``[0, c_rounds)`` of the cache; that prefix is resident — and ring slots
are the identity mapping — exactly while ``hw ≤ W``, so the drivers mask
the carry off once recycling has started (``carry_valid``).

Sharded layout (``core.distributed``): the same ring, split over the
mesh's data axes by reference ownership — each shard holds the
``[n, W·b_loc]`` block of the columns its own rows produce (``b_loc =
B / n_shards``), updated from inside ``shard_map`` via
:func:`shard_slot_read_write`; the ``hw``/``fresh_pos`` scalars are
replicated and advanced outside the collective.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["PicCache", "DEFAULT_CACHE_ROUNDS", "resolve_cache_rounds",
           "resolve_batch_cache_rounds", "make_cache",
           "cache_read_or_write", "cache_advance", "shard_slot_read_write",
           "carry_valid", "fresh_positions"]

# Default width cap in round-blocks: generous enough that tier-scale fits
# (n up to a few thousand at B=100) never recycle — their ledgers stay
# bit-identical to the historical unbounded buffer — while keeping the
# footprint O(n·W·B) at large n (3 orders of magnitude under O(n²) at
# n = 10⁵, B = 100).
DEFAULT_CACHE_ROUNDS = 32


class PicCache(NamedTuple):
    """Device-resident cache state threaded through the search carry.

    ``cols`` — the ring of round-column blocks.  Single-device:
    ``[n, W·B]``.  Sharded: ``[n, n_shards·W·b_loc]``, sharded over the
    column axis so each shard owns its own rows' columns.
    ``hw`` — int32, total rounds ever materialised (monotone; the
    resident window is ``[max(hw − W, 0), hw)``).
    ``fresh_pos`` — uint32, cumulative effective reference positions
    computed fresh (materialisations + evicted-round replays); the fresh
    ledger of a search is ``n · Δfresh_pos`` (:func:`fresh_positions`,
    multiplied by ``n`` host-side).
    ``refresh_pos`` — uint32, the evicted-round replays among them
    (``FitReport.refresh_evals`` is ``n`` times it).
    """

    cols: jnp.ndarray
    hw: jnp.ndarray
    fresh_pos: jnp.ndarray
    refresh_pos: jnp.ndarray


def resolve_cache_rounds(n_rounds_max: int, batch_size: int,
                         cache_width: Optional[int] = None) -> int:
    """Resolve the ``cache_width`` knob (columns) to a round-block count.

    ``None`` → ``min(n_rounds_max, DEFAULT_CACHE_ROUNDS)``; otherwise the
    width is rounded DOWN to whole round-blocks (the ring recycles whole
    rounds) and clamped to ``[1, n_rounds_max]``.  ``cache_width ≥
    batch_size`` is required — a cache narrower than one round-block can
    never serve a read.
    """
    if cache_width is None:
        return min(n_rounds_max, DEFAULT_CACHE_ROUNDS)
    cache_width = int(cache_width)
    if cache_width < batch_size:
        raise ValueError(
            f"cache_width={cache_width} is narrower than one round-batch "
            f"(batch_size={batch_size}); need cache_width >= batch_size")
    return max(1, min(n_rounds_max, cache_width // batch_size))


def resolve_batch_cache_rounds(ns, batch_size: int,
                               cache_width: Optional[int] = None) -> int:
    """One ring width for a BATCH of padded fits (``fit_batch``): the max
    of each fit's solo-resolved width, so every lane gets at least the
    ring it would have had alone — the bit-parity guarantee of the
    batched path then holds exactly as far as the single-fit one does
    (a fit that would not recycle solo does not recycle in the batch).
    Lanes with smaller n simply leave their trailing slots cold."""
    return max(resolve_cache_rounds(-(-int(n) // batch_size), batch_size,
                                    cache_width) for n in ns)


def make_cache(n_rows: int, block: int, rounds: int) -> PicCache:
    """Fresh all-cold cache: ``rounds`` ring slots of ``block`` columns."""
    return PicCache(cols=jnp.zeros((n_rows, rounds * block), jnp.float32),
                    hw=jnp.int32(0), fresh_pos=jnp.uint32(0),
                    refresh_pos=jnp.uint32(0))


def shard_slot_read_write(cols: jnp.ndarray, rnd, hw, block: int,
                          compute_fresh):
    """One ring access on a (possibly shard-local) column buffer.

    Serves round ``rnd`` from its ring slot when it lies in the resident
    window ``[hw − W, hw)``; otherwise calls ``compute_fresh() ->
    [rows, block]`` and retains the block only when it is a NEW round
    (``rnd ≥ hw`` — retaining an evicted replay would evict a newer
    round and break the trailing-window invariant).  Returns
    ``(block, cols')``; the caller advances ``hw``.

    Device scopes: the ring's read, write and bookkeeping are ``pic``,
    the fresh pass is ``fresh`` (kept apart, not nested).
    """
    W = cols.shape[1] // block
    with jax.named_scope("pic"):
        lo = jnp.maximum(hw - W, 0)
        in_window = jnp.logical_and(rnd >= lo, rnd < hw)
        slot = (rnd % W) * block

    def cached(c):
        with jax.named_scope("pic"):
            return jax.lax.dynamic_slice_in_dim(c, slot, block, 1), c

    def fresh(c):
        with jax.named_scope("fresh"):
            dxy = compute_fresh()
        with jax.named_scope("pic"):
            c2 = jax.lax.cond(
                rnd >= hw,
                lambda cc: jax.lax.dynamic_update_slice_in_dim(cc, dxy, slot,
                                                               1),
                lambda cc: cc, c)
        return dxy, c2

    return jax.lax.cond(in_window, cached, fresh, cols)


def cache_advance(cache: PicCache, cols, rnd, b_eff,
                  rounds_cap: int) -> PicCache:
    """Post-access bookkeeping shared by every PIC stats path (single
    device and sharded): charge ``b_eff`` fresh positions unless round
    ``rnd`` was served from the resident window, charge them to
    ``refresh_pos`` too when the round was materialised before and has
    since been evicted (it lies below the window), and advance the
    high-water mark past it.  ``cols`` is the (possibly updated) ring
    buffer; ``rounds_cap`` its capacity ``W``.  The one definition of
    the window predicate + ledger rule."""
    with jax.named_scope("pic"):
        lo = jnp.maximum(cache.hw - rounds_cap, 0)
        in_window = jnp.logical_and(rnd >= lo, rnd < cache.hw)
        fresh_pos = cache.fresh_pos + jnp.where(
            in_window, 0, b_eff).astype(jnp.uint32)
        refresh_pos = cache.refresh_pos + jnp.where(
            rnd < lo, b_eff, 0).astype(jnp.uint32)
        return PicCache(cols, jnp.maximum(cache.hw, rnd + 1), fresh_pos,
                        refresh_pos)


def cache_read_or_write(be, data_p, ref_idx, *, metric: str,
                        batch_size: int, rnd, b_eff, cache: PicCache):
    """One PIC cache access inside a single-device bandit round.

    Serve round ``rnd`` from the ring when resident, else compute the
    ``[n, B]`` block fresh through the backend's pairwise path (written
    through only for new rounds).  ``data_p`` is the data set as
    ``be.align_rows`` returns it, aligned once by the calling program
    outside its loops; the fresh block gathers its references from it and
    is cropped to the ring's ``n`` rows.  ``b_eff`` is the round's
    effective (non-padding) position count — the fresh-ledger increment
    when the block is computed.  Returns ``(dxy, cache')``.
    """
    dxy, cols = shard_slot_read_write(
        cache.cols, rnd, cache.hw, batch_size,
        lambda: be.pairwise(data_p, data_p[ref_idx], metric=metric,
                            rows=cache.cols.shape[0]))
    return dxy, cache_advance(cache, cols, rnd, b_eff,
                              cache.cols.shape[1] // batch_size)


def carry_valid(cache: PicCache, block: Optional[int] = None,
                rounds_cap: Optional[int] = None):
    """Whether carried per-arm moments may seed the next search: the
    permutation prefix they were accumulated over is resident (and ring
    slots are the identity mapping) exactly while no round has been
    recycled yet.  The ring capacity is derived from ``block`` (the
    single-device round-block width) or passed as ``rounds_cap`` when
    ``cols`` is the mesh-wide sharded buffer (whose column count is
    ``n_shards·W·b_loc``, not ``W·block``)."""
    W = rounds_cap if rounds_cap is not None else cache.cols.shape[1] // block
    return cache.hw <= W


def fresh_positions(cache0: PicCache, cache1: PicCache):
    """Effective reference positions computed fresh between two cache
    states (new materialisations and evicted-round replays alike — each
    is a full column, i.e. ``n`` distance evaluations).  Returns the
    POSITION count; the drivers multiply by ``n`` on the host, where
    Python integers cannot wrap — a device-side ``n·Δ`` uint32 product
    would overflow in exactly the n ≳ 10⁵ regimes the bounded ring
    targets."""
    return cache1.fresh_pos - cache0.fresh_pos

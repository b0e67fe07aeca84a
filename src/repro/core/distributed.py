"""Distributed BanditPAM: data-sharded references x replicated/sharded arms.

The multi-device execution of Algorithm 1 (docs/design.md hardware
adaptations #2/#4, mesh conventions §2):

* The reference set is sharded over the ``data`` (and ``pod``) mesh axes —
  each device owns ``ceil(n / n_shards)`` points (the sharded view is
  padded to a shard multiple with cyclic copies; padding rows sit past
  each shard's valid-draw range so they are never sampled, shards are
  weighted by their valid-row count, and all-padding shards carry weight
  0 — padding never reaches the statistics or the loss).
* Reference sampling is **stratified**: every round each shard contributes
  ``B / n_shards`` uniform draws from its *valid* local points, weighted
  by its stratum size so the estimator of mu_x stays unbiased even when
  the strata are uneven (docs/design.md hardware adaptation #4).  Draws
  are keyed by ``(seed, phase, selection/iteration, round, shard)`` — the
  round counter is folded in explicitly, so no two rounds of a fit can
  ever see identical reference batches (Theorem 1's confidence intervals
  assume fresh, independent batches per round).
* Each device computes the g-statistics of ALL arms against its local
  reference draw **through the registered ``StatsBackend``**
  (``repro.core.engine``): one backend ``pairwise`` block plus the
  backend's from-distances statistics (for ``"pallas"`` that is the tiled
  MXU pairwise kernel and the fused cached-stats SWAP kernel).  A single
  ``psum`` over the data axes — the only collective, owned by this layer,
  never by a backend — yields the global per-arm batch sums.  Arm
  elimination runs redundantly on every device (cheap vector math, saves
  a broadcast).
* **The whole BUILD phase is ONE jit dispatch**: a ``lax.fori_loop`` over
  the k medoid selections with the ``shard_map``-ed bandit search inside
  and ``d_near`` / the medoid mask (and the sharded PIC cache) as loop
  carry — the historical one-dispatch-per-selection shape (k host syncs)
  is gone; ``benchmarks/distributed_bench.py`` asserts the single
  dispatch and records the saving.
* The SWAP loop follows the fused per-iteration step shape of the
  single-device driver (docs/design.md hardware adaptation #5): one jit
  dispatch per iteration (medoid-cache refresh + carried-moment repair +
  sharded bandit search + candidate loss); the host only reads the
  accept/converge scalar.
* ``reuse="pic"`` enables the BanditPAM++ reuse engine on the sharded
  path: reference sampling switches to a **stratified fixed permutation**
  (each shard walks a fixed random permutation of its own valid rows;
  round ``r`` is slice ``[r·b_loc, (r+1)·b_loc)`` of every shard's walk,
  so the schedule is deterministic and every point is consumed exactly
  once at full budget — stratum weights are a replacement-mode device
  and are not used), and the bounded PIC column ring
  (``repro.core.pic_cache``) is **sharded over the data axes by
  reference ownership**: each shard holds the ``[n, W·b_loc]`` block of
  the columns its own rows produce, read/written from inside
  ``shard_map`` exactly like the single-device ``adaptive_search`` aux
  threading.  Carried per-arm moments are repaired after each accepted
  swap by a per-shard delta pass over the sharded columns (one extra
  ``psum``), giving multi-swap sharded fits the same fresh/cached ledger
  split as the single-device engine.
* The hierarchical pod axis composes transparently: ``psum`` over
  ("pod", "data") is the cross-pod reduction.

``MedoidCurator`` is the LM-stack integration: it consumes embedding
shards (activations or dataset features) that already live sharded across
the data axis of a training/serving mesh and returns medoid indices +
assignments for data curation (examples/train_lm_curated.py).

The facade front-end is ``repro.api.KMedoids(solver="banditpam_dist",
mesh=..., backend=...)`` (``repro.api.registry``); without ``mesh=`` it
spans every local device (``default_mesh``).
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .adaptive import adaptive_search
from .engine import (counted_dispatch, exact_build_means, exact_swap_means,
                     get_stats_backend, medoid_cache, resolve_stats_backend,
                     total_loss)
from .pic_cache import (PicCache, cache_advance, carry_valid,
                        fresh_positions, resolve_cache_rounds,
                        shard_slot_read_write)
from .report import FitReport

__all__ = ["DistributedBanditPAM", "MedoidCurator", "default_mesh"]


def _shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def default_mesh() -> Mesh:
    """One-axis ``("data",)`` mesh spanning every local device — the
    facade's default when ``KMedoids(solver="banditpam_dist")`` is given
    no ``mesh=``."""
    devs = np.asarray(jax.devices())
    return Mesh(devs.reshape(devs.size), ("data",))


# ---------------------------------------------------------------------------
# Sharded-sampler RNG chain
#
# Key schedule: PRNGKey(seed ^ phase_tag) -> fold(selection/iteration)
# -> fold(round) -> fold(shard).  Every level is folded in explicitly, so
# two distinct (phase, step, round, shard) tuples draw independent
# batches.  (Historically the chain keyed on the adaptive loop's
# ref_idx[0] and ignored the round counter entirely, so two rounds whose
# first sampled index collided silently reused identical reference
# batches — breaking the cross-round independence the Theorem 1
# confidence intervals assume.  tests/test_distributed_fit.py holds the
# regression.)
# ---------------------------------------------------------------------------

_BUILD_TAG = 0x5EED
_SWAP_TAG = 0x50A9


def _phase_key(seed: int, tag: int, step) -> jax.Array:
    """Base key of one bandit search: ``step`` is the BUILD selection
    index or the SWAP iteration counter."""
    return jax.random.fold_in(jax.random.PRNGKey(seed ^ tag), step)


def _round_key(phase_key: jax.Array, rnd) -> jax.Array:
    """Per-round key: folds ``adaptive_search``'s round counter."""
    return jax.random.fold_in(phase_key, rnd)


def _shard_draws(round_key: jax.Array, ax, n_valid, b_loc: int) -> jnp.ndarray:
    """Shard ``ax``'s stratified draw: ``b_loc`` uniform indices into its
    valid local rows (``max(n_valid, 1)`` guards all-padding shards, whose
    stratum weight is 0 anyway)."""
    kk = jax.random.fold_in(round_key, ax)
    return jax.random.randint(kk, (b_loc,), 0, jnp.maximum(n_valid, 1))


# Compiled phase steps, shared across instances: jax.jit's cache is keyed
# on the function object, so rebuilding the step closures every fit would
# recompile both phases.  A module-level table (like the single-device
# driver's module-level jits) makes repeated fits retrace-free even when
# each fit constructs a fresh estimator — the facade registry does exactly
# that.  Keys cover everything the closures capture (see ``_step_key``).
_STEP_CACHE: dict = {}


class DistributedBanditPAM:
    """BanditPAM over a sharded reference set.

    data: [n, d] array (host); sharded internally over the mesh's data
    axes (padded to a shard multiple when n is uneven — padding rows are
    masked out of sampling, statistics, and loss).  Semantics match
    `BanditPAM` (same medoids as PAM w.h.p.); the sampling schedule
    differs (stratified per shard), so seeds are not comparable with the
    single-device class.

    ``backend`` selects the shard-local g-statistics path
    (``repro.core.engine``): ``"auto"`` | ``"pallas"`` | ``"jnp"`` or any
    registered stats backend.  The ``psum`` composition lives here; the
    backends stay collective-free.

    ``reuse="pic"`` enables the BanditPAM++ reuse engine (stratified
    fixed-permutation sampling + the mesh-sharded bounded PIC column
    ring; see the module docstring); ``cache_width`` caps the ring in
    global reference columns (default a few dozen round-batches,
    O(n·width/n_shards) memory per shard).
    """

    def __init__(self, k: int, mesh: Mesh, metric: str = "l2",
                 batch_size: int = 128, delta: Optional[float] = None,
                 max_swaps: Optional[int] = None, seed: int = 0,
                 backend: str = "auto", reuse: str = "none",
                 cache_width: Optional[int] = None):
        if reuse not in ("none", "pic"):
            raise ValueError(f"unknown reuse mode {reuse!r}")
        self.k = int(k)
        self.mesh = mesh
        self.metric = metric
        self.daxes = _data_axes(mesh)
        if not self.daxes:
            raise ValueError(f"mesh has no data axes; axis names must "
                             f"include 'data' and/or 'pod', got "
                             f"{mesh.axis_names}")
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.daxes]))
        if batch_size % self.n_shards:
            batch_size += self.n_shards - batch_size % self.n_shards
        self.batch_size = batch_size
        self.delta = delta
        self.max_swaps = max_swaps if max_swaps is not None else 4 * self.k + 10
        self.seed = seed
        self.backend = backend
        self.reuse = reuse
        self.cache_width = cache_width

    def _step_key(self, phase: str, backend: str, n: int, delta: float,
                  cache_rounds: int = 0):
        """Cache key covering everything the compiled phase closures
        capture: mesh (axes, shard count), backend, shapes, metric, the
        static batch/confidence parameters, and the cache regime."""
        return (phase, self.mesh, backend, n, self.k, self.metric,
                self.batch_size, delta, self.reuse, cache_rounds)

    # -- sharded stats ----------------------------------------------------
    def _shard_data(self, data: jnp.ndarray) -> jnp.ndarray:
        """The sharded reference view: rows padded to a shard multiple
        with cyclic copies (real points, so every metric stays NaN-free;
        the stratum weights below zero them out of the statistics).  The
        modular gather also covers n smaller than the mesh, where the
        padding wraps around the data more than once."""
        n = data.shape[0]
        n_pad = self._n_loc(n) * self.n_shards
        if n_pad != n:
            data = data[jnp.arange(n_pad) % n]
        return jax.device_put(
            data, NamedSharding(self.mesh, P(self.daxes, None)))

    def _n_loc(self, n: int) -> int:
        return -(-n // self.n_shards)

    def _flat_ax(self):
        """The shard's flattened index over the (pod, data) strata."""
        daxes = self.daxes
        if len(daxes) == 1:
            return lambda: jax.lax.axis_index(daxes[0])
        m2 = self.mesh.shape[daxes[1]]
        return lambda: (jax.lax.axis_index(daxes[0]) * m2
                        + jax.lax.axis_index(daxes[1]))

    def _stratum(self, n: int, n_loc: int, ax):
        """(valid row count, stratum weight) of shard ``ax``.

        The weight ``v·n_shards/n`` makes the equal-draws-per-shard
        estimator unbiased under uneven strata: each draw of shard s
        estimates mean_s, and sum_s (B/n_shards)·w_s·mean_s / B =
        sum_s (v_s/n)·mean_s — the global mean.  Even split ⇒ w ≡ 1."""
        v = jnp.clip(n - ax * n_loc, 0, n_loc)
        return v, v.astype(jnp.float32) * self.n_shards / n

    def _build_smap(self, be, n: int):
        """Sharded BUILD statistics: ``smap(data_f, data_l, dnear_f,
        round_key, lead) -> (sums, sqsums, cross)``, psum'd over the data
        axes.  The shard-local stats go through the stats backend; only
        the reduction is owned here."""
        metric = self.metric
        b_loc = self.batch_size // self.n_shards
        n_loc = self._n_loc(n)
        axfn = self._flat_ax()

        def local(data_f, data_l, dnear_f, rkey, lead):
            ax = axfn()
            v, cs = self._stratum(n, n_loc, ax)
            idx = _shard_draws(rkey, ax, v, b_loc)
            gidx = jnp.minimum(ax * n_loc + idx, n - 1)
            w = jnp.ones((b_loc,), jnp.float32)
            dxy = be.pairwise(data_f, data_l[idx], metric=metric)  # [n, b_loc]
            s, q, c = be.build_stats_from_d(dxy, dnear_f[gidx], w, lead)
            return (jax.lax.psum(s * cs, self.daxes),
                    jax.lax.psum(q * (cs * cs), self.daxes),
                    jax.lax.psum(c * (cs * cs), self.daxes))

        return _shard_map(local, self.mesh,
                          in_specs=(P(), P(self.daxes, None), P(), P(), P()),
                          out_specs=(P(), P(), P()))

    def _swap_smap(self, be, n: int):
        """Sharded SWAP statistics over the flattened (medoid, candidate)
        arm set: ``smap(data_f, data_l, d1_f, d2_f, assign_f, round_key,
        lead)``.  On the Pallas backend the from-distances stats hit the
        fused cached-stats kernel."""
        metric = self.metric
        k = self.k
        b_loc = self.batch_size // self.n_shards
        n_loc = self._n_loc(n)
        axfn = self._flat_ax()

        def local(data_f, data_l, d1_f, d2_f, a_f, rkey, lead):
            ax = axfn()
            v, cs = self._stratum(n, n_loc, ax)
            idx = _shard_draws(rkey, ax, v, b_loc)
            gidx = jnp.minimum(ax * n_loc + idx, n - 1)
            w = jnp.ones((b_loc,), jnp.float32)
            dxy = be.pairwise(data_f, data_l[idx], metric=metric)
            s, q, c = be.swap_stats_from_d(dxy, d1_f[gidx], d2_f[gidx],
                                           a_f[gidx], w, k, lead)
            return (jax.lax.psum(s * cs, self.daxes),
                    jax.lax.psum(q * (cs * cs), self.daxes),
                    jax.lax.psum(c * (cs * cs), self.daxes))

        return _shard_map(local, self.mesh,
                          in_specs=(P(), P(self.daxes, None), P(), P(), P(),
                                    P(), P()),
                          out_specs=(P(), P(), P()))

    def _replicated(self, fn):
        """``fn`` on every device of the mesh, operands and results
        replicated.  XLA cannot partition a Mosaic kernel, so the backend
        calls outside the sharded statistics (the exact fallback passes
        and the d_near update) run in a ``shard_map`` of their own, each
        device repeating the same work."""
        return _shard_map(fn, self.mesh, in_specs=P(), out_specs=P())

    # -- PIC: stratified permutation layout + sharded column ring ---------
    def _pic_layout(self, n: int, ckey: jax.Array):
        """Build the ``reuse="pic"`` sampling schedule and cache buffers.

        Each shard gets a fixed random permutation of its ``n_loc`` local
        rows; round ``r`` is slice ``[r·b_loc, (r+1)·b_loc)`` of every
        shard's walk.  Positions whose value falls outside the shard's
        valid rows (cyclic padding) carry weight 0, so every real point
        is consumed exactly once across the ``R_max`` rounds — at full
        budget the running mean IS the exact mean, like the single-device
        permutation mode (stratum weights are a replacement-mode device
        and are not used here).

        Returns ``(lperm, lw, perm_idx_g, perm_w_g, cache, W)``: the
        per-shard walks ``[S, R_max·b_loc]`` (sharded over the data
        axes), the matching global position layout ``[R_max·B]`` for
        ``adaptive_search``'s budget accounting, the all-cold sharded
        column ring (cols ``[n, S·W·b_loc]`` sharded by reference
        ownership), and the ring capacity in rounds.
        """
        S = self.n_shards
        b_loc = self.batch_size // S
        n_loc = self._n_loc(n)
        r_max = -(-n_loc // b_loc)
        W = resolve_cache_rounds(r_max, self.batch_size, self.cache_width)
        width_loc = r_max * b_loc
        lperm = np.empty((S, width_loc), np.int32)
        lw = np.empty((S, width_loc), np.float32)
        pos = np.arange(width_loc)
        for s in range(S):
            p = np.asarray(jax.random.permutation(
                jax.random.fold_in(ckey, s), n_loc), np.int32)
            tiled = np.tile(p, -(-width_loc // n_loc))[:width_loc]
            v = min(max(n - s * n_loc, 0), n_loc)
            lperm[s] = tiled
            lw[s] = ((pos < n_loc) & (tiled < v)).astype(np.float32)
        gidx = np.minimum(np.arange(S)[:, None] * n_loc + lperm, n - 1)
        # Global layout: round r occupies slots [r·B, (r+1)·B), shard s
        # owning the [s·b_loc, (s+1)·b_loc) sub-slice — the exact order
        # the shard-local draws are concatenated in.
        to_global = lambda a: jnp.asarray(
            a.reshape(S, r_max, b_loc).transpose(1, 0, 2).reshape(-1))
        sh_rows = NamedSharding(self.mesh, P(self.daxes, None))
        sh_cols = NamedSharding(self.mesh, P(None, self.daxes))
        lperm_d = jax.device_put(jnp.asarray(lperm), sh_rows)
        lw_d = jax.device_put(jnp.asarray(lw), sh_rows)
        cache = PicCache(
            cols=jax.device_put(
                jnp.zeros((n, S * W * b_loc), jnp.float32), sh_cols),
            hw=jnp.int32(0), fresh_pos=jnp.uint32(0))
        return (lperm_d, lw_d, to_global(gidx.astype(np.int32)),
                to_global(lw), cache, W)

    def _build_smap_pic(self, be, n: int, W: int):
        """Sharded BUILD statistics under the stratified fixed
        permutation, served through the shard-local PIC column ring:
        ``smap(data_f, data_l, dnear_f, lperm, lw, cols, rnd, hw, lead)
        -> (sums, sqsums, cross, cols')``."""
        metric = self.metric
        b_loc = self.batch_size // self.n_shards
        n_loc = self._n_loc(n)
        axfn = self._flat_ax()
        daxes = self.daxes

        def local(data_f, data_l, dnear_f, lperm, lw, cols, rnd, hw, lead):
            ax = axfn()
            lidx = jax.lax.dynamic_slice(lperm[0], (rnd * b_loc,), (b_loc,))
            w = jax.lax.dynamic_slice(lw[0], (rnd * b_loc,), (b_loc,))
            gidx = jnp.minimum(ax * n_loc + lidx, n - 1)
            dxy, cols = shard_slot_read_write(
                cols, rnd, hw, b_loc,
                lambda: be.pairwise(data_f, data_l[lidx], metric=metric))
            s, q, c = be.build_stats_from_d(dxy, dnear_f[gidx], w, lead)
            return (jax.lax.psum(s, daxes), jax.lax.psum(q, daxes),
                    jax.lax.psum(c, daxes), cols)

        return _shard_map(local, self.mesh,
                          in_specs=(P(), P(self.daxes, None), P(),
                                    P(self.daxes, None), P(self.daxes, None),
                                    P(None, self.daxes), P(), P(), P()),
                          out_specs=(P(), P(), P(), P(None, self.daxes)))

    def _swap_smap_pic(self, be, n: int, W: int):
        """Sharded SWAP statistics under the stratified fixed permutation
        + shard-local PIC ring (FastPAM1 flattened arm set)."""
        metric = self.metric
        k = self.k
        b_loc = self.batch_size // self.n_shards
        n_loc = self._n_loc(n)
        axfn = self._flat_ax()
        daxes = self.daxes

        def local(data_f, data_l, d1_f, d2_f, a_f, lperm, lw, cols, rnd, hw,
                  lead):
            ax = axfn()
            lidx = jax.lax.dynamic_slice(lperm[0], (rnd * b_loc,), (b_loc,))
            w = jax.lax.dynamic_slice(lw[0], (rnd * b_loc,), (b_loc,))
            gidx = jnp.minimum(ax * n_loc + lidx, n - 1)
            dxy, cols = shard_slot_read_write(
                cols, rnd, hw, b_loc,
                lambda: be.pairwise(data_f, data_l[lidx], metric=metric))
            s, q, c = be.swap_stats_from_d(dxy, d1_f[gidx], d2_f[gidx],
                                           a_f[gidx], w, k, lead)
            return (jax.lax.psum(s, daxes), jax.lax.psum(q, daxes),
                    jax.lax.psum(c, daxes), cols)

        return _shard_map(local, self.mesh,
                          in_specs=(P(), P(self.daxes, None), P(), P(), P(),
                                    P(self.daxes, None), P(self.daxes, None),
                                    P(None, self.daxes), P(), P(), P()),
                          out_specs=(P(), P(), P(), P(None, self.daxes)))

    def _carry_smap(self, be, n: int, W: int):
        """Carried-moment repair over the sharded PIC columns: each shard
        re-scores only its own changed prefix positions (old vs new
        medoid cache) and one ``psum`` composes the global per-arm delta
        — zero fresh distance evaluations, exactly the single-device
        ``banditpam._carry_delta`` split over reference ownership."""
        k = self.k
        b_loc = self.batch_size // self.n_shards
        n_loc = self._n_loc(n)
        width_loc = W * b_loc
        axfn = self._flat_ax()
        daxes = self.daxes

        def local(cols, lperm, lw, n_prefix_loc, d1o, d2o, ao, d1n, d2n, an):
            ax = axfn()
            pidx = lperm[0][:width_loc]
            pw = lw[0][:width_loc]
            gidx = jnp.minimum(ax * n_loc + pidx, n - 1)
            in_prefix = (jnp.arange(width_loc) < n_prefix_loc).astype(
                jnp.float32)
            b1, b2, ba = d1o[gidx], d2o[gidx], ao[gidx]
            c1, c2, ca = d1n[gidx], d2n[gidx], an[gidx]
            changed = ((b1 != c1) | (b2 != c2) | (ba != ca)).astype(
                jnp.float32)
            w = pw * in_prefix * changed
            s_old, q_old, _ = be.swap_stats_from_d(cols, b1, b2, ba, w, k,
                                                   None)
            s_new, q_new, _ = be.swap_stats_from_d(cols, c1, c2, ca, w, k,
                                                   None)
            return (jax.lax.psum(s_new - s_old, daxes),
                    jax.lax.psum(q_new - q_old, daxes),
                    jax.lax.psum(jnp.sum(w), daxes))

        return _shard_map(local, self.mesh,
                          in_specs=(P(None, self.daxes),
                                    P(self.daxes, None), P(self.daxes, None),
                                    P(), P(), P(), P(), P(), P(), P()),
                          out_specs=(P(), P(), P()))

    # -- fused phase steps -----------------------------------------------
    def _make_build_phase(self, be, n: int, delta: float, W: int):
        """The whole BUILD phase as ONE jit dispatch: ``fori_loop`` over
        the k medoid selections with the ``shard_map``-ed bandit search
        inside and d_near / the medoid mask / the sharded PIC ring as
        loop carry — the single-device ``_build_fused`` shape with the
        shard_map inside the loop.  The host reads only the final
        medoids and ledger arrays.  ``data``/``data_sh`` are jit
        arguments (not closure constants) so XLA never constant-folds
        distance blocks at compile time."""
        mode = self.reuse
        smap = (self._build_smap_pic(be, n, W) if mode == "pic"
                else self._build_smap(be, n))
        metric = self.metric
        B = self.batch_size
        k = self.k
        exact_means = self._replicated(
            lambda data, dnear: exact_build_means(be, data, dnear,
                                                  metric=metric))
        dist_row = self._replicated(
            lambda data, m: be.pairwise(data[m][None, :], data,
                                        metric=metric)[0])

        @jax.jit
        def build_phase(data, data_sh, base_key, subkeys, lperm, lw,
                        perm_idx_g, perm_w_g, cache):
            def body(i, c):
                dnear, med_mask, medoids, cc, rounds_a, evals_a, cached_a = c
                if mode == "pic":
                    def stats_fn(ref_idx, w, lead, rnd, aux):
                        s, q, cr, cols = smap(data, data_sh, dnear, lperm,
                                              lw, aux.cols, rnd, aux.hw,
                                              lead)
                        return s, q, cr, cache_advance(
                            aux, cols, rnd, jnp.sum(w), W)

                    sr = adaptive_search(
                        subkeys[i], stats_fn=stats_fn,
                        exact_fn=lambda: exact_means(data, dnear),
                        n_arms=n, n_ref=n, batch_size=B, delta=delta,
                        active_init=jnp.logical_not(med_mask),
                        sampling="permutation", baseline="leader",
                        perm_idx=perm_idx_g, perm_w=perm_w_g,
                        free_rounds=cc.hw,
                        free_lo=jnp.maximum(cc.hw - W, 0), aux_init=cc)
                else:
                    phase_key = jax.random.fold_in(base_key, i)

                    def stats_fn(ref_idx, w, lead, rnd):
                        # The adaptive loop's own (replacement-mode) draw
                        # is ignored; each shard draws locally from the
                        # round key.
                        return smap(data, data_sh, dnear,
                                    _round_key(phase_key, rnd), lead)

                    sr = adaptive_search(
                        subkeys[i], stats_fn=stats_fn,
                        exact_fn=lambda: exact_means(data, dnear),
                        n_arms=n, n_ref=n, batch_size=B, delta=delta,
                        active_init=jnp.logical_not(med_mask),
                        sampling="replacement", baseline="leader")
                m = sr.best
                medoids = medoids.at[i].set(m)
                med_mask = med_mask.at[m].set(True)
                dnear = jnp.minimum(dnear, dist_row(data, m))
                if mode == "pic":
                    # Fresh POSITION count; the host multiplies by n
                    # (a device uint32 n·Δ product would wrap at large n).
                    cc2 = sr.aux
                    fresh = fresh_positions(cc, cc2)
                    cached_a = cached_a.at[i].set(sr.n_evals_cached)
                    cc = cc2
                else:
                    fresh = sr.n_evals
                evals_a = evals_a.at[i].set(fresh)
                rounds_a = rounds_a.at[i].set(sr.rounds)
                return (dnear, med_mask, medoids, cc, rounds_a, evals_a,
                        cached_a)

            init = (jnp.full((n,), jnp.inf, jnp.float32),
                    jnp.zeros((n,), jnp.bool_),
                    jnp.zeros((k,), jnp.int32),
                    cache,
                    jnp.zeros((k,), jnp.int32),
                    jnp.zeros((k,), jnp.uint32),
                    jnp.zeros((k,), jnp.uint32))
            return jax.lax.fori_loop(0, k, body, init)

        return build_phase

    def _make_swap_iter(self, be, n: int, delta: float, W: int):
        """One SWAP iteration as ONE fused jit dispatch (hardware
        adaptation #5 shape): medoid-cache refresh (+ carried-moment
        repair over the sharded PIC columns under ``reuse="pic"``) +
        sharded bandit search + candidate loss; only the accept/converge
        scalar is read on host."""
        mode = self.reuse
        smap = (self._swap_smap_pic(be, n, W) if mode == "pic"
                else self._swap_smap(be, n))
        carry_smap = self._carry_smap(be, n, W) if mode == "pic" else None
        metric = self.metric
        B = self.batch_size
        b_loc = B // self.n_shards
        k = self.k
        exact_means = self._replicated(
            lambda data, d1, d2, assign: exact_swap_means(
                be, data, d1, d2, assign, k, metric=metric))

        @jax.jit
        def swap_iter(data, data_sh, medoids, med_mask, phase_key,
                      search_key, lperm, lw, perm_idx_g, perm_w_g, cache,
                      carry):
            d1, d2, assign = medoid_cache(data, medoids, metric=metric)
            n_changed = jnp.int32(0)
            init_sums = init_sqsums = None
            init_rounds = 0
            if mode == "pic" and carry is not None:
                # Repair the carried per-arm moments against the new
                # medoid cache from the sharded PIC columns (zero fresh
                # evals); once ring recycling has evicted part of the
                # carried prefix the repair is skipped entirely
                # (lax.cond) and the search starts cold.
                c_sums, c_sq, c_rounds, d1o, d2o, ao = carry
                valid = carry_valid(cache, rounds_cap=W)

                def repair(_):
                    ds, dq, nch = carry_smap(
                        cache.cols, lperm, lw, c_rounds * b_loc,
                        d1o, d2o, ao, d1, d2, assign)
                    return c_sums + ds, c_sq + dq, nch.astype(jnp.int32)

                def cold(_):
                    return (jnp.zeros_like(c_sums), jnp.zeros_like(c_sq),
                            jnp.int32(0))

                init_sums, init_sqsums, n_changed = jax.lax.cond(
                    valid, repair, cold, None)
                init_rounds = jnp.where(valid, c_rounds, 0)

            active0 = jnp.tile(jnp.logical_not(med_mask)[None, :],
                               (k, 1)).reshape(-1)

            def count_fn(active):
                # FastPAM1: one distance per (x, y) serves all k arms (·, x).
                any_x = jnp.any(active.reshape(k, n), axis=0)
                return jnp.sum(any_x.astype(jnp.uint32))

            def exact_fn():
                return exact_means(data, d1, d2, assign)

            if mode == "pic":
                def stats_fn(ref_idx, w, lead, rnd, aux):
                    s, q, cr, cols = smap(data, data_sh, d1, d2, assign,
                                          lperm, lw, aux.cols, rnd, aux.hw,
                                          lead)
                    return s, q, cr, cache_advance(
                        aux, cols, rnd, jnp.sum(w), W)

                sr = adaptive_search(
                    search_key, stats_fn=stats_fn, exact_fn=exact_fn,
                    n_arms=k * n, n_ref=n, batch_size=B, delta=delta,
                    active_init=active0, count_fn=count_fn,
                    sampling="permutation", baseline="leader",
                    perm_idx=perm_idx_g, perm_w=perm_w_g,
                    free_rounds=cache.hw,
                    free_lo=jnp.maximum(cache.hw - W, 0),
                    init_sums=init_sums, init_sqsums=init_sqsums,
                    init_rounds=init_rounds, aux_init=cache)
                cache2 = sr.aux
                fresh = fresh_positions(cache, cache2)
                cached = sr.n_evals_cached
            else:
                def stats_fn(ref_idx, w, lead, rnd):
                    return smap(data, data_sh, d1, d2, assign,
                                _round_key(phase_key, rnd), lead)

                sr = adaptive_search(
                    search_key, stats_fn=stats_fn, exact_fn=exact_fn,
                    n_arms=k * n, n_ref=n, batch_size=B, delta=delta,
                    active_init=active0, count_fn=count_fn,
                    sampling="replacement", baseline="leader")
                cache2 = cache
                fresh = sr.n_evals
                cached = sr.n_evals_cached
            m_idx = sr.best // n
            x_idx = sr.best % n
            cand = medoids.at[m_idx].set(x_idx)
            new_loss = total_loss(data, cand, metric=metric)
            new_carry = (sr.sums, sr.sqsums, sr.rounds, d1, d2, assign)
            # fresh is a POSITION count and n_changed a point count under
            # "pic"; the host multiplies both by n (uint32-safe).
            return (sr.best, new_loss, cand, new_carry, cache2, fresh,
                    cached, n_changed, sr.used_exact)

        return swap_iter

    # -- fit --------------------------------------------------------------
    def fit(self, data) -> FitReport:
        data = jnp.asarray(data, jnp.float32)
        n = data.shape[0]
        if n <= self.k:
            raise ValueError("need n > k")
        backend = resolve_stats_backend(self.backend, self.metric)
        be = get_stats_backend(backend)
        data_sh = self._shard_data(data)
        key = jax.random.PRNGKey(self.seed)
        res = FitReport(medoids=np.zeros(self.k, np.int64), loss=np.inf,
                        n_swaps=0, converged=False, distance_evals=0,
                        solver="banditpam_dist", metric=str(self.metric))

        pic = self.reuse == "pic"
        if pic:
            key, ckey = jax.random.split(key)
            lperm, lw, pidx_g, pw_g, cache, W = self._pic_layout(n, ckey)
        else:
            lperm = lw = pidx_g = pw_g = cache = None
            W = 0

        # BUILD — the whole phase is ONE jit dispatch (fori_loop over the
        # k selections, shard_map inside); the host reads only the final
        # medoids and ledger arrays.
        t0 = time.perf_counter()
        delta = self.delta if self.delta is not None else 1.0 / (1000.0 * n)
        ck = self._step_key("build", backend, n, delta, W)
        if ck not in _STEP_CACHE:
            _STEP_CACHE[ck] = self._make_build_phase(be, n, delta, W)
        # dispatches_by_phase is MEASURED at the call sites (one count per
        # compiled-phase call) — the bench assertion guards real behavior.
        build_phase = counted_dispatch(_STEP_CACHE[ck],
                                       res.dispatches_by_phase, "build")
        # One subkey per medoid selection, split exactly as the historical
        # per-selection host loop did, so trajectories are seed-compatible.
        subs = []
        for _ in range(self.k):
            key, sub = jax.random.split(key)
            subs.append(sub)
        (dnear, med_mask, med, cache, rounds_a, evals_a,
         cached_a) = build_phase(
            data, data_sh, jax.random.PRNGKey(self.seed ^ _BUILD_TAG),
            jnp.stack(subs), lperm, lw, pidx_g, pw_g, cache)
        res.build_rounds.extend(
            int(r) for r in np.asarray(rounds_a, np.int64))
        # Under "pic" the per-step entries are fresh POSITION counts; the
        # n· multiply happens here on host ints (no uint32 wrap).
        res.evals_by_phase["build"] = (
            (n if pic else 1) * int(np.asarray(evals_a, np.int64).sum())
            + n * self.k)
        if pic:
            res.evals_by_phase["build_cached"] = int(
                np.asarray(cached_a, np.int64).sum())
        jax.block_until_ready(dnear)
        res.wall_by_phase["build"] = time.perf_counter() - t0

        # SWAP — the fused per-iteration step; host reads accept/converge.
        t0 = time.perf_counter()
        delta_s = (self.delta if self.delta is not None
                   else 1.0 / (1000.0 * self.k * n))
        ck = self._step_key("swap", backend, n, delta_s, W)
        if ck not in _STEP_CACHE:
            _STEP_CACHE[ck] = self._make_swap_iter(be, n, delta_s, W)
        swap_iter = counted_dispatch(_STEP_CACHE[ck],
                                     res.dispatches_by_phase, "swap")
        loss = float(total_loss(data, med, metric=self.metric))
        swap_evals = 0
        swap_cached = 0
        converged = False
        carry = None
        for t in range(self.max_swaps):
            key, sub = jax.random.split(key)
            (best, new_loss_d, cand, new_carry, cache, fresh, cached,
             n_changed, used_exact) = swap_iter(
                data, data_sh, med, med_mask,
                _phase_key(self.seed, _SWAP_TAG, t), sub,
                lperm, lw, pidx_g, pw_g, cache, carry)
            # cache refresh (n·k) + candidate loss (n·k) + bandit rounds;
            # under "pic" fresh/n_changed are position/point counts and
            # the n· multiplies run on host ints (no uint32 wrap).
            swap_evals += 2 * n * self.k + (n if pic else 1) * int(fresh)
            swap_cached += int(cached) + n * int(n_changed)
            res.swap_exact_fallbacks += int(used_exact)
            if pic:
                carry = new_carry
            new_loss = float(new_loss_d)
            if new_loss < loss - 1e-7 * max(1.0, abs(loss)):
                m_idx, x_idx = divmod(int(best), n)
                old = int(med[m_idx])
                med = cand
                med_mask = med_mask.at[old].set(False).at[x_idx].set(True)
                res.swap_history.append((old, x_idx, new_loss))
                loss = new_loss
            else:
                converged = True
                break
        res.evals_by_phase["swap"] = swap_evals
        if pic:
            res.evals_by_phase["swap_cached"] = swap_cached
        res.wall_by_phase["swap"] = time.perf_counter() - t0

        res.medoids = np.asarray(med, np.int64)
        res.loss = loss
        res.n_swaps = len(res.swap_history)
        res.converged = converged
        res.distance_evals = sum(v for ph, v in res.evals_by_phase.items()
                                 if not ph.endswith("_cached"))
        res.cached_evals = sum(v for ph, v in res.evals_by_phase.items()
                               if ph.endswith("_cached"))
        return res


class MedoidCurator:
    """Embedding-space curation for the LM stack: cluster a (possibly
    sharded) embedding table with distributed BanditPAM, return medoid
    indices + assignments for coreset batch selection.

    The distributed path is gated on the *mesh's own* device count — a
    1-device mesh on a multi-device host runs the single-device solver,
    and a multi-device sub-mesh is honoured even when it covers only part
    of the host."""

    def __init__(self, k: int, mesh: Optional[Mesh] = None,
                 metric: str = "cosine", seed: int = 0,
                 backend: str = "auto"):
        self.k, self.mesh, self.metric, self.seed = k, mesh, metric, seed
        self.backend = backend

    def curate(self, embeddings) -> Tuple[np.ndarray, np.ndarray]:
        from .banditpam import BanditPAM
        emb = jnp.asarray(embeddings, jnp.float32)
        if self.mesh is not None and self.mesh.devices.size > 1:
            fit = DistributedBanditPAM(self.k, self.mesh, metric=self.metric,
                                       seed=self.seed,
                                       backend=self.backend).fit(emb)
        else:
            fit = BanditPAM(self.k, metric=self.metric, seed=self.seed,
                            baseline="leader", backend=self.backend).fit(emb)
        _, _, assign = medoid_cache(emb, jnp.asarray(fit.medoids),
                                    metric=self.metric)
        return fit.medoids, np.asarray(assign)

"""Algorithm 1 of the paper: Adaptive-Search(S_tar, S_ref, g, B, delta, sigma).

A batched UCB / successive-elimination best-arm routine, recast for TPU:

* The arm set is *static* — eliminated arms are masked, not removed, so the
  whole search is a single ``lax.while_loop`` with fixed shapes (hardware
  adaptation #1 in docs/design.md).  The *algorithmic* number of distance
  evaluations (what the paper counts and what real hardware pays with the
  compacted execution) is tracked exactly via ``count_fn``.
* Arm statistics are streamed: ``stats_fn`` returns per-arm batch *sums*,
  *square-sums* and *leader cross-sums*, never materialising an
  ``[arms, B]`` tensor in HBM.  This is what allows the SWAP step to use the
  FastPAM1 rewrite (one distance per ``(x, y)`` shared across all k
  medoid-arms) as a single matmul.

Two sampling modes:

* ``"replacement"`` — the paper's §3.2 literal procedure: i.i.d. uniform
  batches; if the budget (``n_used ≥ |S_ref|``) is exhausted with >1
  surviving arm, survivors are resolved exactly (Algorithm 1 lines 13–15).
* ``"permutation"`` (default) — the paper's own Appendix 2.2 refinement:
  batches are consecutive slices of a fixed random permutation of S_ref
  (sampling without replacement).  The confidence interval gains a
  finite-population factor ``sqrt(1 − n_used/n_ref)`` (Serfling/Hoeffding
  for simple random sampling), so at full budget the running mean *is* the
  exact mean and CI = 0 — survivors resolve without the separate exact
  pass.  Theorem 2's proof does not require cross-round independence of the
  reference sampling, so correctness guarantees carry over.

Beyond-paper optimization (``baseline="leader"``): every arm is evaluated on
the *same* reference batch, so for any two arms the difference estimator
``μ̂_x − μ̂_lead`` has variance ``Var(g_x(J) − g_lead(J))`` — typically far
smaller than ``σ_x² + σ_lead²`` for the near-optimal arms that dominate the
paper's cost bound (their g-returns are strongly positively correlated).
After a pilot round picks a leader, we additionally track differenced
statistics ``D_x = g_x − g_lead`` and eliminate on *either* the raw CI rule
(paper) or the differenced CI rule.  Both are valid 1−δ confidence
sequences for quantities whose argmin is the same arm, so the union-bound
correctness argument of Theorem 1 carries through (with 2δ in place of δ).
Final selection still uses the raw running means (exact at full budget in
permutation mode), so the returned arm matches PAM's argmin exactly.

BanditPAM++ reuse (``init_sums`` / ``init_sqsums`` / ``init_rounds``): in
permutation mode over a FIXED shared permutation, the per-arm moments
accumulated over a prefix of the reference stream are *permutation-invariant
cacheable* — a later call whose arms' ``g`` returns are unchanged (or whose
caller has delta-corrected the moments for the arms that did change) may
seed the search with them and resume mid-stream, paying zero evaluations
for the carried prefix.  See ``repro.core.banditpam`` for the SWAP-phase
driver that exploits this across swap iterations.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

# Per-arm sub-Gaussianity floor: keeps CIs finite for degenerate arms whose
# first-batch returns are constant (e.g. duplicated points).
SIGMA_FLOOR = 1e-8

# Deterministic tie-break for the differenced-CI (leader) elimination
# rule: the leader's own differenced statistics are exactly 0, so
# near-leader arms' margins sit on floating-point ties where a ~1e-6
# backend-dependent distance delta (Pallas vs jnp) used to decide kills.
# Requiring the margin to clear a small fraction of the arm's RAW
# confidence width — orders of magnitude above fp noise, orders of
# magnitude below any gap the rule can genuinely resolve — makes the
# per-round survivor sets (and hence the eval ledgers) identical across
# stats backends.  A kill this margin delays is re-taken within a few
# rounds (the CI shrinks as 1/sqrt(t)), so the variance-reduction win is
# untouched.  See docs/design.md, Testing conventions.
LEAD_TIE_REL = 1e-2


class SearchResult(NamedTuple):
    best: jnp.ndarray        # int32 index into the (flattened) arm set
    mu_best: jnp.ndarray     # estimated/exact objective of the winner
    n_evals: jnp.ndarray     # uint32: fresh algorithmic distance evaluations
    rounds: jnp.ndarray      # int32: bandit rounds executed (absolute, incl. carried)
    used_exact: jnp.ndarray  # bool: fell through to exact computation
    n_survivors: jnp.ndarray # int32: surviving arms at loop exit
    n_evals_cached: jnp.ndarray  # uint32: evaluations served from a cache
    sums: jnp.ndarray        # [arms] final Σ g over the consumed prefix
    sqsums: jnp.ndarray      # [arms] final Σ g² over the consumed prefix
    aux: Any = ()            # caller state threaded through the search carry
    #                          (device PIC cache buffer + high-water mark)


class _State(NamedTuple):
    key: jax.Array
    sums: jnp.ndarray        # [arms] Σ g (from round 1, incl. carried seed)
    sqsums: jnp.ndarray      # [arms] Σ g² (carried across calls for PIC reuse)
    sigma: jnp.ndarray       # [arms] per-arm sub-Gaussian scale (Eq. 11)
    active: jnp.ndarray      # [arms] bool survivor mask
    n_used: jnp.ndarray      # int32 reference points consumed so far
    lead: jnp.ndarray        # int32 pilot-round leader (-1 before pilot)
    d_sums: jnp.ndarray      # [arms] Σ (g_x - g_lead) post-pilot
    d_sq: jnp.ndarray        # [arms] Σ (g_x - g_lead)² post-pilot
    sigma_d: jnp.ndarray     # [arms] differenced sub-Gaussian scale
    n_post: jnp.ndarray      # int32 post-pilot samples
    n_evals: jnp.ndarray     # uint32 fresh distance evaluations
    n_cached: jnp.ndarray    # uint32 cache-served distance evaluations
    rounds: jnp.ndarray
    aux: Any                 # caller state (see adaptive_search ``aux_init``)


StatsFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray],
                   Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]
ExactFn = Callable[[], jnp.ndarray]
CountFn = Callable[[jnp.ndarray], jnp.ndarray]


def _default_count(active: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum(active.astype(jnp.uint32))


def adaptive_search(
    key: jax.Array,
    *,
    stats_fn: StatsFn,
    exact_fn: ExactFn,
    n_arms: int,
    n_ref: int,
    batch_size: int = 100,
    delta: Optional[float] = None,
    active_init: Optional[jnp.ndarray] = None,
    count_fn: Optional[CountFn] = None,
    sampling: str = "permutation",
    baseline: str = "none",
    stop_when_positive: bool = False,
    perm: Optional[jnp.ndarray] = None,
    perm_idx: Optional[jnp.ndarray] = None,
    perm_w: Optional[jnp.ndarray] = None,
    free_rounds=0,
    free_lo=0,
    init_sums: Optional[jnp.ndarray] = None,
    init_sqsums: Optional[jnp.ndarray] = None,
    init_rounds=0,
    aux_init: Any = None,
    n_ref_eff=None,
    log_term=None,
) -> SearchResult:
    """Run one best-arm identification (one BUILD assignment or one SWAP pick).

    Args:
      stats_fn: ``(ref_idx[B], w[B], lead, rnd) -> (sums, sqsums, cross)``
        — per-arm weighted batch sums of ``g``, ``g²`` and ``g·g_lead``
        over the sampled reference points (weights are the {0,1} padding
        mask; ``lead`` is an arm index, only meaningful when ≥ 0; ``rnd``
        is the round index, letting the caller serve cached distance
        columns for warm rounds).
      aux_init: optional caller state threaded through the search carry.
        When given, ``stats_fn`` takes a fifth argument (the current aux)
        and returns it, possibly updated, as a fourth output:
        ``(ref_idx, w, lead, rnd, aux) -> (sums, sqsums, cross, aux)``.
        This is how the device-resident PIC cache achieves write-through:
        the ``[n, width]`` column buffer plus its high-water round count
        ride the ``while_loop`` carry, and each fresh round's distance
        block is stored from inside the loop — the recompute that a
        host-side cache materialisation would pay is gone.  The final aux
        is returned as ``SearchResult.aux``.
      perm / free_rounds: paper App 2.2 cache — reuse a FIXED reference
        permutation across calls; rounds in ``[free_lo, free_rounds)``
        (Python ints or traced int32 scalars) hit the caller's distance
        cache and cost zero *new* evaluations (they are tallied in
        ``n_evals_cached`` instead).  ``free_lo > 0`` is the bounded-width
        PIC cache (``repro.core.pic_cache``): rounds below the resident
        window were recycled, so the caller recomputes them — they count
        as fresh again.
      perm_idx / perm_w: explicit pre-tiled permutation layout (position
        index and {0,1} validity weight per reference slot), overriding
        the cyclic tiling of ``perm``.  This is how the sharded driver
        runs permutation sampling over per-shard stratified permutations:
        round ``r`` occupies slots ``[r·B, (r+1)·B)`` with shard ``s``
        owning the ``[s·b_loc, (s+1)·b_loc)`` sub-slice.  The layout must
        cover every reference point exactly once among its weight-1 slots
        (``Σ perm_w == n_ref``) so the budget exhausts exactly.
      init_sums / init_sqsums / init_rounds: BanditPAM++ permutation-
        invariant caching (PIC).  Seed the search with per-arm Σg / Σg²
        already accumulated over the first ``init_rounds`` batches of the
        SAME fixed ``perm`` by a previous call (the caller must have
        re-validated them against the current g — see
        ``banditpam._carry_delta``).  The loop resumes at round
        ``init_rounds`` with ``n_used = min(init_rounds·B, n_ref)``; per-arm
        σ is re-derived from the carried moments (a strictly better estimate
        than the paper's first-batch Eq. 11, with the same union-bound
        validity since σ is treated as a known scale).  Requires
        ``sampling="permutation"`` and an explicit ``perm``.
      exact_fn: ``() -> mu[n_arms]`` exact objective; only used by the
        ``"replacement"`` fallback.
      count_fn: distance evaluations *per reference point* as a function of
        the survivor mask (BUILD: #active arms; SWAP: #distinct active
        non-medoids, since FastPAM1 shares distances across the k medoids).
      n_ref_eff: optional TRACED effective reference count ≤ ``n_ref``.
        ``n_ref`` keeps sizing every shape (perm tiling, arm arrays) while
        ``n_ref_eff`` drives every *value* use — the budget condition, the
        finite-population CI factor, and the exact-fallback accounting.
        This is what lets one compiled search serve a batch of padded
        fits with ragged per-fit n (``repro.core.banditpam.fit_batch``):
        shapes are padded to the batch maximum, the per-fit logical n
        rides in as data.  Defaults to ``n_ref`` (the historical static
        behavior, bit-identical).
      log_term: optional traced ``log(1/δ)`` override.  ``delta`` is a
        static trace constant; a batch of fits with ragged n has per-fit
        δ = 1/(1000·n_i), so the batched driver passes the log-term as
        data instead.  Mutually redundant with ``delta`` — when given,
        ``delta`` is ignored.
    """
    if sampling not in ("permutation", "replacement"):
        raise ValueError(f"unknown sampling mode {sampling!r}")
    if baseline not in ("none", "leader"):
        raise ValueError(f"unknown baseline mode {baseline!r}")
    if init_sums is not None and (
            sampling != "permutation" or (perm is None and perm_idx is None)):
        raise ValueError("carried statistics require permutation sampling "
                         "over an explicit fixed perm (PIC invariant)")
    if (perm_idx is None) != (perm_w is None):
        raise ValueError("perm_idx and perm_w must be given together")
    if delta is None:
        delta = 1.0 / (1000.0 * n_arms)
    if count_fn is None:
        count_fn = _default_count
    if log_term is None:
        log_term = jnp.float32(jnp.log(1.0 / delta))
    else:
        log_term = jnp.asarray(log_term, jnp.float32)
    n_eff = n_ref if n_ref_eff is None else n_ref_eff
    B = int(batch_size)
    use_perm = sampling == "permutation"
    use_lead = baseline == "leader"

    active0 = jnp.ones((n_arms,), jnp.bool_) if active_init is None else active_init

    n_rounds_max = -(-n_ref // B)
    if use_perm and perm_idx is None:
        if perm is None:
            key, pkey = jax.random.split(key)
            perm = jax.random.permutation(pkey, n_ref).astype(jnp.int32)
        total = n_rounds_max * B
        reps = -(-total // n_ref)
        perm_idx = jnp.tile(perm, reps)[:total]
        perm_w = (jnp.arange(total) < n_ref).astype(jnp.float32)

    def cond(s: _State) -> jnp.ndarray:
        go = jnp.logical_and(s.n_used < n_eff,
                             jnp.sum(s.active.astype(jnp.int32)) > 1)
        if stop_when_positive:
            # SWAP-convergence shortcut (beyond-paper, EXPERIMENTS §Perf):
            # the driver only *uses* the winner if its mean is negative
            # (a loss-improving swap).  Once every surviving arm's LCB is
            # positive, no arm can be an improving swap w.p. ≥ 1−δ, so
            # identifying the argmin among them is wasted sampling.
            n_used_f = jnp.maximum(s.n_used.astype(jnp.float32), 1.0)
            mu = s.sums / n_used_f
            ci = s.sigma * jnp.sqrt(log_term / n_used_f)
            lcb_min = jnp.min(jnp.where(s.active, mu - ci, jnp.inf))
            go = jnp.logical_and(go, lcb_min <= 0.0)
        return go

    def body(s: _State) -> _State:
        with jax.named_scope("search"):
            return _round(s)

    def _round(s: _State) -> _State:
        if use_perm:
            start = s.rounds * B
            ref_idx = jax.lax.dynamic_slice(perm_idx, (start,), (B,))
            w = jax.lax.dynamic_slice(perm_w, (start,), (B,))
            key = s.key
        else:
            key, sub = jax.random.split(s.key)
            ref_idx = jax.random.randint(sub, (B,), 0, n_ref)
            w = jnp.ones((B,), jnp.float32)
        b_eff = jnp.sum(w).astype(jnp.int32)
        b_eff_f = b_eff.astype(jnp.float32)
        if aux_init is None:
            sums_b, sq_b, cross_b = stats_fn(ref_idx, w,
                                             jnp.maximum(s.lead, 0), s.rounds)
            aux = s.aux
        else:
            sums_b, sq_b, cross_b, aux = stats_fn(
                ref_idx, w, jnp.maximum(s.lead, 0), s.rounds, s.aux)

        # ---- raw statistics (paper) ----
        sums = s.sums + sums_b
        sqsums = s.sqsums + sq_b
        n_new = s.n_used + b_eff
        n_new_f = n_new.astype(jnp.float32)
        mu_hat = sums / n_new_f
        batch_mean = sums_b / b_eff_f
        batch_var = jnp.maximum(sq_b / b_eff_f - batch_mean * batch_mean, 0.0)
        sigma = jnp.where(s.n_used == 0,                      # Eq. 11
                          jnp.sqrt(batch_var) + SIGMA_FLOOR, s.sigma)
        fpc = (jnp.sqrt(jnp.maximum(1.0 - n_new_f / n_eff, 0.0))
               if use_perm else jnp.float32(1.0))
        ci = sigma * jnp.sqrt(log_term / n_new_f) * fpc
        ucb = jnp.where(s.active, mu_hat + ci, jnp.inf)
        lcb = mu_hat - ci
        kill_raw = lcb > jnp.min(ucb)

        # ---- differenced statistics vs the pilot leader (beyond-paper) ----
        if use_lead:
            have_lead = s.lead >= 0
            d_b = sums_b - sums_b[jnp.maximum(s.lead, 0)]
            dsq_b = sq_b - 2.0 * cross_b + sq_b[jnp.maximum(s.lead, 0)]
            d_sums = s.d_sums + jnp.where(have_lead, d_b, 0.0)
            d_sq = s.d_sq + jnp.where(have_lead, dsq_b, 0.0)
            n_post = s.n_post + jnp.where(have_lead, b_eff, 0)
            n_post_f = jnp.maximum(n_post.astype(jnp.float32), 1.0)
            first_d = jnp.logical_and(have_lead, s.n_post == 0)
            dvar = jnp.maximum(dsq_b / b_eff_f - (d_b / b_eff_f) ** 2, 0.0)
            sigma_d = jnp.where(first_d, jnp.sqrt(dvar) + SIGMA_FLOOR, s.sigma_d)
            mu_d = d_sums / n_post_f
            ci_d = sigma_d * jnp.sqrt(log_term / n_post_f)
            ucb_d = jnp.where(s.active, mu_d + ci_d, jnp.inf)
            # Deterministic fp-tie break (see LEAD_TIE_REL): the margin
            # must clear a sliver of the arm's RAW confidence width, and
            # the leader is excluded from its own elimination test — its
            # differenced margin is structurally an exact-zero tie.
            eps_d = LEAD_TIE_REL * sigma * jnp.sqrt(log_term / n_post_f)
            kill_d = jnp.logical_and(
                n_post > 0, (mu_d - ci_d) > jnp.min(ucb_d) + eps_d)
            kill_d = jnp.logical_and(kill_d, jnp.arange(n_arms) != s.lead)
            kill = jnp.logical_or(kill_raw, kill_d)
            # pilot leader: fixed after the first round
            lead = jnp.where(s.lead >= 0, s.lead,
                             jnp.argmin(jnp.where(s.active, mu_hat, jnp.inf)
                                        ).astype(jnp.int32))
        else:
            kill = kill_raw
            lead = s.lead
            d_sums, d_sq, sigma_d, n_post = s.d_sums, s.d_sq, s.sigma_d, s.n_post

        active = jnp.logical_and(s.active, jnp.logical_not(kill))
        # Cache-served rounds are [free_lo, free_rounds); rounds below the
        # resident window (recycled slots) are fresh recomputations.
        fresh = jnp.logical_or(s.rounds >= free_rounds,
                               s.rounds < free_lo).astype(jnp.uint32)
        cost = count_fn(s.active) * b_eff.astype(jnp.uint32)
        n_evals = s.n_evals + fresh * cost
        n_cached = s.n_cached + (1 - fresh) * cost
        return _State(key, sums, sqsums, sigma, active, n_new, lead,
                      d_sums, d_sq, sigma_d, n_post, n_evals, n_cached,
                      s.rounds + 1, aux)

    zeros = jnp.zeros((n_arms,), jnp.float32)
    if init_sums is not None:
        # PIC seed: resume from the carried permutation prefix.  σ comes
        # from the carried moments (all arms share the same sample count).
        # The consumed count is Σ perm_w over the prefix — NOT rounds·B:
        # stratified sharded layouts scatter weight-0 padding into early
        # rounds, and an inflated n_used would both tighten the seeded
        # CIs beyond the δ guarantee and exhaust the budget before the
        # permutation is actually consumed.  (For the cyclic single-
        # device tiling this reduces to min(rounds·B, n_ref) exactly.)
        rounds0 = jnp.asarray(init_rounds, jnp.int32)
        n_used0 = jnp.sum(
            perm_w * (jnp.arange(perm_w.shape[0]) < rounds0 * B)
        ).astype(jnp.int32)
        n0_f = jnp.maximum(n_used0.astype(jnp.float32), 1.0)
        mu0 = init_sums / n0_f
        var0 = jnp.maximum(init_sqsums / n0_f - mu0 * mu0, 0.0)
        sums0, sqsums0 = init_sums, init_sqsums
        sigma0 = jnp.sqrt(var0) + SIGMA_FLOOR
    else:
        rounds0 = jnp.int32(0)
        n_used0 = jnp.int32(0)
        sums0, sqsums0 = zeros, zeros
        sigma0 = jnp.full((n_arms,), jnp.inf, jnp.float32)
    init = _State(
        key=key, sums=sums0, sqsums=sqsums0, sigma=sigma0,
        active=active0, n_used=n_used0, lead=jnp.int32(-1),
        d_sums=zeros, d_sq=zeros,
        sigma_d=jnp.full((n_arms,), jnp.inf, jnp.float32),
        n_post=jnp.int32(0), n_evals=jnp.uint32(0), n_cached=jnp.uint32(0),
        rounds=rounds0, aux=() if aux_init is None else aux_init,
    )
    final = jax.lax.while_loop(cond, body, init)

    n_survivors = jnp.sum(final.active.astype(jnp.int32))
    mu_final = final.sums / jnp.maximum(final.n_used.astype(jnp.float32), 1.0)

    def exact_branch(_):
        with jax.named_scope("exact"):
            mu_exact = exact_fn()
        mu_sel = jnp.where(final.active, mu_exact, jnp.inf)
        best = jnp.argmin(mu_sel).astype(jnp.int32)
        extra = count_fn(final.active) * jnp.asarray(n_eff).astype(jnp.uint32)
        return best, mu_sel[best], final.n_evals + extra, jnp.bool_(True)

    def sampled_branch(_):
        # In permutation mode a full budget means mu_hat is the exact mean,
        # so ties are resolved by lowest index — identical to PAM's argmin.
        mu_sel = jnp.where(final.active, mu_final, jnp.inf)
        best = jnp.argmin(mu_sel).astype(jnp.int32)
        return best, mu_sel[best], final.n_evals, jnp.bool_(False)

    if use_perm:
        best, mu_best, n_evals, used_exact = sampled_branch(None)
    else:
        best, mu_best, n_evals, used_exact = jax.lax.cond(
            n_survivors > 1, exact_branch, sampled_branch, operand=None)

    return SearchResult(best=best, mu_best=mu_best, n_evals=n_evals,
                        rounds=final.rounds, used_exact=used_exact,
                        n_survivors=n_survivors,
                        n_evals_cached=final.n_cached,
                        sums=final.sums, sqsums=final.sqsums,
                        aux=final.aux)

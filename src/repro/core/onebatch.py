"""OneBatchPAM (de Mathelin et al. 2025) — the latency-floor k-medoids.

Where BanditPAM adaptively *grows* each arm's reference sample until the
confidence intervals separate, OneBatchPAM commits to ONE fixed reference
batch up front and solves the induced finite-sample k-medoids problem
exactly: the objective is the mean dissimilarity to the ``b`` batch
points instead of all ``n``, so the whole fit touches a single ``[n, b]``
distance block — one kernel residency, no bandit loop, no per-round
host/device round-trips.  The returned medoids approximate the full-data
optimum with the usual subsample guarantees (the same grounds as CLARA's
PAM-on-subsamples, but with *candidates* still ranging over all n points,
which is why it dominates CLARA at equal budget).

The fit itself is one jit (:func:`_onebatch_solve`): a ``fori_loop``
BUILD (greedy k selections against the batch objective) followed by a
``while_loop`` of best-improvement SWAP iterations in the FastPAM1
decomposition — per candidate x, one row of the resident block scores
all k removals via ``Δ(m, x) = Σ_j base_x(j) + Σ_{j∈C_m} corr_x(j)``.

Role in this repo: the *fast-path refit* of the streaming
``repro.serve.MedoidService`` — when assignment drift demands new
medoids NOW, one fixed-batch solve (optionally warm-started from the
serving medoids via ``init=``) is the cheapest answer that still
searches the full candidate set.  Registered on the facade as
``solver="onebatchpam"``.

Ledger: ``n·b`` fresh evaluations for the batch block plus ``n·k`` for
the final exact loss/assignment — everything else is replays of the
resident block, which the paper's accounting (and ours) counts once.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .distances import EXACT
from .engine import get_stats_backend, resolve_stats_backend, total_loss
from .report import FitReport

__all__ = ["onebatchpam", "DEFAULT_REF_SIZE"]

# Default reference-batch size: comfortably past the B=100 bandit round
# batch (same estimation grounds) while keeping the [n, b] block one
# kernel residency at serving scale.
DEFAULT_REF_SIZE = 256


@functools.partial(jax.jit, static_argnames=("k", "max_swaps", "do_build"))
def _onebatch_solve(D, init_meds, *, k: int, max_swaps: int, do_build: bool):
    """BUILD + SWAP against the fixed-batch objective, ONE jit.

    ``D`` is the resident ``[n, b]`` candidate-to-batch block.  With
    ``do_build=False`` the BUILD loop is skipped and ``init_meds`` seeds
    SWAP directly (the warm-start entry the serving layer uses).

    Returns (medoids, iters, converged, old[T], new[T], loss_b[T],
    acc[T]) — the swap trajectory over the *batch* objective, which the
    host turns into ``FitReport.swap_history``.
    """
    n, b = D.shape
    T = max_swaps

    if do_build:
        # Greedy BUILD: each selection minimises the batch loss given the
        # already-chosen medoids (dnear = running min over batch columns).
        def build_body(i, c):
            meds, mask, dnear = c
            scores = jnp.sum(jnp.minimum(D, dnear[None, :]), axis=1)
            scores = jnp.where(mask, jnp.inf, scores)
            m = jnp.argmin(scores).astype(jnp.int32)
            return (meds.at[i].set(m), mask.at[m].set(True),
                    jnp.minimum(dnear, D[m]))

        meds, mask, _ = jax.lax.fori_loop(
            0, k, build_body, (jnp.zeros((k,), jnp.int32),
                               jnp.zeros((n,), jnp.bool_),
                               jnp.full((b,), jnp.inf, jnp.float32)))
    else:
        meds = init_meds
        mask = jnp.zeros((n,), jnp.bool_).at[meds].set(True)

    def cond(st):
        return jnp.logical_and(st[0] < T, jnp.logical_not(st[1]))

    def body(st):
        t, done, meds, mask, old_a, new_a, loss_a, acc_a = st
        Dm = D[meds]                                        # [k, b]
        a_b = jnp.argmin(Dm, axis=0).astype(jnp.int32)      # [b]
        d1 = jnp.min(Dm, axis=0)
        Dm2 = Dm.at[a_b, jnp.arange(b)].set(jnp.inf)
        d2 = jnp.min(Dm2, axis=0)
        loss_b = jnp.sum(d1)
        # FastPAM1 decomposition over the resident block: one [n, b] x
        # [b, k] matmul scores every (candidate, removed-medoid) pair.
        md = jnp.minimum(D, d1[None, :])
        base = md - d1[None, :]                             # [n, b]
        corr = jnp.minimum(D, d2[None, :]) - md
        onehot = jax.nn.one_hot(a_b, k, dtype=D.dtype)      # [b, k]
        delta = (jnp.sum(base, axis=1)[:, None]                  # [n, k]
                 + jnp.matmul(corr, onehot, precision=EXACT))
        delta = jnp.where(mask[:, None], jnp.inf, delta)
        best = jnp.argmin(delta.reshape(-1))
        x, m = best // k, best % k
        dval = delta.reshape(-1)[best]
        # The repo's one swap-accept rule (relative f32 margin).
        accept = dval < -1e-7 * jnp.maximum(1.0, jnp.abs(loss_b))
        old = meds[m]
        meds2 = jnp.where(accept, meds.at[m].set(x.astype(jnp.int32)), meds)
        mask2 = jnp.where(accept,
                          mask.at[old].set(False).at[x].set(True), mask)
        return (t + 1, jnp.logical_not(accept), meds2, mask2,
                old_a.at[t].set(old), new_a.at[t].set(x.astype(jnp.int32)),
                loss_a.at[t].set(loss_b + dval), acc_a.at[t].set(accept))

    st0 = (jnp.int32(0), jnp.bool_(False), meds, mask,
           jnp.zeros((T,), jnp.int32), jnp.zeros((T,), jnp.int32),
           jnp.zeros((T,), jnp.float32), jnp.zeros((T,), jnp.bool_))
    t, done, meds, _, old_a, new_a, loss_a, acc_a = jax.lax.while_loop(
        cond, body, st0)
    return meds, t, done, old_a, new_a, loss_a, acc_a


def onebatchpam(data, k: int, *, metric: str = "l2",
                ref_size: Optional[int] = None, seed: int = 0,
                max_swaps: Optional[int] = None, init=None,
                backend: str = "auto") -> FitReport:
    """Fit k medoids against ONE fixed reference batch.

    Args:
      data: ``[n, d]`` float32 (index-augmented for ``"precomputed"``).
      ref_size: reference-batch size ``b`` (clamped to n; default
        ``min(n, DEFAULT_REF_SIZE)``).
      init: optional ``[k]`` medoid indices — skips BUILD and warm-starts
        SWAP from them (the serving layer's incremental-refit entry).
      backend: stats-backend name for the one pairwise block
        (``repro.core.engine``; ``"auto"`` resolves like every solver).

    Returns a :class:`FitReport` whose ``loss`` is the EXACT full-data
    loss of the selected medoids (one final ``n·k`` pass), while the
    search itself only ever paid the ``n·b`` batch block.
    """
    data = jnp.asarray(data, jnp.float32)
    n = data.shape[0]
    if n <= k:
        raise ValueError("need n > k")
    b = min(n, int(ref_size) if ref_size is not None else DEFAULT_REF_SIZE)
    if b < 1:
        raise ValueError(f"ref_size must be >= 1, got {ref_size}")
    T = int(max_swaps) if max_swaps is not None else 4 * int(k) + 10
    bname = resolve_stats_backend(backend, metric)
    be = get_stats_backend(bname)

    key = jax.random.PRNGKey(seed)
    ref = jax.random.choice(key, n, shape=(b,), replace=False
                            ).astype(jnp.int32)
    D = be.pairwise(data, data[ref], metric=metric)         # [n, b]

    if init is not None:
        ws = np.asarray(init, np.int64).ravel()
        if ws.shape[0] != k or len(set(ws.tolist())) != k:
            raise ValueError(f"init must be {k} distinct medoid indices, "
                             f"got {ws.tolist()}")
        if ws.min() < 0 or ws.max() >= n:
            raise ValueError(f"init indices out of range [0, {n})")
        init_meds = jnp.asarray(ws, jnp.int32)
    else:
        init_meds = jnp.zeros((k,), jnp.int32)
    meds, iters, done, old_a, new_a, loss_a, acc_a = _onebatch_solve(
        D, init_meds, k=int(k), max_swaps=T, do_build=init is None)

    meds_np = np.asarray(meds, np.int64)
    loss = float(total_loss(data, meds, metric=metric))
    res = FitReport(medoids=meds_np, loss=loss, n_swaps=0,
                    converged=bool(done), distance_evals=0)
    res.evals_by_phase["ref_batch"] = n * b
    res.evals_by_phase["final_loss"] = n * k
    res.distance_evals = n * b + n * k
    old_np, new_np = np.asarray(old_a), np.asarray(new_a)
    la_np, acc_np = np.asarray(loss_a), np.asarray(acc_a)
    for t in range(int(iters)):
        if acc_np[t]:
            # the recorded loss is the BATCH objective after the swap
            res.swap_history.append((int(old_np[t]), int(new_np[t]),
                                     float(la_np[t])))
    res.n_swaps = len(res.swap_history)
    return res

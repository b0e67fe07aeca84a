"""The paper's comparison baselines (Fig. 1a): CLARANS, Voronoi Iteration,
CLARA.  These trade clustering quality for speed — the paper uses them to
show BanditPAM matches PAM's (better) loss.

Also FasterPAM (Schubert & Rousseeuw 2019/2021): the eager-swap exact
k-medoids reference.  Unlike PAM's best-swap-per-pass, it performs every
improving swap the moment it is found while sweeping the candidates, using
the same ``base + 1[y∈C_m]·corr`` decomposition as our fused SWAP step to
score all k removals of one candidate from a single distance row.  It
converges to a (possibly different) 1-swap local optimum of the same
neighbourhood structure as PAM, so it serves as the loss-parity check for
the BanditPAM++ reuse engine.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .banditpam import _swap_terms, medoid_cache, total_loss
from .distances import EXACT, get_metric
from .pam import pam
from .report import FitReport

# Alias of the unified report type (see repro.core.report).
BaselineResult = FitReport


# ---------------------------------------------------------------------------
# FasterPAM (Schubert & Rousseeuw 2019) — eager multi-medoid swaps
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "k"))
def _eager_swap_delta(data, x, d1, d2, assign, *, metric: str, k: int):
    """Loss change of swapping candidate x in for each of the k medoids.

    One distance row d(x, ·) scores all k removals via the FastPAM1
    decomposition (the same base/corr split as the fused SWAP kernel):

        Δ(m) = Σ_y base_x(y) + Σ_{y∈C_m} corr_x(y)

    Returns (best slot, its Δ).
    """
    dx = get_metric(metric)(data[x][None, :], data)             # [1, n]
    base, corr = _swap_terms(dx, d1, d2)
    delta = jnp.sum(base) + jax.ops.segment_sum(corr[0], assign,
                                                num_segments=k)
    m = jnp.argmin(delta).astype(jnp.int32)
    return m, delta[m]


def fasterpam(data, k: int, metric: str = "l2", max_steps: Optional[int] = None,
              seed: int = 0, init=None) -> BaselineResult:
    """Eager-swap exact k-medoids: perform each improving swap immediately
    while sweeping candidates; stop after a full improvement-free sweep.

    Converges to a 1-swap local optimum of the same swap neighbourhood as
    PAM (typically matching its loss to within a percent from random init),
    at ``n`` distance evaluations per candidate scored plus an ``n·k``
    cache rebuild per accepted swap — the loss-parity reference for
    ``BanditPAM(reuse="pic")``.

    ``init`` seeds the medoids (e.g. with a BUILD result); default is a
    uniform random draw.
    """
    data = jnp.asarray(data, jnp.float32)
    n = data.shape[0]
    if init is None:
        rng = np.random.default_rng(seed)
        medoids = jnp.asarray(rng.choice(n, size=k, replace=False).astype(np.int32))
    else:
        medoids = jnp.asarray(np.asarray(init, np.int32))
    d1, d2, assign = medoid_cache(data, medoids, metric=metric)
    evals = n * k
    loss = float(jnp.sum(d1))
    max_steps = max_steps if max_steps is not None else 50 * n
    med_set = set(np.asarray(medoids).tolist())
    since_improved, steps, x, n_swaps = 0, 0, 0, 0
    while since_improved < n and steps < max_steps:
        if x not in med_set:
            m_idx, dval = _eager_swap_delta(data, x, d1, d2, assign,
                                            metric=metric, k=k)
            evals += n
            if float(dval) < -1e-7 * max(1.0, abs(loss)):
                old = int(medoids[int(m_idx)])
                med_set.discard(old)
                med_set.add(x)
                medoids = medoids.at[int(m_idx)].set(x)
                d1, d2, assign = medoid_cache(data, medoids, metric=metric)
                evals += n * k
                loss = float(jnp.sum(d1))
                since_improved = 0
                n_swaps += 1
            else:
                since_improved += 1
        else:
            since_improved += 1
        x = (x + 1) % n
        steps += 1
    return BaselineResult(medoids=np.asarray(medoids), loss=loss,
                          distance_evals=evals, n_swaps=n_swaps,
                          converged=since_improved >= n,
                          evals_by_phase={"swap": evals})


# ---------------------------------------------------------------------------
# Voronoi Iteration (Park & Jun 2009) — k-means-style alternation
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric", "k"))
def _voronoi_update(data, medoids, *, metric: str, k: int):
    """Reassign points, then recompute each cluster's medoid exactly.

    An empty cluster (possible when two medoids coincide or tie for all
    points — argmin assigns everything to the lower index) keeps its
    previous medoid: its cost column is all-inf, and electing argmin's
    arbitrary index 0 there would silently produce duplicate medoids.
    """
    n = data.shape[0]
    dist = get_metric(metric)
    dmat = dist(data, data[medoids])                    # [n, k]
    assign = jnp.argmin(dmat, axis=1)

    # Cost of x as medoid of cluster c: sum over members of d(x, y).
    # One [n, n] pass, masked per cluster via one-hot matmul.
    d_all = dist(data, data)                            # [n, n]
    onehot = jax.nn.one_hot(assign, k, dtype=d_all.dtype)   # [n, k]
    cost = jnp.matmul(d_all, onehot,                    # [n, k] Σ_{y∈C_c} d(x,y)
                      precision=EXACT)
    member = onehot > 0
    cost = jnp.where(member, cost, jnp.inf)             # only members eligible
    nonempty = jnp.any(member, axis=0)                  # [k]
    new_medoids = jnp.where(nonempty,
                            jnp.argmin(cost, axis=0).astype(jnp.int32),
                            medoids.astype(jnp.int32))
    return new_medoids, assign


def voronoi_iteration(data, k: int, metric: str = "l2", max_iters: int = 50,
                      seed: int = 0) -> BaselineResult:
    data = jnp.asarray(data, jnp.float32)
    n = data.shape[0]
    rng = np.random.default_rng(seed)
    medoids = jnp.asarray(rng.choice(n, size=k, replace=False).astype(np.int32))
    evals = 0
    converged = False
    for _ in range(max_iters):
        new_medoids, _ = _voronoi_update(data, medoids, metric=metric, k=k)
        evals += n * n + n * k
        if bool(jnp.all(new_medoids == medoids)):
            converged = True
            break
        medoids = new_medoids
    loss = float(total_loss(data, medoids, metric=metric))
    return BaselineResult(medoids=np.asarray(medoids), loss=loss,
                          distance_evals=evals, converged=converged,
                          evals_by_phase={"alternate": evals})


# ---------------------------------------------------------------------------
# CLARANS (Ng & Han 2002) — randomized swap-graph search
# ---------------------------------------------------------------------------

def clarans(data, k: int, metric: str = "l2", num_local: int = 2,
            max_neighbors: Optional[int] = None, seed: int = 0) -> BaselineResult:
    data = jnp.asarray(data, jnp.float32)
    n = data.shape[0]
    if max_neighbors is None:
        max_neighbors = max(250, int(0.0125 * k * (n - k)))
    rng = np.random.default_rng(seed)
    best_loss, best_medoids = np.inf, None
    evals = 0
    for _ in range(num_local):
        medoids = rng.choice(n, size=k, replace=False).astype(np.int32)
        cur = jnp.asarray(medoids)
        cur_loss = float(total_loss(data, cur, metric=metric))
        evals += n * k
        # Host-side medoid set, maintained across accepted swaps; the
        # neighbour draw maps a uniform draw over the n-k non-medoids
        # through the sorted medoid list (order-statistic shift), so no
        # rejection loop is needed.  (Historically the draw rejected and
        # redrew whenever it hit a medoid — unbounded for small n-k —
        # and re-materialised the medoid array on every attempt.)
        cur_sorted = np.sort(np.asarray(cur))
        j = 0
        while j < max_neighbors:
            m_idx = int(rng.integers(k))
            x = int(rng.integers(n - k))
            for mval in cur_sorted:
                if x >= mval:
                    x += 1
            cand = cur.at[m_idx].set(x)
            cand_loss = float(total_loss(data, cand, metric=metric))
            evals += n * k
            if cand_loss < cur_loss:
                cur, cur_loss, j = cand, cand_loss, 0
                cur_sorted = np.sort(np.asarray(cur))
            else:
                j += 1
        if cur_loss < best_loss:
            best_loss, best_medoids = cur_loss, np.asarray(cur)
    return BaselineResult(medoids=best_medoids, loss=best_loss,
                          distance_evals=evals,
                          evals_by_phase={"search": evals})


# ---------------------------------------------------------------------------
# CLARA (Kaufman & Rousseeuw 1990) — PAM on subsamples
# ---------------------------------------------------------------------------

def clara(data, k: int, metric: str = "l2", n_samples: int = 5,
          sample_size: Optional[int] = None, seed: int = 0) -> BaselineResult:
    data_np = np.asarray(data, np.float32)
    n = data_np.shape[0]
    if sample_size is None:
        sample_size = min(n, 40 + 2 * k)
    rng = np.random.default_rng(seed)
    data_j = jnp.asarray(data_np)
    best_loss, best_medoids = np.inf, None
    evals = 0
    for _ in range(n_samples):
        sub_idx = rng.choice(n, size=sample_size, replace=False)
        sub_res = pam(data_np[sub_idx], k, metric=metric)
        evals += sub_res.distance_evals
        medoids_global = sub_idx[sub_res.medoids]
        loss = float(total_loss(data_j, jnp.asarray(medoids_global.astype(np.int32)),
                                metric=metric))
        evals += n * k
        if loss < best_loss:
            best_loss, best_medoids = loss, medoids_global
    return BaselineResult(medoids=np.asarray(best_medoids), loss=best_loss,
                          distance_evals=evals,
                          evals_by_phase={"subsample": evals})

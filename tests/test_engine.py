"""StatsBackend engine: pallas/jnp full-fit parity, the fused
device-resident driver (single-jit BUILD, fused SWAP steps), re-entrant
fits, and the backend plumbing through the KMedoids facade."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KMedoids
from repro.core import BanditPAM, datasets
from repro.core.adaptive import adaptive_search
from repro.core.banditpam import _build_fused
from repro.core.engine import (available_stats_backends,
                               resolve_stats_backend)


def _ledger(rep):
    return (rep.medoids.tolist(), rep.distance_evals, rep.cached_evals,
            dict(rep.evals_by_phase), rep.n_swaps)


# ---------------------------------------------------------------------------
# Backend parity: pallas and jnp must produce identical fits
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("metric,reuse", [("l2", "none"), ("l2", "pic"),
                                          ("l1", "none")])
def test_backend_parity_full_fit(metric, reuse):
    """Acceptance: backend="pallas" and backend="jnp" give identical
    medoids, loss, and fresh/cached ledger on tier-1 problem sizes."""
    data = datasets.mnist_like(300, seed=7)
    a = BanditPAM(3, metric=metric, seed=0, reuse=reuse,
                  backend="jnp").fit(data)
    b = BanditPAM(3, metric=metric, seed=0, reuse=reuse,
                  backend="pallas").fit(data)
    assert a.medoids.tolist() == b.medoids.tolist()
    assert b.loss == pytest.approx(a.loss, rel=1e-6)
    assert _ledger(a) == _ledger(b)


@pytest.mark.parametrize("reuse", ["none", "pic"])
def test_backend_parity_with_leader_baseline(reuse):
    """The differenced-CI elimination now carries a deterministic
    tie-break (adaptive.LEAD_TIE_REL margin + the leader excluded from
    its own test), so ~1e-6 kernel-vs-jnp distance deltas can no longer
    flip kills that used to sit at exact fp ties — leader-mode ledgers
    compare EXACTLY across stats backends, like baseline="none" always
    did."""
    data = datasets.mnist_like(300, seed=3)
    a = BanditPAM(3, metric="l2", seed=1, baseline="leader", reuse=reuse,
                  backend="jnp").fit(data)
    b = BanditPAM(3, metric="l2", seed=1, baseline="leader", reuse=reuse,
                  backend="pallas").fit(data)
    assert a.medoids.tolist() == b.medoids.tolist()
    assert b.loss == pytest.approx(a.loss, rel=1e-6)
    assert _ledger(a) == _ledger(b)   # incl. medoids + itemised phases


def test_backend_registry_and_resolution():
    assert {"jnp", "pallas"} <= set(available_stats_backends())
    assert resolve_stats_backend("jnp", "l2") == "jnp"
    assert resolve_stats_backend("pallas", "l2") == "pallas"
    # auto never picks interpret-mode pallas on CPU
    if jax.default_backend() == "cpu":
        assert resolve_stats_backend("auto", "l2") == "jnp"
    with pytest.raises(KeyError):
        resolve_stats_backend("bogus", "l2")
    with pytest.raises(ValueError):
        # no kernel for the precomputed lookup metric
        resolve_stats_backend("pallas", "precomputed")


@pytest.mark.parametrize("method", ["build_stats", "swap_stats",
                                    "stream_build_sums", "stream_swap_sums",
                                    "top2"])
def test_pallas_backend_takes_jnp_path_past_vmem_rule(method):
    """A width whose tiles the kernels' VMEM rule refuses (d=8192) runs
    the jnp statistics inside the pallas backend, with jnp's results."""
    from repro.core.engine import get_stats_backend
    from repro.kernels import ops
    n, d, k = 40, 8192, 3
    x = jnp.asarray(np.random.default_rng(0).normal(size=(n, d)),
                    jnp.float32)
    ref_idx = jnp.arange(0, n, 2)
    b = ref_idx.shape[0]
    assert not ops.gstats_fit(128, b, d, k)
    assert not ops.gstats_fit(128, 512, d, k)
    d1 = jnp.linspace(1.0, 2.0, n)
    assign = jnp.arange(n, dtype=jnp.int32) % k
    w = jnp.ones((b,), jnp.float32)
    args = {
        "build_stats": (x, ref_idx, d1[ref_idx], w, None),
        "swap_stats": (x, ref_idx, d1[ref_idx], 2 * d1[ref_idx],
                       assign[ref_idx], w, k, None),
        "stream_build_sums": (x, d1),
        "stream_swap_sums": (x, d1, 2 * d1, assign, k),
        "top2": (x, x[:k]),
    }[method]
    got = getattr(get_stats_backend("pallas"), method)(*args, metric="l2")
    want = getattr(get_stats_backend("jnp"), method)(*args, metric="l2")
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-6, atol=1e-3)


# ---------------------------------------------------------------------------
# Fused driver: single-jit BUILD, fused-vs-stepped equivalence
# ---------------------------------------------------------------------------

def test_build_is_single_jit_entry():
    """The whole BUILD phase is one dispatch of one traced computation:
    a second fit with the same configuration adds no new traces."""
    data = datasets.mnist_like(300, seed=5)
    est = BanditPAM(3, metric="l2", seed=0)
    est.fit(data)
    before = _build_fused._cache_size()
    est.fit(data)
    assert _build_fused._cache_size() == before


@pytest.mark.parametrize("reuse", ["none", "pic"])
def test_fused_matches_stepped(reuse):
    """The fused device-resident driver and the host-orchestrated stepped
    baseline are the same algorithm: identical medoids and ledger."""
    data = datasets.mnist_like(400, seed=3)
    a = BanditPAM(4, metric="l2", seed=1, reuse=reuse, fused=True).fit(data)
    b = BanditPAM(4, metric="l2", seed=1, reuse=reuse, fused=False).fit(data)
    assert _ledger(a) == _ledger(b)
    assert a.loss == pytest.approx(b.loss, rel=1e-6)


def test_wall_by_phase_reported():
    data = datasets.mnist_like(300, seed=0)
    b = BanditPAM(3, metric="l2", seed=0).fit(data)
    # reuse="none" ends SWAP on the certified stop's exact step, whose
    # wall is a part of SWAP's
    assert set(b.wall_by_phase) == {"build", "swap", "certify"}
    assert all(v > 0 for v in b.wall_by_phase.values())
    assert b.wall_by_phase["certify"] <= b.wall_by_phase["swap"]


# ---------------------------------------------------------------------------
# Re-entrancy: per-fit state lives on FitContext, not the instance
# ---------------------------------------------------------------------------

def test_fit_is_reentrant_same_instance():
    """Refitting the same estimator must match a fresh instance exactly —
    no per-fit state (PIC cache, permutation, warm block) may leak."""
    data = datasets.mnist_like(300, seed=13)
    est = BanditPAM(3, metric="l2", seed=0, reuse="pic")
    first = est.fit(data)
    second = est.fit(data)
    fresh = BanditPAM(3, metric="l2", seed=0, reuse="pic").fit(data)
    assert _ledger(first) == _ledger(second) == _ledger(fresh)
    for attr in ("_pic", "_perm", "_dwarm", "_free_rounds"):
        assert not hasattr(est, attr)


def test_fit_is_reentrant_across_shapes():
    """A second fit on a different n must size its own context (the old
    instance-resident cache would have crashed or served stale columns)."""
    est = BanditPAM(3, metric="l2", seed=0, reuse="pic")
    a = est.fit(datasets.mnist_like(300, seed=1))
    b = est.fit(datasets.mnist_like(450, seed=2))
    fresh_b = BanditPAM(3, metric="l2", seed=0,
                        reuse="pic").fit(datasets.mnist_like(450, seed=2))
    assert _ledger(b) == _ledger(fresh_b)
    assert a.medoids.max() < 300 and b.medoids.max() < 450


def test_no_precomputed_state_needed_before_fit():
    """Pre-fit instances are plain configuration (no crashing accessors)."""
    est = BanditPAM(3, metric="l2", seed=0, reuse="pic")
    assert est.reuse == "pic"
    assert not hasattr(est, "_cache_view")


# ---------------------------------------------------------------------------
# adaptive_search aux threading (the PIC write-through carry)
# ---------------------------------------------------------------------------

def test_adaptive_search_threads_aux():
    n = 64
    rng = np.random.default_rng(0)
    mu = jnp.asarray(rng.uniform(0.0, 1.0, size=n).astype(np.float32))

    def stats_fn(ref_idx, w, lead, rnd, aux):
        g = mu[:, None] * jnp.ones_like(w)[None, :] * w[None, :]
        return (jnp.sum(g, 1), jnp.sum(g * g, 1), g @ g[lead],
                aux + jnp.int32(1))

    sr = adaptive_search(jax.random.PRNGKey(0), stats_fn=stats_fn,
                         exact_fn=lambda: mu, n_arms=n, n_ref=n,
                         batch_size=16, aux_init=jnp.int32(0))
    assert int(sr.aux) == int(sr.rounds)
    assert int(sr.best) == int(jnp.argmin(mu))


# ---------------------------------------------------------------------------
# Facade plumbing
# ---------------------------------------------------------------------------

def test_kmedoids_backend_parity():
    data = datasets.mnist_like(300, seed=7)
    a = KMedoids(3, solver="banditpam", metric="l2", seed=0,
                 backend="jnp").fit(data)
    b = KMedoids(3, solver="banditpam", metric="l2", seed=0,
                 backend="pallas").fit(data)
    assert a.medoids_.tolist() == b.medoids_.tolist()
    assert a.report_.ledger() == b.report_.ledger()
    assert np.array_equal(a.labels_, b.labels_)


def test_kmedoids_backend_rejected_for_non_bandit_solver():
    data = datasets.mnist_like(60, seed=0)
    with pytest.raises(ValueError):
        KMedoids(3, solver="pam", metric="l2", backend="pallas").fit(data)
    # the default "auto" stays valid for every solver
    KMedoids(3, solver="pam", metric="l2").fit(data)

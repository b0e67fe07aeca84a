"""The certified stop of ``banditpam`` (``reuse="none"``, with or without
the ``cache_cols`` warm block): SWAP ends only when an exact SWAP step
finds no improving swap, as PAM's own loop ends.

On the scRNA twin (zero-inflated l1 counts) the bandit can eliminate an
improving arm whose g-returns are heavy-tailed, so a rejected bandit pick
alone stops short of PAM's swap optimum.  The reference here is a float64
numpy best-swap check that forms explicit l1 differences and imports
nothing of the program.

Also pinned: ``fit_batch`` lanes run the same exact step as the single
fit (medoids, loss and ledger to the bit), the step's host span, device
scope and counters, and that ``reuse="pic"`` fits are untouched by it.
"""

import glob
import json
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.api import KMedoids
from repro.core import BanditPAM, datasets
from repro.core import banditpam as bp

N, K = 1000, 5


def _l1_matrix(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.float64)
    out = np.empty((x.shape[0], x.shape[0]))
    for i in range(0, x.shape[0], 50):
        out[i:i + 50] = np.abs(x[i:i + 50, None, :] - x[None, :, :]).sum(-1)
    return out


def _best_swap(dist: np.ndarray, medoids) -> tuple:
    """(largest loss decrease of one swap, loss) by PAM's SWAP step over
    the float64 distance matrix."""
    medoids = np.asarray(medoids)
    to_med = dist[:, medoids]
    order = np.sort(to_med, axis=1)
    near, second = order[:, 0], order[:, 1]
    owner = to_med.argmin(axis=1)
    loss = float(near.sum())
    gain = 0.0
    for j in range(len(medoids)):
        mine = (owner == j)[:, None]
        after = np.where(mine, np.minimum(dist, second[:, None]),
                         np.minimum(dist, near[:, None])).sum(axis=0)
        after[medoids] = np.inf
        gain = max(gain, loss - float(after.min()))
    return gain, loss


@pytest.fixture(scope="module")
def scrna():
    x = datasets.scrna_like(N, seed=0, d=1000)
    return x, _l1_matrix(x)


# cache_cols=200 takes the paper's warm block (App 2.2, mode "warm"):
# without the certified stop its seeds 0 and 1 stop short too.
@pytest.mark.parametrize("seed,cache_cols", [
    *(pytest.param(s, 0, id=str(s)) for s in range(8)),
    *(pytest.param(s, 200, id=f"warm{s}") for s in (0, 1))])
def test_banditpam_returns_a_swap_optimum_on_scrna(scrna, seed, cache_cols):
    x, dist = scrna
    est = KMedoids(k=K, solver="banditpam", metric="l1", seed=seed,
                   cache_cols=cache_cols).fit(x)
    gain, loss = _best_swap(dist, est.medoids_)
    assert gain <= 1e-5 * loss, (seed, gain / loss, est.medoids_)
    assert abs(est.loss_ - loss) <= 1e-6 * loss
    rep = est.report_
    assert rep.converged
    assert rep.evals_by_phase["certify"] == rep.certify_evals > 0
    assert rep.dispatches_by_phase["certify"] == rep.certify_swaps + 1


def _lanes(ragged: bool):
    big = datasets.scrna_like(400, seed=0, d=200)
    if ragged:
        return [big, datasets.scrna_like(200, seed=0, d=200)], [2, 3]
    return [big, big, big], [2, 5, 0]


@pytest.mark.parametrize("ragged", [False, True])
def test_fit_batch_lanes_certify_as_the_single_fit(ragged):
    xs, seeds = _lanes(ragged)
    batch = BanditPAM(K, metric="l1", backend="jnp").fit_batch(xs,
                                                               seeds=seeds)
    singles = [BanditPAM(K, metric="l1", seed=s, backend="jnp").fit(x)
               for x, s in zip(xs, seeds)]
    assert sum(r.certify_swaps for r in singles) >= 2
    for got, want in zip(batch, singles):
        assert np.array_equal(got.medoids, want.medoids)
        if ragged:
            # the masked loss sum over a padded lane may split the f32
            # reduction differently (tests/test_multifit.py)
            np.testing.assert_allclose(got.loss, want.loss, rtol=1e-5)
        else:
            assert float(got.loss) == float(want.loss)
        assert got.evals_by_phase == want.evals_by_phase
        assert got.distance_evals == want.distance_evals
        assert (got.certify_evals, got.certify_swaps) == (
            want.certify_evals, want.certify_swaps)
        assert [h[:2] for h in got.swap_history] == [
            h[:2] for h in want.swap_history]
        assert got.converged == want.converged


def test_pic_fits_are_unchanged():
    """``reuse="pic"`` keeps the bandit's own stop: its golden ledger is
    the one recorded before the certified stop existed, and its fits run
    no exact step."""
    from test_multifit import FIXTURE, GOLDEN_CONFIGS, _golden_record

    want = json.loads(FIXTURE.read_text())["l1_pic"]
    assert _golden_record(GOLDEN_CONFIGS["l1_pic"]) == want
    rep = BanditPAM(K, metric="l1", seed=1, reuse="pic").fit(
        datasets.scrna_like(300, seed=0, d=200))
    assert "certify" not in rep.evals_by_phase
    assert "certify" not in rep.wall_by_phase
    assert "certify" not in rep.dispatches_by_phase
    assert (rep.certify_evals, rep.certify_swaps) == (0, 0)


def test_certify_span_scope_and_counters(tmp_path):
    x = datasets.scrna_like(400, seed=0, d=200)
    est = BanditPAM(K, metric="l1", seed=2, backend="jnp")
    est.fit(x)                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep = est.fit(x)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(ev.start_ns, ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "repro.swap.certify"]
    assert len(spans) == rep.dispatches_by_phase["certify"] == (
        rep.certify_swaps + 1) >= 2
    span_s = sum(d for _, d in spans) / 1e9
    assert span_s <= rep.wall_by_phase["certify"] + 1e-4
    assert rep.wall_by_phase["certify"] - span_s < 5e-3
    assert rep.wall_by_phase["certify"] <= rep.wall_by_phase["swap"]
    n = x.shape[0]
    assert rep.certify_evals == (rep.certify_swaps + 1) * (n * n + n * K)

    low = bp._swap_certify_jit.lower(
        x, np.arange(K, dtype=np.int32), np.arange(n) < K,
        np.zeros(n, np.float32), np.zeros(n, np.float32),
        np.zeros(n, np.int32), np.float32(1.0), backend="jnp", metric="l1",
        batch_size=100, k=K)
    names = re.findall(r'loc\("([^"]*)"', low.as_text(debug_info=True))
    assert any("certify" in nm.split("/") for nm in names)

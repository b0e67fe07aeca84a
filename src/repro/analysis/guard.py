"""tracecheck runtime guard — transfer-guard + dispatch-count harness.

The static rules police what the *source* may do; this module polices
what a *running fit* actually does:

* :func:`guarded` / :class:`FitGuard` run a fused ``BanditPAM.fit``
  under ``jax.transfer_guard("disallow")``, so any device↔host transfer
  outside the sanctioned points (``engine.host_read`` explicit reads and
  ``engine.host_stage`` staging spans) raises at the offending call.
* The dispatch ledger check promotes the benchmark assertion
  ``dispatches_by_phase == {"build": 1, "swap": iters}`` (one jit
  dispatch per phase iteration, counted by ``engine.counted_dispatch``)
  to a first-class test fixture.
* :func:`jit_cache_sizes` snapshots the module-level jitted drivers'
  trace-cache sizes so tests can assert a second fit retraces nothing.

Import note: this module imports jax and the core driver; the static
half of :mod:`repro.analysis` stays stdlib-only.  The pytest fixtures
at the bottom are defined only when pytest is importable, so shipping
code may import the harness without a test dependency.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax

from repro.core.engine import host_read, host_stage  # noqa: F401  (re-export)

__all__ = ["FitGuard", "expected_dispatches", "guarded",
           "jit_cache_sizes", "host_read", "host_stage"]


@contextlib.contextmanager
def guarded():
    """``jax.transfer_guard("disallow")`` as a reusable context: implicit
    transfers raise, explicit ``host_read``/``host_stage`` remain legal."""
    with jax.transfer_guard("disallow"):
        yield


def expected_dispatches(report, *, warm: bool = False) -> Dict[str, int]:
    """The one-dispatch-per-phase contract for a fused fit's report.

    BUILD is a single fused dispatch (absent on warm starts); SWAP costs
    one dispatch per iteration — ``n_swaps`` accepted moves plus the
    final rejecting iteration when the fit converged rather than hitting
    ``max_swaps``.  Under the certified stop (``reuse="none"``, whose
    ledger has a ``certify`` phase) every rejected pick costs one exact
    step more: each accepts a swap or ends the fit.
    """
    iters = report.n_swaps + (1 if report.converged else 0)
    exp = {"swap": iters}
    if not warm:
        exp["build"] = 1
    certified = report.certify_swaps + (1 if report.converged else 0)
    if "certify" in report.evals_by_phase and certified:
        exp["certify"] = certified
    return exp


def jit_cache_sizes() -> Dict[str, int]:
    """Trace-cache sizes of the module-level jitted fused drivers."""
    from repro.core import banditpam as bp
    return {
        "_build_fused": bp._build_fused._cache_size(),
        "_swap_iter": bp._swap_iter_jit._cache_size(),
        "_swap_certify": bp._swap_certify_jit._cache_size(),
        "_build_batch": bp._build_batch._cache_size(),
        "_swap_batch": bp._swap_batch._cache_size(),
    }


class FitGuard:
    """Runs fits under the transfer guard and checks the dispatch ledger.

    ``fit()`` warms the jit caches with one unguarded fit (compilation
    legitimately stages constants host→device), then repeats the fit
    inside ``transfer_guard("disallow")`` and asserts

    * the guarded report's medoids/loss/ledger match the warm-up run
      bit-for-bit (the guard must not change the computation), and
    * ``dispatches_by_phase`` equals :func:`expected_dispatches`.
    """

    def __init__(self) -> None:
        self.last_report = None

    def fit(self, est, data, *, warm_start=None, warmup: bool = True,
            check_dispatches: bool = True,
            check_retrace: bool = True) -> "object":
        if not getattr(est, "fused", True):
            raise ValueError(
                "FitGuard covers the fused driver; the stepped baseline "
                "syncs per sub-step by design and is exempt")
        baseline = None
        if warmup:
            baseline = est.fit(data, warm_start=warm_start)
        before = jit_cache_sizes() if (warmup and check_retrace) else None
        with guarded():
            report = est.fit(data, warm_start=warm_start)
        if before is not None:
            after = jit_cache_sizes()
            assert after == before, (
                f"guarded fit retraced a fused driver: {before} -> {after}")
        if baseline is not None:
            assert report.medoids.tolist() == baseline.medoids.tolist(), (
                "transfer guard changed the fit result (medoids)")
            assert report.loss == baseline.loss, (
                "transfer guard changed the fit result (loss)")
            assert report.evals_by_phase == baseline.evals_by_phase, (
                "transfer guard changed the eval ledger")
        if check_dispatches:
            exp = expected_dispatches(report, warm=warm_start is not None)
            assert report.dispatches_by_phase == exp, (
                f"dispatch ledger {report.dispatches_by_phase} != "
                f"one-dispatch-per-phase contract {exp}")
        self.last_report = report
        return report

    def fit_batch(self, est, datasets, *, seeds=None, warmup: bool = True,
                  check_dispatches: bool = True,
                  check_retrace: bool = True) -> "object":
        """The batched twin of :meth:`fit` for ``est.fit_batch``.

        Same discipline: one unguarded warm-up batch (compilation stages
        constants), then the identical batch under
        ``transfer_guard("disallow")``, asserting

        * zero retraces of the module-level batched drivers,
        * every per-fit report bit-matches the warm-up run (medoids,
          loss, eval ledger), and
        * the batch-level dispatch ledger is exactly
          ``{"build": 1, "swap": 1}`` — one jit per phase regardless of
          B, the whole point of the batched engine.
        """
        baseline = None
        if warmup:
            baseline = est.fit_batch(datasets, seeds)
        before = jit_cache_sizes() if (warmup and check_retrace) else None
        with guarded():
            batch = est.fit_batch(datasets, seeds)
        if before is not None:
            after = jit_cache_sizes()
            assert after == before, (
                f"guarded fit_batch retraced a fused driver: "
                f"{before} -> {after}")
        if baseline is not None:
            for i, (rep, base) in enumerate(zip(batch, baseline)):
                assert rep.medoids.tolist() == base.medoids.tolist(), (
                    f"transfer guard changed fit {i} (medoids)")
                assert rep.loss == base.loss, (
                    f"transfer guard changed fit {i} (loss)")
                assert rep.evals_by_phase == base.evals_by_phase, (
                    f"transfer guard changed fit {i}'s eval ledger")
        if check_dispatches:
            exp = {"build": 1, "swap": 1}
            assert batch.dispatches_by_phase == exp, (
                f"batch dispatch ledger {batch.dispatches_by_phase} != "
                f"one-jit-per-phase contract {exp}")
        self.last_report = batch
        return batch


try:  # pragma: no cover - exercised via pytest, absent in production
    import pytest
except ImportError:  # pragma: no cover
    pytest = None

if pytest is not None:
    @pytest.fixture
    def fit_guard() -> FitGuard:
        """Transfer-guard + dispatch-ledger harness for fused fits."""
        return FitGuard()

    @pytest.fixture
    def trace_guard():
        """Bare ``jax.transfer_guard("disallow")`` context factory."""
        return guarded

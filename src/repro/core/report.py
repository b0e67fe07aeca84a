"""The one fit report every k-medoids solver in this repo emits.

Historically each entrypoint had its own result type (``FitResult`` for
``BanditPAM``, ``PAMResult`` for ``pam``, ``BaselineResult`` for the
baselines) with divergent fields, which made cross-solver comparisons —
the paper's whole point — need per-type glue.  ``FitReport`` collapses
them: the old names remain importable as aliases of this class, so every
solver now returns the same dataclass and the ``repro.api.KMedoids``
facade can treat them interchangeably.

Ledger semantics (the paper's headline metric):

* ``distance_evals`` — FRESH pairwise dissimilarity evaluations the
  algorithm paid for, exactly as the paper counts them.
* ``cached_evals`` — evaluations served from a distance cache (the
  BanditPAM++ PIC engine); zero for cache-less solvers.
* ``evals_by_phase`` — the itemised split.  Keys ending in ``_cached``
  count cache-served work and are excluded from ``distance_evals``;
  everything else is fresh.  Typical keys: ``build``, ``swap``,
  ``cache_warm``, ``build_cached``, ``swap_cached``.
* ``refresh_evals`` — the part of ``distance_evals`` that recomputed PIC
  columns the bounded ring had computed before and evicted (what a ring
  holding every round would have served).  Not a phase key, so it moves
  neither ``distance_evals`` nor the ledger.
* ``certify_evals`` — the fresh evaluations of the certified stop's exact
  SWAP steps (``reuse="none"``: a bandit miss is checked by one exact
  step before SWAP ends); also the phase key ``certify``, so a part of
  ``distance_evals``.  ``certify_swaps`` counts the swaps such a step
  accepted after a bandit miss.

The benchmark reads ``distance_evals`` (``bench/metrics/fresh_evals.fit.py``),
``refresh_evals`` (``bench/metrics/refresh_evals.fit.py``),
``certify_evals`` (``bench/metrics/certify_evals.fit.py``) and
``wall_by_phase`` (``bench/metrics/build_s.fit.py``, ``swap_s.fit.py``,
``certify_s.fit.py``);
the serving layer's refit ledger reads :meth:`FitReport.ledger`.

``labels`` (the in-sample cluster assignment) is filled by the facade
after the solve; solvers themselves only need medoids + loss + ledger.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np


@dataclass
class FitReport:
    medoids: np.ndarray
    loss: float
    n_swaps: int = 0
    # False by default: only solvers with an actual convergence criterion
    # (banditpam*, pam/fastpam1, fasterpam, voronoi) set it — budget-
    # exhausting solvers (clara, clarans) honestly report False.
    converged: bool = False
    distance_evals: int = 0
    evals_by_phase: Dict[str, int] = field(default_factory=dict)
    swap_history: List[Tuple[int, int, float]] = field(default_factory=list)
    build_rounds: List[int] = field(default_factory=list)
    swap_exact_fallbacks: int = 0
    cached_evals: int = 0   # evaluations served from a distance cache
    refresh_evals: int = 0  # fresh evaluations of evicted PIC rounds
    certify_evals: int = 0  # fresh evaluations of certified-stop steps
    certify_swaps: int = 0  # swaps a certified-stop step accepted
    labels: Optional[np.ndarray] = None
    solver: str = ""
    metric: str = ""
    # Wall-clock seconds per phase (e.g. "build" / "swap"), filled by
    # solvers that time their phases (BanditPAM, through the host spans
    # ``repro.build`` / ``repro.swap`` of ``engine.phase``; "certify" is
    # the sum of the ``repro.swap.certify`` spans, a part of "swap").
    # Unlike the ledger this is environment-dependent.
    wall_by_phase: Dict[str, float] = field(default_factory=dict)
    # Driver-level compiled phase-step calls, MEASURED at the call site
    # (``engine.counted_dispatch``, not a self-reported constant): the
    # fused BUILD registers 1 for the whole phase, the stepped baseline
    # one per selection; SWAP registers one step per iteration (the
    # stepped baseline's step internally bundles a few sub-dispatches).
    # benchmarks/distributed_bench.py asserts the sharded BUILD stays 1.
    dispatches_by_phase: Dict[str, int] = field(default_factory=dict)

    def ledger(self) -> Dict[str, object]:
        """The unified fresh/cached distance-evaluation ledger as one dict
        (what ``MedoidService``'s refit ledger records per fit)."""
        return {
            "fresh": int(self.distance_evals),
            "cached": int(self.cached_evals),
            "by_phase": {k: int(v) for k, v in self.evals_by_phase.items()},
        }


@dataclass
class BatchFitReport:
    """The result of one batched multi-fit (``BanditPAM.fit_batch`` /
    ``KMedoids.fit_batch``): B independent fits solved in one dispatch
    per phase.

    ``reports`` holds one full per-fit :class:`FitReport` each — medoids,
    loss, and the fresh/cached ledger, bit-identical to what the
    single-fit path would have produced for the same per-fit seed (the
    invariant ``tests/test_multifit.py`` pins).  The batch-level fields
    are what is NOT per-fit:

    * ``dispatches_by_phase`` — measured at the driver call site
      (``engine.counted_dispatch``), for the WHOLE batch: the batched
      engine compiles to one jit per phase, so this reads
      ``{"build": 1, "swap": 1}`` regardless of B (the per-fit reports
      leave theirs empty — a lane inside a batched dispatch has no
      dispatch count of its own).
    * ``wall_by_phase`` — batch wall-clock per phase; divide by
      ``len(batch)`` for the amortised per-fit cost
      (``benchmarks/multifit_bench.py``).
    * ``medoids``/``loss`` — the stacked ``[B, k]`` / ``[B]`` views.
    * ``labels`` — stacked ``[B, n_max]`` in-sample assignments (filled
      by the facade; pad rows carry arbitrary labels — mask with
      ``n_valid``).
    * ``n_valid`` — the logical per-fit n of the (possibly ragged,
      padded) inputs.

    The container is sequence-like: ``len(batch)``, ``batch[i]``, and
    iteration yield the per-fit reports.
    """

    reports: List[FitReport]
    medoids: np.ndarray                     # [B, k]
    loss: np.ndarray                        # [B]
    n_valid: Optional[np.ndarray] = None    # [B] logical n per fit
    labels: Optional[np.ndarray] = None     # [B, n_max]
    solver: str = ""
    metric: str = ""
    wall_by_phase: Dict[str, float] = field(default_factory=dict)
    dispatches_by_phase: Dict[str, int] = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.reports)

    def __getitem__(self, i: int) -> FitReport:
        return self.reports[i]

    def __iter__(self) -> Iterator[FitReport]:
        return iter(self.reports)

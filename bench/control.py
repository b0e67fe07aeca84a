"""The control of the correctness check, and the faults it must catch.

    python bench/control.py --workload mnist-l2.fit --seconds 10 --seeds 1 2 3
    python bench/control.py --workload mnist-l2.fit --seconds 10 --seeds 1 2 3 --program
    python bench/control.py --workload mnist-l2.fit --seconds 1 --seeds 1 2 3 --program --solver-seed-per-run

Runs the cell as ``run.py`` does, several seeds in one process, with the
program's answer replaced: by the control (the plain reference one
precision step down, ``reference.control_distances``) or, with
``--fault``, by one planted fault.  ``--program`` replaces nothing: the
program's own readings, which set the lower end of each limit.  A cell whose
traffic fixes its solver seeds does the same work on every seed;
``--solver-seed-per-run`` fits instead one solver seed drawn from each
run's seed, so that the readings span many answers.  Prints
one JSON line per seed with the numbers compared.  The benchmark's own
runs never run this; ``tests/test_control.py`` runs it at a test size.

Faults, each planted where the answer is produced:

* ``swap_unchanged`` — the SWAP phase returns its state unchanged (the
  BUILD medoids, with their loss).
* ``half_batch`` — a fit's loss is the mean over the first half of the
  points, times n.
* ``altered`` — one label of each answer is changed.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from bench import datagen, harness, reference  # noqa: E402

FAULTS = ("swap_unchanged", "half_batch", "altered")


@contextlib.contextmanager
def _patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _fit_answer(replace):
    """Patch ``KMedoids.fit`` so that ``replace(est, X)`` rewrites the
    answer of every fit."""
    from repro.api.estimator import KMedoids

    def make(orig):
        def fit(self, X):
            orig(self, X)
            replace(self, np.asarray(X, np.float32))
            return self
        return fit
    return _patched(KMedoids, "fit", make)


def _control_fit(est, X):
    dist = reference.control_distances(X, X[est.medoids_],
                                       est._metric_name)
    est.labels_ = dist.argmin(axis=1)
    est.loss_ = reference.loss_of(dist)


def _half_fit(est, X):
    from repro.core.banditpam import medoid_cache
    import jax.numpy as jnp

    d1 = np.asarray(medoid_cache(jnp.asarray(X),
                                 jnp.asarray(est.medoids_, jnp.int32),
                                 metric=est._metric_name)[0], np.float64)
    half = X.shape[0] // 2
    est.loss_ = float(d1[:half].mean() * X.shape[0])


def _altered_fit(est, X):
    est.labels_ = np.array(est.labels_)
    est.labels_[0] = (est.labels_[0] + 1) % est.k


@contextlib.contextmanager
def _swap_unchanged():
    from repro.core import banditpam

    def make(orig):
        def swap(self, data, medoids, med_mask, key, ctx, res):
            loss = float(banditpam.total_loss(data, medoids,
                                              metric=self.metric))
            return medoids, loss, True
        return swap
    with _patched(banditpam.BanditPAM, "_swap", make):
        yield


def planted(what: str):
    """The context that replaces the program's answer by ``what``:
    ``control``, ``program`` (nothing) or a fault of :data:`FAULTS`."""
    if what == "program":
        return contextlib.nullcontext()
    if what == "swap_unchanged":
        return _swap_unchanged()
    fit = {"control": _control_fit, "half_batch": _half_fit,
           "altered": _altered_fit}
    return _fit_answer(fit[what])


def read(cell: harness.Cell, seed: int, seconds: float, what: str,
         solver_seed_per_run: bool = False) -> dict:
    """One run of ``cell`` with ``what`` planted; the result dict."""
    if solver_seed_per_run:
        cell.traffic = dict(cell.traffic,
                            solver_seeds=[datagen.fit_seed(seed)])
    with planted(what):
        return harness.run_cell(cell, seed, seconds, False,
                                time.perf_counter())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    group = ap.add_mutually_exclusive_group()
    group.add_argument("--program", action="store_true")
    group.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--solver-seed-per-run", action="store_true")
    args = ap.parse_args(argv)
    what = "program" if args.program else args.fault or "control"
    cell = harness.Cell(args.workload)
    why = harness.devices_ok(cell.chips)
    if why:
        print(why, file=sys.stderr)
        return 2
    for seed in args.seeds:
        res = read(cell, seed, args.seconds, what,
                   args.solver_seed_per_run)
        print(json.dumps({"workload": args.workload, "what": what,
                          "seed": seed, "solver_seeds":
                          cell.traffic.get("solver_seeds"),
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The fit job: one ``KMedoids.fit`` on a deployment's data, repeated.

A round is one fit per solver seed of the traffic.  Set-up makes the
data, builds one estimator per seed and runs one whole round, so every
program the window runs is compiled or loaded from the cache.  The
window then runs rounds back to back from its start and closes at the
end of the first round that ends after ``--seconds``; ``fit_s`` is the
window over the fits in it.  A fit returns host arrays (medoids, labels,
loss), so the device work of each has finished when it returns.

Traffic key (``workloads/<cell>.json``): ``solver_seeds``, the round.
The run's ``--seed`` only orders it, so every run does the same work:
the solver seed changes the work of a fit (the number of swaps, exact
fallbacks), and the traffic fixes it.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import datagen, reference


class Job:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.traffic = traffic
        self.metric = config["metric"]
        self.solver = config["solver"]
        self.k = int(config["k"])
        self.n = int(config["n"])
        self.seed = seed
        self.reports: List[object] = []
        self.answers: Dict[bytes, tuple] = {}
        self.attempted = 0

    def setup(self) -> None:
        from repro.api import KMedoids

        self.x = datagen.dataset(self.config, self.n)
        seeds = self.traffic["solver_seeds"]
        order = datagen.run_rng(self.seed, "order").permutation(len(seeds))
        seeds = [seeds[i] for i in order]
        self.round = [KMedoids(k=self.k, solver=self.solver,
                               metric=self.metric, seed=int(s))
                      for s in seeds]
        for est in self.round:
            est.fit(self.x)

    def _fit(self, est) -> None:
        self.attempted += 1
        est.fit(self.x)
        medoids = np.asarray(est.medoids_, np.int64)
        labels = np.asarray(est.labels_, np.int64)
        key = (medoids.tobytes() + labels.tobytes()
               + np.float64(est.loss_).tobytes())
        self.answers.setdefault(key, (medoids, labels, float(est.loss_)))
        self.reports.append(est.report_)

    def window(self, seconds: float) -> Dict[str, float]:
        t0 = time.perf_counter()
        fits = 0
        while True:
            for est in self.round:
                self._fit(est)
                fits += 1
            if time.perf_counter() - t0 >= seconds:
                break
        return {"fit_s": (time.perf_counter() - t0) / fits}

    def traced(self) -> None:
        """The round's first fit, warm: the traced run's segment."""
        self._fit(self.round[0])
        self.reports.pop()

    def release(self) -> None:
        self.round = None

    def check(self) -> Dict[str, float]:
        """The compared numbers, each the worst over the distinct answers
        the timed fits returned."""
        worst = {"loss_gap": 0.0, "label_gap": 0.0, "swap_gain": 0.0}
        if not self.answers:
            return {k: float("inf") for k in worst}
        for medoids, labels, loss in self.answers.values():
            for name, v in self.numbers(medoids, labels, loss).items():
                worst[name] = max(worst[name], v)
        return worst

    def numbers(self, medoids, labels, loss) -> Dict[str, float]:
        if (medoids.min() < 0 or medoids.max() >= self.n
                or medoids.shape[0] != self.k):
            return {"loss_gap": float("inf"), "label_gap": float("inf"),
                    "swap_gain": float("inf")}
        ref = reference.dense_distances(self.x, self.x[medoids], self.metric)
        ref_loss = reference.loss_of(ref)
        return {
            "loss_gap": reference.loss_gap(loss, ref_loss),
            "label_gap": reference.label_gap(ref, labels, ref_loss / self.n),
            "swap_gain": reference.swap_gain(self.x, medoids, ref,
                                             self.metric),
        }

    def layer_context(self) -> dict:
        return {"reports": self.reports}

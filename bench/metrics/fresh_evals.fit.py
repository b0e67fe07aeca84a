"""Fresh distance evaluations per timed fit (``FitReport.distance_evals``,
a count the bandit search keeps; it repeats exactly for a fixed seed)."""


def read(ctx):
    evals = [r.distance_evals for r in ctx.get("reports", [])]
    return sum(evals) / len(evals) if evals else None

"""Fresh distance evaluations per timed fit that recomputed PIC columns
the bounded ring had computed before and evicted
(``FitReport.refresh_evals``, a count the ring keeps; a part of
``fresh_evals.fit``).  ``None`` where the program keeps no such count."""


def read(ctx):
    evals = [getattr(r, "refresh_evals", None) for r in ctx.get("reports", [])]
    evals = [e for e in evals if e is not None]
    return sum(evals) / len(evals) if evals else None

"""Fresh distance evaluations of the certified stop's exact SWAP steps per
timed fit (``FitReport.certify_evals``, a count the SWAP driver keeps; a
part of ``fresh_evals.fit``).  ``None`` where the program keeps no such
count."""


def read(ctx):
    evals = [getattr(r, "certify_evals", None) for r in ctx.get("reports", [])]
    evals = [e for e in evals if e is not None]
    return sum(evals) / len(evals) if evals else None

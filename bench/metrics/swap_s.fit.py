"""Mean SWAP wall per timed fit (``FitReport.wall_by_phase["swap"]``,
host clock; the phase ends after its per-iteration host reads)."""


def read(ctx):
    walls = [r.wall_by_phase["swap"] for r in ctx.get("reports", [])
             if "swap" in r.wall_by_phase]
    return sum(walls) / len(walls) if walls else None

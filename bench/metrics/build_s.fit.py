"""Mean BUILD wall per timed fit (``FitReport.wall_by_phase["build"]``,
host clock; the phase ends in ``block_until_ready``)."""


def read(ctx):
    walls = [r.wall_by_phase["build"] for r in ctx.get("reports", [])
             if "build" in r.wall_by_phase]
    return sum(walls) / len(walls) if walls else None

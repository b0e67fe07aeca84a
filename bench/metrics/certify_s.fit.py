"""Mean wall of the certified stop's exact SWAP steps per timed fit
(``FitReport.wall_by_phase["certify"]``, the summed ``repro.swap.certify``
host spans; a part of ``swap_s.fit``).  ``None`` where the program runs
no such step."""


def read(ctx):
    walls = [r.wall_by_phase["certify"] for r in ctx.get("reports", [])
             if "certify" in r.wall_by_phase]
    return sum(walls) / len(walls) if walls else None

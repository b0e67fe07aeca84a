"""Share of the traced fit in which no operation ran on the device."""


def read(ctx):
    trace = ctx.get("trace")
    return None if trace is None else trace["idle_share"]

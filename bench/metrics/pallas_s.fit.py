"""Device time of Pallas kernels in the traced fit, from the trace."""


def read(ctx):
    trace = ctx.get("trace")
    return None if trace is None else trace["pallas_s"]

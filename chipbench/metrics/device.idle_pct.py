"""Share of the traced window in which no operation ran on the device:
1 - (union of the trace's operation intervals) / window, in %."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.idle_pct

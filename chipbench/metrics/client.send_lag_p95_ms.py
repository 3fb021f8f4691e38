"""How late the load generator handed requests over: submit time minus due
time, 95th percentile over the window, in ms.  The engine takes arrivals
at the start of each of its turns, so a long turn shows here first.  In
a traced run, over the requests due before the profiler stopped: its
stop holds the engine loop, and what came due then waited for that."""
from chipbench.harness import pctl


def read(ctx):
    recs = ctx.recs if ctx.traced is None else \
        [r for r in ctx.recs if r.due < ctx.traced[1]]
    value = pctl([r.submit - r.due for r in recs], 95)
    return None if value is None else value * 1e3

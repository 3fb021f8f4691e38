"""95th percentile of time to first token over every request due (open
loop) or sent (closed loop) in the window, timed from when it was due or
sent, on the host clock.  Requests that never produced a token are counted
in ``failed`` and left out here."""
from chipbench.harness import pctl


def read(ctx):
    return pctl([r.times[0] - r.due for r in ctx.recs if r.times], 95)

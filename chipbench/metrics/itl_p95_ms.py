"""95th percentile of the gap between consecutive tokens of a request,
over every gap that ends inside the window, on the host clock."""
from chipbench.harness import pctl


def read(ctx):
    t0, t1 = ctx.gen.t0, ctx.gen.t_end
    gaps = [b - a for r in ctx.gen.recs.values()
            for a, b in zip(r.times, r.times[1:]) if t0 <= b < t1]
    value = pctl(gaps, 95)
    return None if value is None else value * 1e3

"""Tokens committed inside the window, over the window's length."""


def read(ctx):
    t0, t1 = ctx.gen.t0, ctx.gen.t_end
    n = sum(t0 <= t < t1 for r in ctx.gen.recs.values() for t in r.times)
    return n / ctx.seconds

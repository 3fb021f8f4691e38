"""Mean number of requests a decode step advanced: tokens committed over
decode steps the engine counted, over the window and its drain."""


def read(ctx):
    steps = ctx.counters["decode_steps"]
    tokens = sum(len(r.tokens) for r in ctx.gen.recs.values())
    return tokens / steps if steps else None

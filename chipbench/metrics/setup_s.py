"""Seconds from the start of the run to the end of warm-up: loading,
weights, packing, engine build and every compile the traffic needs."""


def read(ctx):
    return ctx.setup_s

"""Host time the engine spends admitting one request: the program's
``admission`` phase spans over the traced turns (prefill dispatch, the
insertion of the prompt's K/V rows into the cache, lifecycle
bookkeeping), per request admitted in them, in ms."""
from chipbench import harness


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    admitted = len(harness.traced_prefills(ctx))
    if not admitted:
        return None
    return t.phase_s.get("admission", 0.0) / admitted * 1e3

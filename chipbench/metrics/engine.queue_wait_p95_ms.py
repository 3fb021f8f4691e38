"""How long a request waits in the engine's queue: the program's
``queued`` lifecycle spans (category ``request``; ``serve/engine.py``
``_observe_transition``: from submit, or a return to the queue, to
admission), summed per request, 95th percentile over the window's
requests, in ms.  A request counts if each of its waits ended before the
profiler's stop and none overlapped the profiler's start: the stop holds
the engine loop for tens of seconds, and the requests that arrive
meanwhile queue behind one another once it ends."""
from chipbench import program_spans
from chipbench.harness import pctl


def read(ctx):
    if ctx.traced is None:
        return None
    lo, hi = ctx.traced
    plan = ctx.gen.trace
    start = lo - (plan.stalls.get("start", 0.0) if plan is not None else 0.0)
    uids = {r.req.uid for r in ctx.recs}
    waits, left_out = {}, set()
    for ph, name, cat, _track, ts, dur, args in program_spans.events(ctx):
        if ph != "X" or cat != "request" or name != "queued" \
                or args["uid"] not in uids:
            continue
        uid = args["uid"]
        waits[uid] = waits.get(uid, 0.0) + dur
        if ts + dur > hi or start < lo and ts < lo and ts + dur > start:
            left_out.add(uid)
    value = pctl([w for uid, w in waits.items() if uid not in left_out], 95)
    return None if value is None else value * 1e3

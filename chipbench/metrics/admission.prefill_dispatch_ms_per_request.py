"""Host time admission spends dispatching the prefill: the program's
``prefill_dispatch`` spans (category ``admit``; ``serve/engine.py``
``_admit``: the padded token and length arrays and the ``jit_prefill``
call) that start inside the profiler's window, per request admitted in
the traced turns, in ms.  A part of ``engine.admission_ms_per_request``."""
from chipbench import harness, program_spans

SPAN = "prefill_dispatch"


def read(ctx):
    if ctx.trace is None or ctx.traced is None:
        return None
    lo, hi = ctx.traced
    spans = program_spans.events(ctx)
    durs = [dur for ph, name, cat, _track, ts, dur, _args in spans
            if ph == "X" and cat == "admit" and name == SPAN and lo <= ts < hi]
    admitted = len(harness.traced_prefills(ctx))
    if not durs or not admitted:
        return None
    return sum(durs) / admitted * 1e3

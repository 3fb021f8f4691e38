"""The program tracer's events over a traced run's window, for the readers
of the program's spans.

The harness's ``_TracePlan`` enables the tracer as the window opens, which
clears what it held, and disables it after the profiler's stop.  Disabling
keeps the events, so the readers, which run after that, find the window's
spans on the plan's tracer."""


def events(ctx) -> list[tuple]:
    """``(ph, name, cat, track, ts, dur, args)`` tuples, ``ts`` and ``dur``
    in perf_counter seconds; none outside a traced run."""
    plan = getattr(ctx.gen, "trace", None)
    if plan is None:
        return []
    return plan.tracer.get_tracer().events()

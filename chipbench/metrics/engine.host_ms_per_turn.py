"""Host time the engine loop spends per turn outside admission, dispatch
and the wait for the device: the program's ``reap``, ``prep``, ``commit``
and ``bookkeeping`` phase spans over the traced turns, per turn, in ms."""

PHASES = ("reap", "prep", "commit", "bookkeeping")


def read(ctx):
    t = ctx.trace
    if t is None or not t.turns:
        return None
    total = sum(t.phase_s.get(p, 0.0) for p in PHASES)
    return total / t.turns * 1e3

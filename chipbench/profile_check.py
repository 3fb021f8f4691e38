#!/usr/bin/env python3
"""One traced run of a cell that also checks the profiler's own trace.

    python chipbench/profile_check.py --workload yi6b-chat --seed 7 --seconds 51

Runs the cell as ``run.py --trace 1`` does and prints its result line last,
with a ``profile`` entry added: how long each ``jax.profiler.stop_trace``
took; the events on each line of the trace; how long reading the trace
took; and, for each engine span the program mirrors into the trace as a
``TraceAnnotation``, the largest distance between where the
``chipbench.sync`` mark places the span on the trace's clock and where its
annotation starts (``offset_us``; null where the program writes no such
annotation).  Exits 2 without a TPU, like ``run.py``.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, trace_reduce  # noqa: E402

#: engine spans whose placement is checked: (name, category)
CHECKED = (("admission", "phase"), ("kv_insert", "admit"), ("step", "step"))


def read_profile(path: str, spans, sync_host_s: float, window) -> dict:
    """Line sizes of the trace and the sync mark's offsets (see module)."""
    from jax.profiler import ProfileData
    t0 = time.perf_counter()
    pd = ProfileData.from_file(path)
    lines, mark, starts = {}, None, {name: [] for name, _ in CHECKED}
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            n = 0
            for e in line.events:
                n += 1
                if not host:
                    continue
                if e.name == trace_reduce.SYNC:
                    mark = e.start_ns
                elif e.name in starts:
                    starts[e.name].append(e.start_ns)
            lines[f"{plane.name} {line.name}"] = n
    read_s = time.perf_counter() - t0
    offsets = {}
    for name, cat in CHECKED:
        ann = sorted(starts[name])
        worst = None
        for ph, n, c, _track, ts, dur, _args in spans:
            # an annotation is written when it closes: one still open at
            # the profiler's stop is not in the trace
            if ph != "X" or n != name or c != cat or mark is None \
                    or not window[0] <= ts < ts + dur < window[1] or not ann:
                continue
            placed = mark + (ts - sync_host_s) * 1e9
            i = bisect.bisect_left(ann, placed)
            near = min((ann[j] for j in (i - 1, i) if 0 <= j < len(ann)),
                       key=lambda a: abs(a - placed))
            off = (near - placed) / 1e3
            worst = off if worst is None or abs(off) > abs(worst) else worst
        offsets[name] = {"annotations": len(ann), "offset_us": worst}
    return {"lines": lines, "read_s": read_s, "sync_offsets": offsets}


def run(workload: str, seed: int, seconds: float, **kw) -> dict:
    """``harness.run(..., trace=True, **kw)``'s result with ``profile``."""
    profile = {"stop_s": []}
    stop, reduce_dir = harness._TracePlan._stop, trace_reduce.reduce_dir

    def timed_stop() -> None:
        t = time.perf_counter()
        stop()
        profile["stop_s"].append(time.perf_counter() - t)

    def checked_reduce_dir(path: str, **kw):
        (xplane,) = glob.glob(f"{path}/**/*.xplane.pb", recursive=True)
        profile.update(read_profile(xplane, kw["spans"], kw["sync_host_s"],
                                    kw["window"]))
        return reduce_dir(path, **kw)

    harness._TracePlan._stop = staticmethod(timed_stop)
    trace_reduce.reduce_dir = checked_reduce_dir
    try:
        result = harness.run(workload, seed, seconds, True, **kw)
    finally:
        harness._TracePlan._stop = staticmethod(stop)
        trace_reduce.reduce_dir = reduce_dir
    result["profile"] = profile
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds)
    except harness.NoChip as e:
        print(f"chipbench: {e}; not running", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

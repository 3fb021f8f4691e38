#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest arrival rate the engine
sustains.  One process builds the cell once and offers its traffic at each
rate for ``--seconds``; a rate is sustained when time to first token
does not grow over the window (no growing backlog): the median over the
requests due in its last third is within ``--slack`` seconds of that over
its first third.  The cell's rate is then set to about four fifths of the knee,
by hand, in its traffic file.

    python chipbench/sweep.py --workload yi6b-chat --seed 5 --seconds 15 \
        --rates 4 6 8 10 12 14
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, system  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--slack", type=float, default=1.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload, harness.benchmark())
    harness.chips(cell.entry["chips"])
    harness.enable_cache()
    import jax
    params = system.pack(cell.conf, args.seed)
    eng = system.engine(cell.conf, params, cell.cell["engine"], args.seed)
    del params
    for req in harness.warm_requests(cell):
        eng.submit(system.request(req.uid, req.prompt, req.max_new))
    eng.run()
    rows = []
    for i, rate in enumerate(args.rates):
        d = harness.LoadGen(eng, cell, args.seed + i, args.seconds, rate=rate)
        d.run()
        jax.block_until_ready(eng.state)
        recs = d.in_window()
        ttft = [r.times[0] - r.due for r in recs if r.times]
        third = len(ttft) // 3
        first = harness.pctl(ttft[:third], 50)
        last = harness.pctl(ttft[-third:], 50)
        gaps = [b - a for r in recs for a, b in zip(r.times, r.times[1:])]
        toks = sum(d.t0 <= t < d.t_end for r in recs for t in r.times)
        row = {"rate": rate, "requests": len(recs),
               "ttft_p50_s": harness.pctl(ttft, 50),
               "ttft_p95_s": harness.pctl(ttft, 95),
               "itl_p95_ms": harness.pctl(gaps, 95) * 1e3,
               "tokens_per_s": toks / args.seconds,
               "ttft_p50_first_third_s": first,
               "ttft_p50_last_third_s": last,
               "sustained": last - first <= args.slack}
        rows.append(row)
        print(json.dumps(row), flush=True)
        time.sleep(1.0)
    ok = [r["rate"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_rate": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python3
"""Readings behind a cell's check limits, on the chip, in one process: for
each seed, a whole run of the cell (set-up, window, check), reading the
program's served-token gaps (the widest and the mean; the lower readings
are the largest over the seeds) and, on the same sampled requests, the
gaps of the tokens the control puts first (the reference with float8
matmul inputs; the upper readings are the smallest over the seeds), and
whether the cell's limits fail the control (``control_correct`` false).

    python chipbench/calibrate.py --workload yi6b-chat --seconds 15 \
        --seeds 101 102 103
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    rows = []
    for seed in args.seeds:
        r = harness.run(args.workload, seed, args.seconds, False,
                        control=True)
        row = {"seed": seed, "correct": r["correct"],
               "attempted": r["attempted"], "failed": r["failed"],
               "gaps": r["gaps"], "control_gaps": r["control_gaps"],
               "control_correct": r["control_correct"],
               "metrics": {k: m["value"] for k, m in r["metrics"].items()},
               "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for stat in ("max", "mean"):
        mine = [r["gaps"][stat] for r in rows if r["gaps"][stat] is not None]
        low = [r["control_gaps"][stat] for r in rows]
        summary[stat] = {"lower": max(mine) if mine else None,
                         "upper": min(low) if low else None}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

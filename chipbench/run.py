#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this machine holds.

    python chipbench/run.py --workload yi6b-chat --seed 7 --seconds 20 --trace 0

Sets up the cell (weights from the seed, packing, engine, every shape the
traffic uses), offers the traffic for ``--seconds``, checks what was served
against the plain reference, and prints the result as one JSON line last
on standard output: the end-to-end metrics with ``--trace 0``, the
per-layer metrics read from spans, counters and the profiler trace with
``--trace 1``.  Without a TPU, or with fewer chips than the cell asks for,
it exits 2 and prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except harness.NoChip as e:
        print(f"chipbench: {e}; not running", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

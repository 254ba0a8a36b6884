#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 bench/sweep.py --workload deepseek-7b-l14.chat --seed 5 \
        --seconds 30 --rates 0.3,0.45,0.6

One process runs the cell's traffic at each rate in turn (set-up included
each time) and prints a line per rate: TTFT and TPOT, whether every request
due in the window finished, and the mean queue wait of the window's first
and last quarter. A rate is sustained while the last quarter waits about
as long as the first. The cell's own rate is then set at about 0.7 of the
knee in its traffic file; the sweep is run once, when a cell is defined.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import harness, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.cell_of(harness.benchmark(), args.workload)
    mix = traffic.load(cell["traffic"])
    for rate in [float(r) for r in args.rates.split(",")]:
        try:
            r = harness.run_cell(args.workload, args.seed, args.seconds,
                                 False, time.perf_counter(),
                                 mix=dict(mix, rate_per_s=rate),
                                 details=True)
        except harness.NoChip as e:
            print(f"[sweep] {e}", file=sys.stderr)
            return 2
        print(json.dumps({"rate_per_s": rate, "correct": r["correct"],
                          **r["details"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload deepseek-7b-l14.chat --seed 7 \
        --seconds 51 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown`` of the trace, and last ``checks``: each number the
comparison with the reference held against its limit. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.

``--seeds a,b,...`` reads the comparison on several seeds in one process
(one line each; the benchmark's own runs never use it), and ``--control 1``
also reads the control: the reference computed in int8 (every matmul's
operands), teacher-forced on the same served tokens, put in the program's
place and judged by the same verdict (``control.correct``, and its widest
gap among the ``checks``); the limit must refuse it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench.harness import NoChip, run_cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seeds", help="comma-separated seeds, one process")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    if seeds == [None]:
        ap.error("--seed or --seeds is required")
    t_start = T_START
    for seed in seeds:
        try:
            result = run_cell(args.workload, seed, args.seconds,
                              bool(args.trace), t_start,
                              control=bool(args.control))
        except NoChip as e:
            print(f"[bench] {e}", file=sys.stderr)
            return 2
        if args.seeds:
            result["seed"] = seed
        print(json.dumps(result), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Chip benchmark of the SpecEE serving path (see BENCHMARK.json, PERF.md).

Run one cell from the root of a checkout:

    python3 bench/run.py --workload deepseek-7b-l14.chat --seed 7 \
        --seconds 51 --trace 0
"""

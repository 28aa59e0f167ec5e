#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload kron21.bfs --seed 7 --seconds 30 --trace 0

The cells, configurations and metrics are those of ``BENCHMARK.json`` at
the root of the checkout.  Exits 2 without a result where JAX finds no
accelerator or fewer chips than the cell asks for.  The last line of
standard output is the result as one JSON object; the numbers that decide
``correct`` are the last lines of standard error, each beside its limit.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

harness.set_env(ROOT)

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], root=ROOT, t_start=T_START))

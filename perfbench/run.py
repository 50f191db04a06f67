#!/usr/bin/env python3
"""Benchmark entry point: one run of one cell.

    python3 perfbench/run.py --workload pigs-cges-l4 --seed 7 --seconds 10 \
        --trace 0

Run it from the root of a checkout on a machine that holds the chips the
cell asks for.  It exits non-zero, printing no result, when JAX finds no
TPU or fewer chips than the cell needs.  The last line of stdout is the
result as JSON (see ``harness.py``).
"""
import os
import sys
import time

T0 = time.perf_counter()

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perfbench.harness import main

    sys.exit(main(t0=T0))

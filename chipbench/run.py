#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload qnn232-to-target --seed 7 \
        --seconds 10 --trace 0

The cells, metrics and bounds are in ``BENCHMARK.json`` at the root of
the checkout; see ``chipbench/harness.py``. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))

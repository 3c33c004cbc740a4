#!/usr/bin/env python3
"""Run one benchmark cell once on the chip:

    python3 chipbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers compared with their limits.
Exits non-zero, printing no result, without a TPU or with fewer chips
than the cell needs.
"""
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from chipbench import harness
    sys.exit(harness.main(t0=T0))

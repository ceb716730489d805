#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

See ``bench/harness/main.py``.  Exits non-zero, with no result line, where
JAX finds no TPU or fewer chips than the cell asks for.
"""
import os
import sys
import time

if __name__ == "__main__":
    t_start = time.perf_counter()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from harness.main import main
    sys.exit(main(t_start=t_start))

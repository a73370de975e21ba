#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 chip_bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name (``spec.py``), makes the weights and the
traffic from the seed, warms up, measures for ``--seconds``, checks what
the timed path produced against the float32 reference, and prints the
compared numbers beside their limits on standard error and one JSON object
as the last line of standard output. Exits 2, printing no result, where
JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()      # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chip_bench import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(t_start=T_START))

#!/usr/bin/env python3
"""Benchmark of ``select()`` on the TPU, one cell per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; see ``harness/runner.py``
for what a run does and prints.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.runner import entry  # noqa: E402

if __name__ == "__main__":
    sys.exit(entry(T_START))

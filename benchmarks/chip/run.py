#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout, and each is a file of its
own under ``benchmarks/chip``.  The run needs as many TPU chips as its
cell names: with no TPU, or too few, it exits nonzero and prints no
result.  The last line of stdout is the result (see ``chipbench/harness``).
"""

import time

T_START = time.perf_counter()

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench.harness import main

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))

"""The chip benchmark's own library: manifest lookup, traffic, work counts,
trace reduction, statistics and the correctness comparison.

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own under ``benchmarks/chip`` and is found by the name
that ``BENCHMARK.json`` gives it; this package holds only what they share.
"""

from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]      # benchmarks/chip
REPO_ROOT = BENCH_DIR.parents[1]

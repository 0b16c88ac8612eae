"""Run one benchmark cell once on the chip and print its result line.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are named in
BENCHMARK.json at the root of the checkout; the program under test is
imported from ``src/``.  Without a TPU it exits non-zero and prints no
result.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())

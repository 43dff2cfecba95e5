#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by the names in
``BENCHMARK.json`` at the checkout root (see ``bench/harness.py``).  The
last line of standard output is one JSON object; the numbers the
correctness check compared, with their limits, are the last lines of
standard error.  With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t0=T0))

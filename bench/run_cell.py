#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for.  Exits non-zero, printing no result, when JAX finds no TPU,
fewer chips than the cell needs, or a device kind missing from
``bench/peaks.json``.  See ``bench/harness.py``.
"""
import time

_T0 = time.perf_counter()   # set-up is timed from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t0=_T0))

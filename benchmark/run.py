#!/usr/bin/env python3
"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints
one JSON result as the last line. Exits non-zero, with no result, when JAX
finds no TPU or fewer chips than the cell asks for. See ``README.md``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402  (starts the set-up clock)

if __name__ == "__main__":
    sys.exit(harness.main())

#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness.py``.  Exits nonzero, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for, and where the system
under test (``src/repro``) is not beside the benchmark.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())

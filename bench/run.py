#!/usr/bin/env python3
"""Run one benchmark cell once; the last line of stdout is its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness.py`` for what a run does, and BENCHMARK.json for the
cells. Exits non-zero, with no result line, where there is no chip.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t0=T0))

#!/usr/bin/env python3
"""One run of one benchmark cell on the chip it is started on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. See ``bench/harness.py`` for the phases
and ``bench/README.md`` for the files a cell is made of.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout's root and the program's sources, in place of this script's
# directory, whose module names (trace, ...) would shadow the stdlib's
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(t_start=T_START))

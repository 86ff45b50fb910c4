#!/usr/bin/env python3
"""``wall_step.py`` with the wall clock put back by 250 ms instead of
forward. A step back reads worse on a wall-clock yardstick: every write
issued within the step's length, less a commit latency, before it is
"placed before a write acknowledged before it was issued"; a step forward
counts only writes that the system ordered after a later-issued one.

    python3 tests/benchmark/bench_util.py --generator \
        tests/benchmark/faults/wall_step_back.py .bench_runs/wall_step_back
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.realpath(__file__)))

import wall_step  # noqa: E402

if __name__ == "__main__":
    wall_step.STEP_S = -0.250
    wall_step.main(sys.argv[1:])

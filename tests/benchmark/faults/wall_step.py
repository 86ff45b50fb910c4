#!/usr/bin/env python3
"""A host whose wall clock is stepped inside the window: the benchmark's
generator with ``time.time()`` jumping forward by 250 ms halfway through
the measured window (what NTP's ``makestep`` or a VM put right after a
pause does to ``CLOCK_REALTIME``; ``CLOCK_MONOTONIC`` does not move).

Not a fault of the system: every generator steps at the same monotonic
instant, as the processes of one host would, and the deployment is
untouched. A yardstick that compares wall-clock instants reads the
writes in flight across the step as placed before writes acknowledged
before they were issued (``replica_realtime_wrong`` in the thousands at
a saturated cell's size, every other number 0: what refused PR 28). One
that compares monotonic instants reads 0 throughout and reports the step
as ``clock wall_step_ms``, about 250.

    python3 tests/benchmark/bench_util.py --generator \
        tests/benchmark/faults/wall_step.py .bench_runs/wall_step

prints the manifest to give ``run.py --manifest``.
"""

import json
import os
import sys
import time

STEP_S = 0.250
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__)))))
sys.path.insert(0, os.path.join(REPO, "benchmark", "generators"))

import closed_kv  # noqa: E402


def main(argv: list) -> None:
    with open(argv[argv.index("--traffic") + 1]) as f:
        warmup_s = json.load(f)["warmup_s"]
    wall, mono = time.time, time.monotonic
    step_at = [float("inf")]             # time.monotonic() seconds

    class Stdin:
        """``go <start> <end>``, on whichever clock the launcher names
        them: the step comes half a window after the warm-up."""

        def readline(self) -> str:
            line = sys.__stdin__.readline()
            _, start, end = line.split()
            step_at[0] = mono() + warmup_s + (float(end) - float(start)) / 2
            return line

    time.time = lambda: wall() + (STEP_S if mono() >= step_at[0] else 0.0)
    sys.stdin = Stdin()
    closed_kv.main(argv)


if __name__ == "__main__":
    main(sys.argv[1:])

"""What the broken role entries share: the benchmark's own role entry,
found beside the harness."""

import os
import sys

HARNESS_PARENT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))), "benchmark")
if HARNESS_PARENT not in sys.path:
    sys.path.insert(0, HARNESS_PARENT)

from harness import role_entry  # noqa: E402,F401

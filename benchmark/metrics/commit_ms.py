"""Issue to committed reply over all writes of the window, client's
clock: the percentile that the metric's name states (``..._p95_...`` is
the 95th)."""

import re

from harness.readings import percentile_ms


def read(run, metric):
    return percentile_ms(run, int(re.search(r"_p(\d+)(?=_|$)",
                                            metric["name"]).group(1)))

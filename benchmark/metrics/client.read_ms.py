"""Issue to answer over all linearizable reads issued in the window and
answered, client's clock: the percentile that the metric's name states
(``client.read_p95_ms`` is the 95th). Nothing where no read was."""

import re

READ = 1


def read(run, metric):
    import numpy as np

    ops = run.ops
    start, end = run.window
    latencies = ops["latency_s"][(ops["kind"] == READ)
                                 & (ops["issue_mono_s"] >= start)
                                 & (ops["issue_mono_s"] < end)
                                 & (ops["latency_s"] >= 0)]
    if not len(latencies):
        return None
    q = int(re.search(r"_p(\d+)(?=_|$)", metric["name"]).group(1))
    return float(np.percentile(latencies, q)) * 1e3

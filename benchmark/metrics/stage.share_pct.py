"""Self time of one stage over the window, as a share of the window's
wall time, in the busiest process of a kind (``/metrics``, the program's
``fpx_runtime_drain_stage_seconds``). The name is
``stage.share_pct.<kind>.<stage>``. 100 % is one thread busy in that
stage all the time; ``collect`` counts every collector thread of the
process."""

from harness.stages import growth


def read(run, metric):
    kind, stage = metric["name"].split(".", 3)[2:]
    found = growth(run, kind).get(stage)
    if found is None:
        return None
    return 100.0 * found[0] / run.seconds

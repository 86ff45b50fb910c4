"""Mean self time of one observation of a stage over the window (a
drain, a collect, a flush pass; for a ``-wait`` stage the mean time one
piece of work waited), in the busiest process of a kind. The name is
``stage.mean_ms.<kind>.<stage>``."""

from harness.stages import growth


def read(run, metric):
    kind, stage = metric["name"].split(".", 3)[2:]
    found = growth(run, kind).get(stage)
    if found is None:
        return None
    return 1e3 * found[0] / found[1]

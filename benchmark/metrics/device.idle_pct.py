"""The share of the traced span in which no operation ran on the
device."""


def read(run, metric):
    if run.trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])

"""Device busy time in the traced span over the device drains in it."""

from harness.readings import DRAINS, span_growth


def read(run, metric):
    drains = span_growth(run, DRAINS % "device")
    if drains <= 0 or run.trace["busy_s"] <= 0:
        return None
    return 1e6 * run.trace["busy_s"] / drains

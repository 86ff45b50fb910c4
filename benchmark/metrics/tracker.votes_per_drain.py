"""Votes per tracker drain over the window (``/metrics``)."""

from harness.readings import DRAINS, VOTES, window_growth


def read(run, metric):
    drains = (window_growth(run, DRAINS % "device")
              + window_growth(run, DRAINS % "host"))
    if drains <= 0:
        return None
    return (window_growth(run, VOTES % "device")
            + window_growth(run, VOTES % "host")) / drains

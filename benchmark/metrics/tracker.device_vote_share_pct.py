"""Of all votes the tracker took in the window, the share a device
kernel decided: votes of device drains less those spilled to the host
tally, over all votes (``/metrics``)."""

from harness.readings import VOTES, window_growth


def read(run, metric):
    device = window_growth(run, VOTES % "device")
    host = window_growth(run, VOTES % "host")
    spilled = window_growth(run, VOTES % "spilled")
    if device + host <= 0:
        return None
    return 100.0 * (device - spilled) / (device + host)

"""Jitted calls per device drain of the tracker over the window
(``/metrics``): growth of the launch series over growth of the
device-drain series. 1.0 is one launch a drain; more says that ring
straddles, drains of several rounds or sparse stragglers split drains.
A program without the launch series reads nothing."""

from harness.readings import DRAINS, window_growth

LAUNCHES = "multipaxos_proxy_leader_tpu_launches_total"


def read(run, metric):
    drains = window_growth(run, DRAINS % "device")
    launches = window_growth(run, LAUNCHES)
    if drains <= 0 or launches <= 0:
        return None
    return launches / drains

"""The least time the chip could take for the traced span's Mencius
quorum work over the time its device was busy: the work of the votes the
Mencius proxy leaders sent to the device and of the decisions that came
back (``trace_reduce.least_bytes``, over the configuration's board rows),
whatever program did it. Memory bandwidth bounds it. A program whose
Mencius proxy leader keeps no device vote series reads nothing."""

from harness.readings import span_growth, span_tracker_growth
from harness.trace_reduce import least_bytes, peaks_of

VOTES = 'mencius_proxy_leader_tpu_votes_total{path="device"}'


def read(run, metric):
    peak = peaks_of(run.device["kind"])["hbm_bytes_per_s"]
    busy_s = run.trace["busy_s"]
    votes = span_growth(run, VOTES)
    if peak is None or busy_s <= 0 or votes <= 0:
        return None
    work = least_bytes(int(votes), span_tracker_growth(run, "reported"),
                       run.config["board"]["nodes"])
    return 100.0 * (work / peak) / busy_s

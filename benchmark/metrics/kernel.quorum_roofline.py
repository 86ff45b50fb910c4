"""The least time the chip could take for the traced span's quorum work
over the time its device was busy. The work is counted from the votes
that went to the device and the decisions that came back
(``trace_reduce.least_bytes``), whatever program did it; memory bandwidth
bounds it, there is no matrix product worth counting."""

from harness.readings import span_growth, span_tracker_growth, VOTES
from harness.trace_reduce import least_bytes, peaks_of


def read(run, metric):
    peak = peaks_of(run.device["kind"])["hbm_bytes_per_s"]
    busy_s = run.trace["busy_s"]
    votes = span_growth(run, VOTES % "device")
    if peak is None or busy_s <= 0 or votes <= 0:
        return None
    work = least_bytes(int(votes), span_tracker_growth(run, "reported"),
                       run.config["board"]["nodes"])
    return 100.0 * (work / peak) / busy_s

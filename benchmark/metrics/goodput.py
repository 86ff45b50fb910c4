"""Writes acknowledged to a client inside the window, over the window's
seconds (generators' rows, client's clock)."""

from harness.readings import WRITE


def read(run, metric):
    ops = run.ops
    start, end = run.window
    acked_at = ops["issue_mono_s"] + ops["latency_s"]
    acked = ((ops["kind"] == WRITE) & (ops["latency_s"] >= 0)
             & (acked_at >= start) & (acked_at < end))
    return int(acked.sum()) / (end - start)

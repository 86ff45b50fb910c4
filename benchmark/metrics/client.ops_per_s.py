"""Operations of either kind, reads and writes, answered to a client
inside the window, over the window's seconds (generators' rows, client's
clock)."""


def read(run, metric):
    ops = run.ops
    start, end = run.window
    answered_at = ops["issue_mono_s"] + ops["latency_s"]
    answered = ((ops["latency_s"] >= 0) & (answered_at >= start)
                & (answered_at < end))
    return int(answered.sum()) / (end - start)

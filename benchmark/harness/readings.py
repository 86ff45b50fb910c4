"""What several metric readers share: the window's writes, a counter's
growth over the window or over the traced span."""

from __future__ import annotations

WRITE = 0


def window_write_latencies(run):
    """Seconds from issue to committed reply of every write issued in the
    window and answered."""
    ops = run.ops
    start, end = run.window
    return ops["latency_s"][(ops["kind"] == WRITE)
                            & (ops["issue_mono_s"] >= start)
                            & (ops["issue_mono_s"] < end)
                            & (ops["latency_s"] >= 0)]


def percentile_ms(run, q: float):
    import numpy as np

    latencies = window_write_latencies(run)
    if not len(latencies):
        return None
    return float(np.percentile(latencies, q)) * 1e3


def window_growth(run, name: str) -> float:
    """How much the chip owner's ``/metrics`` series ``name`` grew between
    the window's two scrapes."""
    first = run.scrapes["start"][run.chip_owner]
    last = run.scrapes["end"][run.chip_owner]
    return last.get(name, 0.0) - first.get(name, 0.0)


def span_growth(run, name: str) -> float:
    """The same between the two ends of the traced span."""
    return (run.span["after"]["metrics"].get(name, 0.0)
            - run.span["before"]["metrics"].get(name, 0.0))


def span_tracker_growth(run, name: str) -> int:
    """Growth of one of the role entry's own tracker counters, summed
    over the trackers, across the traced span."""
    return sum(after[name] - before[name]
               for before, after in zip(run.span["before"]["trackers"],
                                        run.span["after"]["trackers"]))


def mean_span_ms(run, name: str):
    """Mean length of the host spans called ``name`` in the traced span."""
    span = run.trace["host_spans"].get(name)
    if not span or not span["count"]:
        return None
    return 1e3 * span["total_s"] / span["count"]


VOTES = 'multipaxos_proxy_leader_tpu_votes_total{path="%s"}'
DRAINS = 'multipaxos_proxy_leader_tpu_drains_total{path="%s"}'

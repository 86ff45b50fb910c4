"""What the busiest acceptor's write-ahead log did in the window, over
the writes answered to clients in it (``goodput``'s writes). The name is
``wal.<what>_per_commit``:

  fsyncs   group commits: growth of the count of
           ``fpx_runtime_wal_fsync_seconds`` (one observation a drain's
           fsync scope). A drain that votes for hundreds of writes pays
           one fsync for them.
  bytes    bytes made durable, group commits and compactions: growth of
           ``fpx_runtime_wal_synced_bytes_total``. Between compactions it
           is a vote's record; a compaction writes everything the
           acceptor has ever voted for again.

A program without the series, or whose roles keep no log (the fsync
histogram then stays at zero), gives nothing to read."""

from harness.readings import WRITE
from harness.stages import busiest

SERIES = {"fsyncs": 'fpx_runtime_wal_fsync_seconds_count{role="%s"}',
          "bytes": 'fpx_runtime_wal_synced_bytes_total{role="%s"}'}


def read(run, metric):
    what = metric["name"].split(".")[1].partition("_per_")[0]
    label = busiest(run, "acceptor")
    if label is None:
        return None
    series = SERIES[what] % label
    first = run.scrapes["start"].get(label, {}).get(series, 0.0)
    grown = run.scrapes["end"][label].get(series, 0.0) - first
    ops = run.ops
    start, end = run.window
    acked_at = ops["issue_mono_s"] + ops["latency_s"]
    writes = int(((ops["kind"] == WRITE) & (ops["latency_s"] >= 0)
                  & (acked_at >= start) & (acked_at < end)).sum())
    if grown <= 0 or not writes:
        return None
    return grown / writes

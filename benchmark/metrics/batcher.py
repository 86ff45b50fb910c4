"""What the batchers did over the window, from their own counters
(``/metrics``), summed over every batcher process. The name is
``batcher.<what>``:

  commands_per_batch   commands the batchers put in batches
                       (``multipaxos_batcher_batched_commands_total``)
                       over the batches they sent
                       (``multipaxos_batcher_batches_sent_total``, every
                       ``reason``): the commands a log slot holds
  batched_share_pct    100 x the commands batched over the writes whose
                       answer came inside the window: how much of the
                       cell's load reached its slot through a batcher

A program whose batchers keep no ``batched_commands`` series gives
nothing to read, and neither does a window in which no batch was sent."""

from harness.readings import WRITE

BATCHED = "multipaxos_batcher_batched_commands_total"
BATCHES = "multipaxos_batcher_batches_sent_total"


def batchers_growth(run, name: str):
    """Growth over the window of every series of ``name`` (whatever its
    labels), over all batcher processes; None where none has it."""
    found = None
    for label, scrape in run.scrapes["end"].items():
        if not label.startswith("batcher"):
            continue
        first = run.scrapes["start"].get(label, {})
        for series, value in scrape.items():
            if series.split("{", 1)[0] == name:
                found = (found or 0.0) + value - first.get(series, 0.0)
    return found


def answered_writes(run) -> int:
    """Writes whose answer came inside the window."""
    ops = run.ops
    start, end = run.window
    answered_at = ops["issue_mono_s"] + ops["latency_s"]
    return int(((ops["kind"] == WRITE) & (ops["latency_s"] >= 0)
                & (answered_at >= start) & (answered_at < end)).sum())


def read(run, metric):
    what = metric["name"].split(".", 1)[1]
    batched = batchers_growth(run, BATCHED)
    if what == "commands_per_batch":
        batches = batchers_growth(run, BATCHES)
        if batched is None or not batches:
            return None
        return batched / batches
    if what == "batched_share_pct":
        answered = answered_writes(run)
        if not batched or not answered:
            return None
        return 100.0 * batched / answered
    raise ValueError(f"no reading called {metric['name']!r}")

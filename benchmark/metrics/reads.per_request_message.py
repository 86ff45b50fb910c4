"""Reads the busiest replica executed in the window for each read
request message it handled (a ``ReadRequest`` is one read, a client's
``ReadRequestBatch`` the reads one of its loop passes issued): growth of
``multipaxos_replica_executed_reads_total`` over growth of
``multipaxos_replica_read_messages_total`` between the window's two
scrapes. Nothing where the program has no such counter or the replica
handled no read request."""

from harness.stages import busiest

EXECUTED = "multipaxos_replica_executed_reads_total"
MESSAGES = "multipaxos_replica_read_messages_total"


def read(run, metric):
    label = busiest(run, "replica")
    if label is None:
        return None
    first = run.scrapes["start"].get(label, {})
    last = run.scrapes["end"][label]
    if MESSAGES not in last:
        return None
    messages = last[MESSAGES] - first.get(MESSAGES, 0.0)
    if messages <= 0:
        return None
    return (last.get(EXECUTED, 0.0) - first.get(EXECUTED, 0.0)) / messages

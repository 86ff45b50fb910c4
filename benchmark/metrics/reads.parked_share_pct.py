"""Of the reads the busiest replica executed in the window, the share
it had to park first because their slot was not yet executed: growth of
``multipaxos_replica_deferred_reads_total`` over growth of
``multipaxos_replica_executed_reads_total`` between the window's two
scrapes. Nothing where the program has no such counter or the replica
executed no read."""

from harness.stages import busiest

PARKED = "multipaxos_replica_deferred_reads_total"
EXECUTED = "multipaxos_replica_executed_reads_total"


def read(run, metric):
    label = busiest(run, "replica")
    if label is None:
        return None
    first = run.scrapes["start"].get(label, {})
    last = run.scrapes["end"][label]
    if PARKED not in last:
        return None
    executed = last.get(EXECUTED, 0.0) - first.get(EXECUTED, 0.0)
    if executed <= 0:
        return None
    return 100.0 * (last[PARKED] - first.get(PARKED, 0.0)) / executed

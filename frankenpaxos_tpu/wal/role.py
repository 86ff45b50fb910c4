"""DurableRole: the ONE implementation of the group-commit ordering.

Every durable actor (MultiPaxos/Mencius acceptors and replicas) shares
the same release discipline -- records staged during a drain are
fsynced ONCE, and only then do the acks that depend on them leave the
actor. That ordering is the WAL's entire safety argument (a crash can
never lose acked state), so it lives here exactly once instead of
drifting across four role classes; only ``_wal_compact`` (what live
state a compaction re-logs) and recovery genuinely differ per role.

A drain is sync -> release -> compact. WHY THE ACKS MAY LEAVE BEFORE A
DUE COMPACTION, and do: every held message depends only on records
the drain's ``wal.sync()`` has just made durable in the OLD segments.
``Wal.compact`` syncs what is staged, writes the new segment and
fsyncs it, and only THEN deletes the old ones; recovery replays the
old segments first and resets at the new segment's ``WalSnapshot``.
So at whatever storage call a compaction dies, recovery finds either
the old segments alone, or the old segments followed by the whole new
one, or the new one alone, and each holds every record a released ack
depended on. (That rests, as it did when the compaction came first,
on the new segment reaching the disk whole or not at all before its
fsync: a torn prefix of it would reset recovery at a snapshot
whose re-logged state is cut short, for the acks of every EARLIER
drain under either order. docs/DURABILITY.md,
"Segment rotation and watermark GC".) Holding the acks through the
rewrite bought no safety and cost the rewrite's whole length in
latency, for this drain's acks and, in a thrifty quorum, for every
slot that needs this role.
"""

from __future__ import annotations

import time


class DurableRole:
    """Mixin over Actor: wal staging, deferred sends, and the drain's
    sync -> release -> compact sequence."""

    def _wal_init(self, wal) -> None:
        self.wal = wal
        self._wal_sends: list = []
        # The log's counts as series (obs.RuntimeMetrics.wal_series).
        self._wal_series = None

    def _wal_recover(self) -> None:
        """Rebuild the role from its log (the role's own
        ``_recover_from_wal``), timed as stage ``wal-recover``. A role
        is built beside its event loop's thread, not on it, so the
        time is handed to the stage and no scope is opened."""
        metrics = self.transport.runtime_metrics
        if metrics is None:
            self._recover_from_wal()
            return
        t0 = metrics.clock()
        self._recover_from_wal()
        metrics.observe_stage("wal-recover", metrics.clock() - t0)
        self._wal_counts().recovered(self.wal.metrics.recovered_records)

    def _wal_counts(self):
        """The log's series, made at the first call that finds metrics
        attached to the transport; None before."""
        series = self._wal_series
        if series is None:
            metrics = self.transport.runtime_metrics
            if metrics is not None:
                series = self._wal_series = metrics.wal_series()
        return series

    def _wal_send(self, dst, message) -> None:
        """Send, or -- when durable -- hold until the drain's group
        commit (the group-commit rule, wal/log.py): an ack that
        depends on a staged record must never precede its fsync."""
        if self.wal is None:
            self.send(dst, message)
        else:
            self._wal_sends.append((dst, message))

    def _wal_drain(self) -> None:
        """The on_drain tail for durable roles: ONE fsync covers every
        record this drain appended, then the held acks go out, and
        only then does a due compaction run (the module docstring has
        why that order is safe). The two paxtrace drain stages
        wal-fsync and send-release are exactly the latency a command
        spends waiting on the group commit (the dominant cloud-Paxos
        cost PAPERS.md's experience report attributes poorly without
        tracing). Stage wal-compact opens every drain, as wal-fsync
        does round a sync that may have nothing to write: the check
        for a due compaction and, when one is due, the compaction,
        which runs here on the loop and stops the role's answers for
        as long as it takes. What the drain released is therefore
        pushed to the wire first (``Transport.flush_sends``): a
        transport that writes at the end of a loop pass would
        otherwise hold it in its buffers through the rewrite."""
        wal = self.wal
        if wal is None:
            return
        with self.trace_stage("wal-fsync"):
            wal.sync()
        if self._wal_sends:
            sends, self._wal_sends = self._wal_sends, []
            with self.trace_stage("send-release"):
                for dst, message in sends:
                    self.send(dst, message)
        compacted_s = None
        with self.trace_stage("wal-compact"):
            if wal.wants_compaction():
                self.transport.flush_sends()
                t0 = time.perf_counter()
                self._wal_compact()
                compacted_s = time.perf_counter() - t0
        series = self._wal_counts()
        if series is not None:
            series.publish(wal.metrics)
            if compacted_s is not None:
                series.compacted(compacted_s)

    def _wal_compact(self) -> None:  # pragma: no cover - roles override
        raise NotImplementedError

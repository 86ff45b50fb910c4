"""DurableRole: the ONE implementation of the group-commit ordering.

Every durable actor (MultiPaxos/Mencius acceptors and replicas) shares
the same release discipline -- records staged during a drain are
fsynced ONCE, and only then do the acks that depend on them leave the
actor. That ordering is the WAL's entire safety argument (a crash can
never lose acked state), so it lives here exactly once instead of
drifting across four role classes; only ``_wal_compact`` (what live
state a compaction re-logs) and recovery genuinely differ per role.
"""

from __future__ import annotations

import time


class DurableRole:
    """Mixin over Actor: wal staging, deferred sends, and the drain's
    sync -> compact -> release sequence."""

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
        record this drain appended, compaction runs on the same
        boundary, and only then do the held acks go out. The two
        paxtrace drain stages wal-fsync and send-release are exactly
        the latency a command spends waiting on the group commit (the
        dominant cloud-Paxos cost PAPERS.md's experience report
        attributes poorly without tracing). Stage wal-compact opens
        every drain, as wal-fsync does round a sync that may have
        nothing to write: the check for a due compaction and, when
        one is due, the compaction, which runs here on the loop and
        holds this drain's acks back for as long as it takes."""
        wal = self.wal
        if wal is None:
            return
        with self.trace_stage("wal-fsync"):
            wal.sync()
        compacted_s = None
        with self.trace_stage("wal-compact"):
            if wal.wants_compaction():
                t0 = time.perf_counter()
                self._wal_compact()
                compacted_s = time.perf_counter() - t0
        series = self._wal_counts()
        if series is not None:
            series.publish(wal.metrics)
            if compacted_s is not None:
                series.compacted(compacted_s)
        if self._wal_sends:
            sends, self._wal_sends = self._wal_sends, []
            with self.trace_stage("send-release"):
                for dst, message in sends:
                    self.send(dst, message)

    def _wal_compact(self) -> None:  # pragma: no cover - roles override
        raise NotImplementedError

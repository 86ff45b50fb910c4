"""MultiPaxos Replica.

Reference behavior: multipaxos/Replica.scala:151-691. A BufferMap log
(Replica.scala:194), in-order ``execute_log`` advancing the executed
watermark (Replica.scala:394-453), a simple client table (in-order
execution per client), chosen-watermark gossip every N entries with
responsibility round-robin'd across replicas (Replica.scala:421-447), a
randomized hole-recovery timer (Replica.scala:238-260), and deferred
reads parked until their slot executes (Replica.scala:203-211,455-530).

The log holds only what cannot be executed yet. A ``ChosenRun`` is
walked once as plain rows (``LazyValueArray.rows``: no value object is
made). One that reaches the executed watermark is executed straight
from them and never enters the log; the rows of one above a hole are
parked in the log, a slot each, and leave it once they have been
executed. Nothing reads an executed entry: a slot under the watermark
is a duplicate without a look at the log.
"""

from __future__ import annotations

import dataclasses
import random
import struct
from typing import Optional

from frankenpaxos_tpu.protocols.multipaxos.config import (
    DistributionScheme,
    MultiPaxosConfig,
)
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    Chosen,
    ChosenRun,
    ChosenWatermark,
    ClientReply,
    ClientReplyArray,
    ClientReplyBatch,
    Command,
    CommandId,
    EventualReadRequest,
    EventualReadRequestBatch,
    NOOP,
    ReadReply,
    ReadReplyBatch,
    ReadRequest,
    ReadRequestBatch,
    Recover,
    SequentialReadRequest,
    SequentialReadRequestBatch,
)
from frankenpaxos_tpu.protocols.multipaxos.wire import (
    _put_address,
    _put_bytes,
    _take_address,
    _take_bytes,
    decode_value_array,
    encode_value_array,
    LazyValueArray,
    row_value,
    value_row,
)
from frankenpaxos_tpu.runs.records import log_chosen_values
from frankenpaxos_tpu.runtime import Actor, Collectors, FakeCollectors, Logger
from frankenpaxos_tpu.runtime.transport import Address, Transport
from frankenpaxos_tpu.statemachine import StateMachine
from frankenpaxos_tpu.utils import BufferMap
from frankenpaxos_tpu.wal import DurableRole, WalChosenRun, WalSnapshot


@dataclasses.dataclass(frozen=True)
class ReplicaOptions:
    log_grow_size: int = 5000
    unsafe_dont_use_client_table: bool = False
    send_chosen_watermark_every_n_entries: int = 100
    recover_log_entry_min_period_s: float = 10.0
    recover_log_entry_max_period_s: float = 20.0
    unsafe_dont_recover: bool = False
    measure_latencies: bool = True
    # paxload read-path admission (serve/admission.py): a replica
    # sheds READ traffic only -- Chosen/ChosenRun deliveries are the
    # write pipeline's control plane and never pass the controller.
    # The in-flight measure here is the deferred-read backlog. All
    # zeros (default) builds no controller.
    admission_token_rate: float = 0.0
    admission_token_burst: float = 0.0
    admission_inflight_limit: int = 0
    admission_inbox_capacity: int = 0
    admission_inbox_policy: str = "reject"
    admission_codel_target_s: float = 0.0
    admission_codel_interval_s: float = 0.1
    admission_retry_after_ms: int = 0


class Replica(Actor, DurableRole):
    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, state_machine: StateMachine,
                 config: MultiPaxosConfig,
                 options: ReplicaOptions = ReplicaOptions(),
                 collectors: Collectors | None = None, seed: int = 0,
                 wal=None):
        super().__init__(address, transport, logger)
        config.check_valid()
        logger.check(address in config.replica_addresses)
        self.config = config
        self.options = options
        self.state_machine = state_machine
        self.rng = random.Random(seed)
        collectors = collectors or FakeCollectors()
        self.metrics_latency = collectors.summary(
            "multipaxos_replica_requests_latency_seconds", labels=("type",))
        self.metrics_executed = collectors.counter(
            "multipaxos_replica_executed_commands_total")
        self.metrics_reads = collectors.counter(
            "multipaxos_replica_executed_reads_total")
        self.metrics_deferred_reads = collectors.counter(
            "multipaxos_replica_deferred_reads_total")
        # One a read request message handled, whatever its width; with
        # executed_reads it gives reads a request message.
        self.metrics_read_messages = collectors.counter(
            "multipaxos_replica_read_messages_total")
        # Which way a ChosenRun with something new went: executed from
        # its columns ("direct") or parked in the log ("logged").
        runs = collectors.counter("multipaxos_replica_runs_total",
                                  labels=("path",))
        self.metrics_runs_direct = runs.labels("direct")
        self.metrics_runs_logged = runs.labels("logged")
        # Entries the log holds after a run: what waits above a hole.
        # Chosen entries above the executed watermark, and the log
        # holds no other (the tests count the log beside it).
        self.metrics_log_entries = collectors.gauge(
            "multipaxos_replica_log_entries")
        self.index = list(config.replica_addresses).index(address)
        self.log: BufferMap = BufferMap(options.log_grow_size)
        # slot -> [(when it was parked, its commands)], one entry a
        # parked read or a parked batch; taken out as the slot executes.
        self.deferred_reads: BufferMap = BufferMap(options.log_grow_size)
        # Every entry below executed_watermark has been executed; numChosen
        # counts chosen entries -- together they detect pending holes.
        self.executed_watermark = 0
        self.num_chosen = 0
        # (client address, pseudonym) -> (largest executed id, its reply).
        self.client_table: dict[tuple, tuple[int, bytes]] = {}
        # Durability (wal/): chosen entries append to the WAL as they
        # arrive and client replies are held back until on_drain's
        # group-commit fsync releases them (DurableRole), so an
        # acknowledged write is always recoverable from this replica's
        # own log. Compaction snapshots the SM at the executed
        # watermark and reclaims every segment behind it (the
        # watermark GC extended to disk). wal=None is the reference's
        # in-memory behavior.
        self._wal_init(wal)
        # paxload read-path admission (serve/): built only when armed.
        self._deferred_read_count = 0
        self._wm_dirty = False  # executed advanced since last drain
        from frankenpaxos_tpu.serve.admission import (
            AdmissionController,
            options_from_flat,
        )

        admission_options = options_from_flat(options)
        if admission_options is not None:
            self.admission = AdmissionController(
                admission_options, role=f"replica_{self.index}",
                metrics=transport.runtime_metrics)
            transport.note_admission(address, self)
        self.recover_timer = None
        if wal is not None:
            self._wal_recover()
        if not options.unsafe_dont_recover:
            self.recover_timer = self.timer(
                "recover",
                self.rng.uniform(options.recover_log_entry_min_period_s,
                                 options.recover_log_entry_max_period_s),
                self._recover)
            if wal is not None and self.executed_watermark < self.num_chosen:
                # Recovered with holes (chosen records above a gap):
                # start hole recovery immediately on rejoin.
                self.recover_timer.start()

    # --- durability -------------------------------------------------------
    def _snapshot_payload(self) -> bytes:
        """SM snapshot + executed watermark + client table, encoded
        with the wire helpers (no code execution on decode except the
        addresses' own escape hatch)."""
        out = bytearray()
        out += struct.pack("<q", self.executed_watermark)
        _put_bytes(out, self.state_machine.to_bytes())
        out += struct.pack("<i", len(self.client_table))
        for (address, pseudonym), (client_id, result) in \
                self.client_table.items():
            _put_address(out, address)
            out += struct.pack("<qq", pseudonym, client_id)
            _put_bytes(out, result)
        return bytes(out)

    def _restore_snapshot(self, payload: bytes) -> None:
        (watermark,) = struct.unpack_from("<q", payload, 0)
        sm_bytes, at = _take_bytes(payload, 8)
        (n,) = struct.unpack_from("<i", payload, at)
        at += 4
        table: dict = {}
        for _ in range(n):
            address, at = _take_address(payload, at)
            pseudonym, client_id = struct.unpack_from("<qq", payload, at)
            result, at = _take_bytes(payload, at + 16)
            table[(address, pseudonym)] = (client_id, result)
        self.state_machine.from_bytes(sm_bytes)
        self.executed_watermark = watermark
        # Every slot below the watermark is chosen and executed; the
        # log is GC'd to the watermark, so replayed/late entries below
        # it read as duplicates (see _log_chosen).
        self.num_chosen = watermark
        self.client_table = table
        self.log.garbage_collect(watermark)
        self.deferred_reads.garbage_collect(watermark)

    def _recover_from_wal(self) -> None:
        for record in self.wal.recover(self.logger):
            if isinstance(record, WalSnapshot):
                # Compaction base: reset, then restore.
                self.log = BufferMap(self.options.log_grow_size)
                self.executed_watermark = 0
                self.num_chosen = 0
                self.client_table = {}
                self._restore_snapshot(record.payload)
            elif isinstance(record, WalChosenRun):
                # Re-execute the recovered records as they were taken
                # (deterministic: same entries, same order). Replies
                # are DISCARDED -- every reply the pre-crash replica
                # sent was covered by a synced record, and unacked
                # clients resend (the client table keeps re-execution
                # exactly-once).
                self._take_run(record.start_slot,
                               decode_value_array(record.values), {},
                               durable=False)
            else:
                self.logger.fatal(
                    f"unexpected replica WAL record {record!r}")

    def _park(self, start_slot: int, rows: list) -> int:
        """Park the rows of a contiguous run of chosen values in the
        log (runs/records.py), a slot each, until the slots before
        them have executed; returns how many were new."""
        # The direct path moves the watermark without a look at the
        # log: catch the log up first, or a put sizes its buffer by
        # the distance.
        self.log.garbage_collect(self.executed_watermark)
        new, _ = log_chosen_values(self.log, self.executed_watermark,
                                   start_slot, 1, rows)
        self.num_chosen += new
        return new

    def _wal_compact(self) -> None:
        """Snapshot the SM at the executed watermark and reclaim every
        segment behind it -- the in-memory watermark GC extended to
        disk. Chosen-but-unexecuted entries above the watermark (holes
        pending) are re-logged after the snapshot marker."""
        records = []
        for slot, row in self.log.items(start=self.executed_watermark):
            records.append(WalChosenRun(
                start_slot=slot, stride=1,
                values=encode_value_array((row_value(row),))))
        self.wal.compact(WalSnapshot(payload=self._snapshot_payload()),
                         records)
        self.log.garbage_collect(self.executed_watermark)
        self.deferred_reads.garbage_collect(self.executed_watermark)

    def on_drain(self) -> None:
        # Drain-granular watermark tail (paxload): the every-N
        # notification above leaves the leader's view up to N-1 slots
        # stale when the pipeline goes quiet mid-decade -- with a
        # watermark-tied in-flight admission budget that staleness is
        # a LIVENESS hole (the span never drops below the limit and
        # every retry is rejected until budgets exhaust). One extra
        # message per drain, from one replica (slot-round-robin),
        # closes the tail.
        if (self._wm_dirty
                and self.executed_watermark
                % self.options.send_chosen_watermark_every_n_entries
                and self.executed_watermark % self.config.num_replicas
                == self.index):
            self._send_chosen_watermark(self.executed_watermark)
        self._wm_dirty = False
        # GROUP COMMIT (DurableRole): one fsync covers every chosen
        # entry this drain logged; only then do the replies it
        # produced go out.
        self._wal_drain()

    def _send_chosen_watermark(self, slot: int) -> None:
        watermark = ChosenWatermark(slot=slot)
        proxy = self._proxy_replica_address()
        if proxy is not None:
            self._wal_send(proxy, watermark)
        else:
            for leader in self.config.leader_addresses:
                self._wal_send(leader, watermark)

    # --- helpers ----------------------------------------------------------
    def _proxy_replica_address(self) -> Optional[Address]:
        if not self.config.proxy_replica_addresses:
            return None
        if self.config.distribution_scheme == DistributionScheme.HASH:
            return self.config.proxy_replica_addresses[
                self.rng.randrange(self.config.num_proxy_replicas)]
        return self.config.proxy_replica_addresses[self.index]

    def _recover(self) -> None:
        recover = Recover(slot=self.executed_watermark)
        proxy = self._proxy_replica_address()
        if proxy is not None:
            self.send(proxy, recover)
        else:
            for leader in self.config.leader_addresses:
                self.send(leader, recover)
        self.recover_timer.start()

    def _execute_rows(self, rows, replies: dict) -> None:
        """Execute ``rows``, a slot each from the executed watermark
        up: ``NOOP``, or the slot's commands as ``(client address,
        pseudonym, client id, payload)`` (``LazyValueArray.rows``).
        The one place a write is executed (Replica.scala:300-344,
        394-453): exactly once through the client table, in slot
        order, one ``state_machine.run`` a command. Reply entries are
        appended to ``replies[client address]`` as ``ClientReplyArray``
        carries them; reads parked at a slot are answered right after
        it. What is the same for every slot is done once a call."""
        table = self.client_table
        keep = not self.options.unsafe_dont_use_client_table
        run = self.state_machine.run
        num_replicas = self.config.num_replicas
        index = self.index
        reads_parked = self._deferred_read_count > 0
        first = slot = self.executed_watermark
        executed = 0
        for batch in rows:
            if batch is not NOOP:
                mine = slot % num_replicas == index
                for address, pseudonym, client_id, payload in batch:
                    key = (address, pseudonym)
                    cached = table.get(key)
                    if cached is None or client_id > cached[0]:
                        result = run(payload)
                        executed += 1
                        if keep:
                            table[key] = (client_id, result)
                        if not mine:
                            continue
                    elif client_id == cached[0]:
                        # A resend of the client's newest command:
                        # answered from the table, by every replica.
                        result = cached[1]
                    else:
                        continue
                    entries = replies.get(address)
                    if entries is None:
                        entries = replies[address] = []
                    entries.append((pseudonym, client_id, slot, result))
            slot += 1
            self.executed_watermark = slot
            if reads_parked:
                parked = self.deferred_reads.pop(slot - 1)
                if parked is not None:
                    self._process_deferred_reads(parked)
        if slot == first:
            return
        self._wm_dirty = True
        self.metrics_executed.inc(executed)
        # Every boundary the rows crossed is announced, by the replica
        # whose turn it is (Replica.scala:421-447).
        every_n = self.options.send_chosen_watermark_every_n_entries
        for boundary in range((first // every_n + 1) * every_n, slot + 1,
                              every_n):
            if (boundary // every_n) % num_replicas == index:
                self._send_chosen_watermark(boundary)

    def _execute_log(self, replies: dict) -> None:
        """Execute the contiguous chosen prefix the log holds, and
        drop it from the log: nothing reads an executed entry."""
        log = self.log
        slot = self.executed_watermark
        if log.largest_key < slot:
            return  # nothing is parked
        rows = []
        while (row := log.get(slot)) is not None:
            rows.append(row)
            slot += 1
        self._execute_rows(rows, replies)
        log.garbage_collect(self.executed_watermark)

    def _execute_read(self, command: Command) -> ReadReply:
        result = self.state_machine.run(command.command)
        self.metrics_reads.inc()
        return ReadReply(command_id=command.command_id,
                         slot=self.executed_watermark - 1, result=result)

    def _send_read_replies(self, replies: list[ReadReply]) -> None:
        proxy = self._proxy_replica_address()
        if len(replies) <= 1:
            for reply in replies:
                self.send(reply.command_id.client_address, reply)
        elif proxy is not None:
            self.send(proxy, ReadReplyBatch(batch=tuple(replies)))
        else:
            # A batch is answered as a batch: one message a client
            # address (as _send_replies groups a run's write replies).
            by_client: dict = {}
            for reply in replies:
                by_client.setdefault(reply.command_id.client_address,
                                     []).append(reply)
            for address, batch in by_client.items():
                if len(batch) == 1:
                    self.send(address, batch[0])
                else:
                    self.send(address, ReadReplyBatch(batch=tuple(batch)))

    def _process_deferred_reads(self, parked: list) -> None:
        """Answer what ``_defer_read`` parked at a slot that has now
        executed. Stage ``read``, one scope a slot (inside ``execute``,
        which it subtracts from), and one ``read-park-wait``
        observation a parked read or batch: how long it sat (a read
        served at once observes 0, see ``_read_now``)."""
        metrics = self.transport.runtime_metrics
        if metrics is not None:
            now = metrics.clock()
            for parked_at, _ in parked:
                metrics.observe_stage("read-park-wait", now - parked_at)
        reads = [c for _, commands in parked for c in commands]
        self._deferred_read_count -= len(reads)
        if self.admission is not None:
            self.admission.set_inflight(self._deferred_read_count)
        with self.trace_stage("read"):
            self._send_read_replies([self._execute_read(c) for c in reads])

    def _admit_read(self, command: Command, sync: bool = True) -> bool:
        """paxload read admission: the in-flight measure is the
        deferred-read backlog; refusal answers the CLIENT (not the
        read batcher) with an explicit Rejected so its backoff engages
        instead of a resend storm. ``sync=False`` skips the backlog
        resync so batch callers can sync ONCE and let ``admit()``'s
        increments accumulate across the batch -- resyncing per
        command would erase them and the limit would never bind
        within one batch."""
        admission = self.admission
        if admission is None:
            return True
        if sync:
            admission.set_inflight(self._deferred_read_count)
        if admission.admit(1):
            return True
        from frankenpaxos_tpu.serve.messages import Rejected

        cid = command.command_id
        self.send(cid.client_address, Rejected(
            entries=((cid.client_pseudonym, cid.client_id),),
            retry_after_ms=admission.retry_after_ms(),
            reason=admission.last_reason))
        return False

    def _read_now(self, commands) -> None:
        """Execute reads whose slot has executed and answer each its
        client: stage ``read``, one scope a message. It waited for no
        slot, which ``read-park-wait`` counts as an observation of 0,
        so that the stage's mean is over all reads and batches, parked
        or not."""
        metrics = self.transport.runtime_metrics
        if metrics is not None:
            metrics.observe_stage("read-park-wait", 0.0)
        with self.trace_stage("read"):
            self._send_read_replies(
                [self._execute_read(c) for c in commands])

    def _defer_read(self, slot: int, commands) -> None:
        """Park one read, or one batch of reads, until ``slot`` has
        executed (``_execute_log`` takes them out again)."""
        metrics = self.transport.runtime_metrics
        entry = (metrics.clock() if metrics is not None else 0.0, commands)
        parked = self.deferred_reads.get(slot)
        if parked is None:
            self.deferred_reads.put(slot, [entry])
        else:
            parked.append(entry)
        self._deferred_read_count += len(commands)
        self.metrics_deferred_reads.inc(len(commands))

    # --- handlers ---------------------------------------------------------
    def receive(self, src: Address, message) -> None:
        # timed(label) handler latency summaries (Leader.scala:281-293).
        if self.options.measure_latencies:
            with self.receive_timer(self.metrics_latency, message):
                self._receive_impl(src, message)
        else:
            self._receive_impl(src, message)

    def _receive_impl(self, src: Address, message) -> None:
        if isinstance(message, Chosen):
            self._handle_chosen(src, message)
        elif isinstance(message, ChosenRun):
            self._handle_chosen_run(src, message)
        elif isinstance(message, ReadRequest):
            self._handle_read_request(src, message)
        elif isinstance(message, SequentialReadRequest):
            self._handle_sequential_read_request(src, message)
        elif isinstance(message, EventualReadRequest):
            self._handle_eventual_read_request(src, message)
        elif isinstance(message, ReadRequestBatch):
            self._handle_read_request_batch(src, message)
        elif isinstance(message, SequentialReadRequestBatch):
            self._handle_read_request_batch(src, ReadRequestBatch(
                slot=message.slot, commands=message.commands))
        elif isinstance(message, EventualReadRequestBatch):
            self._handle_eventual_read_batch(message)
        else:
            self.logger.fatal(f"unexpected replica message {message!r}")

    def _handle_eventual_read_batch(self, batch) -> None:
        """Batched eventual reads execute immediately (no defer), but
        still pass read admission: each refused command's client gets
        a Rejected, like the single-message path. Sync once per batch
        so the limit binds within it, then settle back to the
        deferred-read backlog."""
        self.metrics_read_messages.inc()
        admission = self.admission
        if admission is None:
            commands = batch.commands
        else:
            admission.set_inflight(self._deferred_read_count)
            commands = [c for c in batch.commands
                        if self._admit_read(c, sync=False)]
        try:
            if commands:
                with self.trace_stage("read"):
                    self._send_read_replies(
                        [self._execute_read(c) for c in commands])
        finally:
            if admission is not None:
                admission.set_inflight(self._deferred_read_count)

    def _handle_read_request_batch(self, src: Address,
                                   batch: ReadRequestBatch) -> None:
        """Batched deferrable reads (Replica.scala:478-530
        handleDeferrableReads)."""
        self.metrics_read_messages.inc()
        admission = self.admission
        if admission is None:
            # Admission-off fast path: no per-command filter call (the
            # disabled-path budget is one attribute load + is-None per
            # frame, see runtime/actor.py).
            commands = batch.commands
        else:
            admission.set_inflight(self._deferred_read_count)
            commands = [c for c in batch.commands
                        if self._admit_read(c, sync=False)]
        try:
            if not commands:
                return
            if batch.slot >= self.executed_watermark:
                self._defer_read(batch.slot, commands)
                return
            self._read_now(commands)
        finally:
            # Settle to the true backlog: deferred reads are in
            # _deferred_read_count; immediately-executed ones release.
            if admission is not None:
                admission.set_inflight(self._deferred_read_count)

    def _wal_log_chosen_run(self, start_slot: int, values) -> None:
        """Append a run to the WAL as it came: for the lazy value
        array a run arrives in, ONE raw copy. Whole, the slots it
        repeats among them (a resend, an overlap after a failover):
        replay skips what is executed or parked as the live pass
        does."""
        self.wal.append(WalChosenRun(start_slot=start_slot, stride=1,
                                     values=encode_value_array(values)))

    def _handle_chosen(self, src: Address, chosen: Chosen) -> None:
        """(Replica.scala:572-628). One command a message, so it opens
        no stage of its own: its time is the transport's ``handler``.
        Always through the log (recovery and the uncoalesced path)."""
        if self._park(chosen.slot, [value_row(chosen.value)]) == 0:
            return  # duplicate Chosen
        if self.wal is not None:
            self._wal_log_chosen_run(chosen.slot, (chosen.value,))
        replies: dict = {}
        self._execute_log(replies)
        self._note_log_entries()
        self._send_replies(replies, arrays=False)
        self._restart_recover_timer()

    def _handle_chosen_run(self, src: Address, run: ChosenRun) -> None:
        """A contiguous drain of chosen values in one message: take
        the whole run, execute once, and ship each client ONE reply
        array for the drain instead of one ClientReply per command.
        Stages ``log``, ``execute`` and ``reply``, one scope each a
        run."""
        replies: dict = {}
        if not self._take_run(run.start_slot, run.values, replies,
                              durable=self.wal is not None):
            return
        if replies:
            with self.trace_stage("reply"):
                self._send_replies(replies, arrays=True)
        self._restart_recover_timer()

    def _take_run(self, start_slot: int, values, replies: dict,
                  durable: bool) -> bool:
        """Execute what a run of chosen values lets execute; False for
        a run that held nothing new. Stage ``log``: the one walk over
        the encoded array (no value object is made), the WAL's raw
        copy, and whatever enters the log. Which way the rows go is
        decided by where the run lies: one that reaches the executed
        watermark, with nothing parked in the slots it brings, is
        executed from them at once ("direct"); one above a hole, or
        one that meets parked entries, is parked a slot each
        ("logged"). Stage ``execute``: the pass, and then whatever
        the log holds in order, which leaves the log. Shared by the
        live handler and WAL replay (``durable=False``: the records
        are in the WAL already)."""
        watermark = self.executed_watermark
        end = start_slot + len(values)
        if end <= watermark:
            return False  # a resend of what has been executed
        with self.trace_stage("log"):
            if not isinstance(values, LazyValueArray):
                values = decode_value_array(encode_value_array(values))
            # All of it before any of it is executed: a corrupt array
            # raises here, and nothing has changed.
            rows = list(values.rows())
            direct = (start_slot <= watermark
                      and not self._parked_in(watermark, end))
            if direct:
                del rows[:watermark - start_slot]
                self.num_chosen += end - watermark
            elif self._park(start_slot, rows) == 0:
                return False
            if durable:
                self._wal_log_chosen_run(start_slot, values)
        (self.metrics_runs_direct if direct
         else self.metrics_runs_logged).inc()
        with self.trace_stage("execute"):
            if direct:
                self._execute_rows(rows, replies)
            # What was parked above a hole that is now filled, if any.
            self._execute_log(replies)
        self._note_log_entries()
        return True

    def _parked_in(self, start: int, end: int) -> bool:
        """Whether the log holds an entry in ``[start, end)``; one
        comparison while nothing is parked at all."""
        log = self.log
        return log.largest_key >= start and any(
            log.get(slot) is not None
            for slot in range(start, min(end, log.largest_key + 1)))

    def _note_log_entries(self) -> None:
        # Chosen and not executed is what the log holds, since an
        # executed entry leaves it: reckoned, because a count of the
        # log a run would cost the more the longer a hole stays open.
        self.metrics_log_entries.set(
            self.num_chosen - self.executed_watermark)

    def _send_replies(self, replies: dict, arrays: bool) -> None:
        """Send what ``_execute_rows`` gathered: to a proxy replica as
        one ``ClientReplyBatch``, else to each client address its
        entries, as one ``ClientReplyArray`` (a run) or a
        ``ClientReply`` each (a ``Chosen``)."""
        if not replies:
            return
        proxy = self._proxy_replica_address()
        if proxy is None and arrays:
            for address, entries in replies.items():
                self._wal_send(address,
                               ClientReplyArray(entries=tuple(entries)))
            return
        batch = [ClientReply(CommandId(address, pseudonym, client_id),
                             slot, result)
                 for address, entries in replies.items()
                 for pseudonym, client_id, slot, result in entries]
        if proxy is not None:
            self._wal_send(proxy, ClientReplyBatch(batch=tuple(batch)))
        else:
            for reply in batch:
                self._wal_send(reply.command_id.client_address, reply)

    def _restart_recover_timer(self) -> None:
        # Recover timer runs only while there are unexecuted chosen slots
        # above a hole.
        if self.recover_timer is not None:
            if self.executed_watermark < self.num_chosen:
                self.recover_timer.start()
            else:
                self.recover_timer.stop()

    def _handle_read_request(self, src: Address,
                             request: ReadRequest) -> None:
        """Linearizable read at a slot; defer until executed
        (Replica.scala:455-530)."""
        self.metrics_read_messages.inc()
        if not self._admit_read(request.command):
            return
        if request.slot >= self.executed_watermark:
            self._defer_read(request.slot, (request.command,))
            return
        self._read_now((request.command,))

    def _handle_sequential_read_request(self, src: Address,
                                        request: SequentialReadRequest
                                        ) -> None:
        # Sequential consistency: wait until we've executed past the
        # client's last seen slot (Client.scala:697+).
        self._handle_read_request(src, ReadRequest(slot=request.slot,
                                                   command=request.command))

    def _handle_eventual_read_request(self, src: Address,
                                      request: EventualReadRequest) -> None:
        self.metrics_read_messages.inc()
        if not self._admit_read(request.command):
            return
        with self.trace_stage("read"):
            self.send(src, self._execute_read(request.command))

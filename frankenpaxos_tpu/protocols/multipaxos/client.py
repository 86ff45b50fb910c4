"""MultiPaxos Client.

Reference behavior: multipaxos/Client.scala:120-1060. Per-pseudonym
pending-operation state machines with resend timers:

  * writes (writeImpl, Client.scala:563-603): ClientRequest to a random
    batcher (or the round's leader when there are no batchers); NotLeader
    bounces trigger LeaderInfoRequest round discovery.
  * linearizable reads (readImpl + handleMaxSlotReply,
    Client.scala:604-700, 851-933): a max-slot question to f+1 of a
    random acceptor group (or a grid read quorum); on quorum, read at
    ``max_slot + num_groups - 1`` (grid: ``max_slot``) at a random
    replica, deferred there until executed. Without read batchers the
    reads ONE event-loop pass issues share one quorum round and travel
    as one batch (below).
  * sequential reads (Client.scala:697+): read at the largest slot this
    pseudonym has seen.
  * eventual reads (Client.scala:739+): straight to a random replica.

A pass's linearizable reads are one batch (runs/client.py stages them
as it stages coalesced writes): ONE BatchMaxSlotRequest to the quorum,
on its answers ONE ReadRequestBatch to one replica, which answers with
ONE ReadReplyBatch; one resend timer a batch and phase, the retry
budget still charged read by read. A pass with one read is a batch of
one. The guarantee does not move: a read is staged when issued and its
batch's BatchMaxSlotRequest leaves at the end of that pass, so every
acceptor of the quorum is asked AFTER the read was issued. A write
acknowledged before the read was issued had f+1 votes by then; any f+1
acceptors of the group (any row of the grid) intersect them, so the
batch's slot is at or above that write's, and the replica answers only
once that slot has executed. Still f+1 acceptors a read, still one
replica that has executed the slot. A batch is closed when its request
is sent: a max-slot answer is never used for a read issued later.

A pass's coalesced writes (``coalesce_writes``) leave as one
ClientRequestArray and share its resend timer: one timer a pass where
there was one a write, armed as the array is sent, stopped once every
write of it has been answered. When it fires, the writes still pending
are each charged their own retry and re-sent as one array. A write
sent alone keeps its pseudonym's own timer. Where the config deploys
batchers the array goes to one of them, the same for all of this
client's arrays (the ring key ``(client, -1)``), which re-buckets its
commands into batches of a slot each (batcher.py).
"""

from __future__ import annotations

import dataclasses
import random
from typing import Callable, Optional

from frankenpaxos_tpu.obs.trace import TraceContext
from frankenpaxos_tpu.protocols.multipaxos.config import MultiPaxosConfig
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    BatchMaxSlotReply,
    BatchMaxSlotRequest,
    ClientReply,
    ClientReplyArray,
    ClientRequest,
    ClientRequestArray,
    Command,
    CommandId,
    EventualReadRequest,
    LeaderInfoReplyClient,
    LeaderInfoRequestClient,
    NotLeaderClient,
    ReadReply,
    ReadReplyBatch,
    ReadRequest,
    ReadRequestBatch,
    SequentialReadRequest,
)
from frankenpaxos_tpu.roundsystem import ClassicRoundRobin
from frankenpaxos_tpu.runs.client import RetryAdmissionMixin, StagedWriteMixin
from frankenpaxos_tpu.runs.routing import (
    make_fan_router,
    pick_array_destination,
    pick_request_destination,
)
from frankenpaxos_tpu.runtime import Actor, Logger
from frankenpaxos_tpu.runtime.transport import Address, Transport
from frankenpaxos_tpu.serve.backoff import Backoff
from frankenpaxos_tpu.serve.messages import Rejected

Callback = Callable[[bytes], None]


@dataclasses.dataclass(frozen=True)
class ClientOptions:
    resend_client_request_period_s: float = 10.0
    resend_max_slot_requests_period_s: float = 10.0
    resend_read_request_period_s: float = 10.0
    # Performance-debugging unsafe modes (Client.scala:42-53).
    unsafe_read_at_first_slot: bool = False
    unsafe_read_at_i: bool = False
    flush_writes_every_n: int = 1
    flush_reads_every_n: int = 1
    # Coalesce this event-loop pass's writes into ONE ClientRequestArray
    # (each command gets its own slot at the leader -- see
    # messages.ClientRequestArray -- or, where the config deploys
    # batchers, a place in the batcher's batches). Flushed by on_drain /
    # flush_writes; the array's writes share one resend timer
    # (_WriteBatch).
    coalesce_writes: bool = False
    # paxload retry discipline (serve/backoff.py, docs/SERVING.md).
    # retry_budget = 0 keeps the pre-paxload behavior: unlimited
    # resends, Rejected treated as an immediate-backoff retry with no
    # cap. With a budget, EVERY retry (Rejected backoff or timeout
    # failover) consumes it, and exhaustion completes the operation
    # with serve.RETRY_EXHAUSTED -- no request ever wedges silently.
    retry_budget: int = 0
    backoff: Backoff = Backoff()


@dataclasses.dataclass
class _PendingWrite:
    id: int
    command: bytes
    callback: Callback
    resend: object
    attempts: int = 0
    backoff_pending: bool = False
    # The batch a coalesced write travels in: set when its array
    # leaves, None again once a Rejected write is re-staged
    # (``_reissue``), so that a later batch carries it.
    batch: object = None


class _BatchTimed:
    """The ``resend`` of a read or a coalesced write that travels in a
    batch: it has no timer of its own, its batch's timer re-sends it
    while it is pending (``Client._live_reads``,
    ``Client._resend_write_batch``)."""

    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass


_BATCH_TIMED = _BatchTimed()


@dataclasses.dataclass
class _PendingRead:
    id: int
    command: bytes
    callback: Callback
    resend: object
    attempts: int = 0
    backoff_pending: bool = False
    # The in-flight read request + target replica, kept so a Rejected
    # read can be re-issued after backoff without re-deriving the slot.
    request: object = None
    replica: object = None
    # The batch a linearizable read travels in (no read batchers): set
    # when the batch's BatchMaxSlotRequest leaves, None again once the
    # read goes on alone (``_reissue``). While the batch has no slot
    # yet the only outstanding requests are to acceptors, which carry
    # no admission controller and never draw a Rejected.
    batch: object = None


@dataclasses.dataclass
class _ReadBatch:
    """The linearizable reads one pass issued: one quorum round, then
    one read request. ``reads`` holds (pseudonym, state, Command) of
    the reads last sent; ``replica`` is None until the quorum
    answered."""
    id: int
    reads: list
    request: BatchMaxSlotRequest
    resend_to: list
    timer: object = None
    replies: dict = dataclasses.field(default_factory=dict)
    slot: int = -1
    replica: object = None
    unanswered: int = 0


@dataclasses.dataclass
class _WriteBatch:
    """The coalesced writes one ClientRequestArray carried, and their
    one resend timer. ``writes`` holds (pseudonym, state, Command) of
    the writes it covers; ``unanswered`` counts those of them not yet
    answered nor re-staged."""
    id: int
    writes: list
    unanswered: int = 0
    timer: object = None


class Client(RetryAdmissionMixin, StagedWriteMixin, Actor):
    #: The commit path (obs/trace.py): one write in PATH_EVERY of a
    #: client is sampled, unless a sampled write of it is outstanding.
    #: The gaps are drawn from 1 .. 2 * PATH_EVERY - 1: a fixed stride
    #: would put every sample at the same slot modulo the stride, so
    #: that one replica of two (``slot % replicas`` answers) would
    #: answer them all.
    PATH_EVERY = 64

    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MultiPaxosConfig,
                 options: ClientOptions = ClientOptions(), seed: int = 0):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        self.options = options
        self.rng = random.Random(seed)
        # The commit path: writes until the next sample, the last
        # sampled one's (pseudonym, state), and the path of a sampled
        # write staged for the end-of-pass array. Its own generator,
        # so that sampling moves no other random choice.
        self._path_rng = random.Random(seed)
        self._path_gap = self._path_rng.randrange(1, 2 * self.PATH_EVERY)
        self._path_write: tuple = (None, None)
        self._path_staged: Optional[TraceContext] = None
        self.round_system = ClassicRoundRobin(config.num_leaders)
        self.grid = config.quorum_grid() if config.flexible else None
        self._row_size = len(config.acceptor_addresses[0])
        self.round = 0
        self.ids: dict[int, int] = {}               # pseudonym -> next id
        self.states: dict[int, object] = {}         # pseudonym -> pending op
        self.largest_seen_slots: dict[int, int] = {}  # pseudonym -> slot
        # runs/ retry discipline + coalesce_writes staging.
        self._retry_budget = options.retry_budget
        self._retry_backoff = options.backoff
        self._init_staging()
        # paxfan: consistent ring over the ingest-batcher tier, or
        # the plain batchers that take this client's arrays -- a
        # session key (this client, pseudonym) pins to one shard; a
        # resend timeout suspects THAT shard (its keys fail over to
        # the clockwise survivors, everyone else stays pinned); a
        # Rejected floors backoff against the shedding shard only.
        self._fan = make_fan_router(
            config,
            revive_after_s=options.resend_client_request_period_s,
            batchers=True)
        # One reusable resend timer per pseudonym for writes sent alone
        # (vs a fresh Timer per write): timer construction was a
        # measurable per-command cost at drain widths in the thousands.
        self._write_timers: dict[int, object] = {}
        # Coalesced writes share their array's timer instead. How often
        # that engages: batches armed, the writes they covered, and
        # batch resends fired.
        self._next_write_batch_id = 0
        self.write_batches_armed = 0
        self.write_batch_writes = 0
        self.write_batch_resends = 0
        # Read batches waiting for their max-slot quorum, by the batch
        # id this client counts up (it rides BatchMaxSlotRequest's
        # read_batcher_id); a batch leaves as its ReadRequestBatch does.
        self._read_batches: dict[int, _ReadBatch] = {}
        self._next_read_batch_id = 0

    # --- public API -------------------------------------------------------
    def write(self, pseudonym: int, command: bytes,
              callback: Optional[Callback] = None) -> None:
        self._check_idle(pseudonym)
        callback = callback or (lambda _: None)
        id = self.ids.get(pseudonym, 0)
        self._path_gap -= 1
        path = self._sample_path(pseudonym, id) if not self._path_gap \
            else None
        entry = Command(CommandId(self.address, pseudonym, id), command)
        if self.options.coalesce_writes:
            # Stage for the end-of-pass array flush (runs/client.py:
            # a burst of call_soon'd closed loops, or reissues inside
            # a delivery drain, coalesce into one array), whose timer
            # re-sends it.
            state = _PendingWrite(id, command, callback, _BATCH_TIMED)
            self._stage_write((pseudonym, state, entry))
            if path is not None:
                self._path_staged = path
        else:
            self._send_client_request(ClientRequest(entry), path)
            timer = self._write_resend_timer(pseudonym)
            timer.start()
            state = _PendingWrite(id, command, callback, timer)
        self.states[pseudonym] = state
        if path is not None:
            self._path_write = (pseudonym, state)
        self.ids[pseudonym] = id + 1

    def _sample_path(self, pseudonym: int,
                     id: int) -> Optional[TraceContext]:
        """The commit path of the write being issued, its root instant
        now; None while the last sampled write is outstanding."""
        self._path_gap = self._path_rng.randrange(1, 2 * self.PATH_EVERY)
        last, state = self._path_write
        if state is not None and self.states.get(last) is state:
            return None
        return TraceContext(
            trace_id=self._path_rng.getrandbits(64), span_id=0,
            sampled=True, root_s=self.transport.path_clock(),
            kind="client", pseudonym=pseudonym, client_id=id)

    def _write_resend_timer(self, pseudonym: int):
        timer = self._write_timers.get(pseudonym)
        if timer is None:
            def resend():
                # Reads the CURRENT pending write (the timer outlives
                # individual operations). A timeout is the FAILOVER
                # signal (the leader may be gone) -- re-send on the
                # normal discovery path; with a retry budget set, the
                # failover consumes it like any other retry.
                state = self.states.get(pseudonym)
                if isinstance(state, _PendingWrite):
                    if not self._consume_retry(pseudonym, state,
                                               "failover"):
                        return
                    if self._fan is not None:
                        # paxfan: the timeout suspects THIS key's
                        # shard, so the resend below routes past it
                        # while every other key stays pinned.
                        self._fan.suspect_key(self.address, pseudonym)
                    self._send_client_request(ClientRequest(Command(
                        CommandId(self.address, pseudonym, state.id),
                        state.command)))
                    timer.start()

            timer = self.timer(
                f"resendWrite{pseudonym}",
                self.options.resend_client_request_period_s, resend)
            self._write_timers[pseudonym] = timer
        return timer

    def read(self, pseudonym: int, command: bytes,
             callback: Optional[Callback] = None) -> None:
        """Linearizable quorum read."""
        self._check_idle(pseudonym)
        callback = callback or (lambda _: None)
        id = self.ids.get(pseudonym, 0)
        if self.config.num_read_batchers > 0:
            # Let a read batcher amortize the quorum round
            # (Client.scala:665-690).
            read_request = ReadRequest(
                slot=-1,
                command=Command(CommandId(self.address, pseudonym, id),
                                command))
            batcher = self.config.read_batcher_addresses[
                self.rng.randrange(self.config.num_read_batchers)]
            self.send(batcher, read_request)

            def resend_batched():
                state = self.states.get(pseudonym)
                if not isinstance(state, _PendingRead) \
                        or not self._consume_retry(pseudonym, state,
                                                   "failover"):
                    return
                self.send(batcher, read_request)
                timer.start()

            timer = self.timer(
                f"resendRead{pseudonym}",
                self.options.resend_read_request_period_s, resend_batched)
            timer.start()
            self.states[pseudonym] = _PendingRead(id, command, callback,
                                                  timer,
                                                  request=read_request,
                                                  replica=batcher)
            self.ids[pseudonym] = id + 1
            return
        # Stage for the end-of-pass flush (runs/client.py): the reads
        # of one pass share one quorum round, asked after all of them
        # were issued.
        state = _PendingRead(id, command, callback, _BATCH_TIMED)
        self.states[pseudonym] = state
        self.ids[pseudonym] = id + 1
        self._stage_read((pseudonym, state, Command(
            CommandId(self.address, pseudonym, id), command)))

    def sequential_read(self, pseudonym: int, command: bytes,
                        callback: Optional[Callback] = None) -> None:
        self._check_idle(pseudonym)
        callback = callback or (lambda _: None)
        id = self.ids.get(pseudonym, 0)
        slot = self.largest_seen_slots.get(pseudonym, -1)
        request = SequentialReadRequest(
            slot=slot,
            command=Command(CommandId(self.address, pseudonym, id), command))
        replica = self._random_replica()
        self.send(replica, request)
        timer = self._make_read_resend_timer(pseudonym, replica, request)
        self.states[pseudonym] = _PendingRead(id, command, callback, timer,
                                              request=request,
                                              replica=replica)
        self.ids[pseudonym] = id + 1

    def eventual_read(self, pseudonym: int, command: bytes,
                      callback: Optional[Callback] = None) -> None:
        self._check_idle(pseudonym)
        callback = callback or (lambda _: None)
        id = self.ids.get(pseudonym, 0)
        request = EventualReadRequest(
            Command(CommandId(self.address, pseudonym, id), command))
        replica = self._random_replica()
        self.send(replica, request)
        timer = self._make_read_resend_timer(pseudonym, replica, request)
        self.states[pseudonym] = _PendingRead(id, command, callback, timer,
                                              request=request,
                                              replica=replica)
        self.ids[pseudonym] = id + 1

    # --- helpers ----------------------------------------------------------
    def _check_idle(self, pseudonym: int) -> None:
        if pseudonym in self.states:
            raise RuntimeError(
                f"pseudonym {pseudonym} already has a pending operation; a "
                f"client can have one pending operation per pseudonym")

    def _acceptor_address(self, flat: int) -> Address:
        return self.config.acceptor_addresses[flat // self._row_size][
            flat % self._row_size]

    def _random_replica(self) -> Address:
        return self.config.replica_addresses[
            self.rng.randrange(self.config.num_replicas)]

    def _round_leader(self) -> Address:
        return self.config.leader_addresses[
            self.round_system.leader(self.round)]

    def _send_client_request(self, request: ClientRequest,
                             path: Optional[TraceContext] = None) -> None:
        # runs/routing ladder: ingest disseminators absorb the fan-in
        # (ring-pinned per session -- a dead batcher costs a retry
        # plus a failover to its clockwise survivor, not a wedge) >
        # batchers > the round's leader.
        dst = pick_request_destination(
            self.config, self.rng, self._round_leader, fan=self._fan,
            key=(self.address, request.command.command_id.client_pseudonym))
        if path is not None:
            self.transport.path_out = path
        self.send(dst, request)

    def _flush_staged(self, staged: list) -> None:
        """Ship writes staged by ``coalesce_writes`` as one array (to
        an ingest disseminator or a batcher when the config deploys
        them, else straight to the round's leader). The array spans
        many of this client's pseudonyms, so it rides the client-scoped
        ring key (pseudonym -1). Its writes' resend timer starts as it
        leaves."""
        dst = pick_array_destination(self.config, self.rng,
                                     self._round_leader, fan=self._fan,
                                     key=(self.address, -1))
        if self._path_staged is not None:
            self.transport.path_out, self._path_staged = \
                self._path_staged, None
        self.send(dst, ClientRequestArray(
            commands=tuple(entry for _, _, entry in staged)))
        self._open_write_batch(staged)

    def _open_write_batch(self, staged: list) -> None:
        """One resend timer for the writes of the array just sent that
        are still pending (one answered or given up while it was staged
        is left out)."""
        batch = _WriteBatch(self._next_write_batch_id, [])
        states = self.states
        writes = batch.writes
        for write in staged:
            state = write[1]
            if states.get(write[0]) is state:
                state.batch = batch
                writes.append(write)
        if not writes:
            return
        self._next_write_batch_id += 1
        batch.unanswered = len(writes)
        self.write_batches_armed += 1
        self.write_batch_writes += len(writes)
        batch.timer = self.timer(
            f"resendWriteBatch{batch.id}",
            self.options.resend_client_request_period_s,
            lambda: self._resend_write_batch(batch))
        batch.timer.start()

    def _close_write_batch(self, batch: _WriteBatch) -> None:
        """Nothing of ``batch`` is left to re-send: stop its timer and
        let go of its writes (each points back at it)."""
        if batch.timer is not None:
            batch.timer.stop()
            batch.timer = None
        batch.writes = ()

    def _write_left_batch(self, state: _PendingWrite) -> None:
        """``state`` was answered or re-staged: its batch covers one
        write fewer, and is closed when it covers none."""
        batch = state.batch
        batch.unanswered -= 1
        if batch.unanswered <= 0:
            self._close_write_batch(batch)

    def _resend_write_batch(self, batch: _WriteBatch) -> None:
        """A write batch's resend timer. A timeout is the FAILOVER
        signal (the leader may be gone): each write still pending and
        not backing off is charged its own retry, one that exhausts its
        budget completes with RETRY_EXHAUSTED and leaves the batch, and
        the rest are re-sent as one array on the normal discovery
        path. A write backing off after a Rejected stays in the batch
        unsent until its reissue re-stages it."""
        states = self.states
        kept: list = []
        entries: list = []
        for write in batch.writes:
            pseudonym, state, entry = write
            if states.get(pseudonym) is not state \
                    or state.batch is not batch:
                continue
            if state.backoff_pending:
                kept.append(write)
            elif self._consume_retry(pseudonym, state, "failover"):
                kept.append(write)
                entries.append(entry)
        batch.writes = kept
        batch.unanswered = len(kept)
        if not kept:
            self._close_write_batch(batch)
            return
        if entries:
            self.write_batch_resends += 1
            if self._fan is not None:
                # paxfan: the timeout suspects the shard the array's
                # key routes to, so the resend routes past it.
                self._fan.suspect_key(self.address, -1)
            dst = pick_array_destination(self.config, self.rng,
                                         self._round_leader, fan=self._fan,
                                         key=(self.address, -1))
            self.send(dst, ClientRequestArray(commands=tuple(entries)))
        batch.timer.start()

    def _flush_staged_reads(self, staged: list) -> None:
        """Open the quorum round of the reads this pass issued: ONE
        BatchMaxSlotRequest to f+1 acceptors of a random group (a
        random read quorum of the grid). The batch is closed here; a
        read issued from now on waits for the next round."""
        batch_id = self._next_read_batch_id
        self._next_read_batch_id += 1
        if not self.config.flexible:
            group_index = self.rng.randrange(self.config.num_acceptor_groups)
            group = list(self.config.acceptor_addresses[group_index])
            quorum = self.rng.sample(group, self.config.f + 1)
            resend_to = group
        else:
            quorum = [self._acceptor_address(flat)
                      for flat in self.grid.random_read_quorum(self.rng)]
            resend_to = [a for g in self.config.acceptor_addresses
                         for a in g]
        batch = _ReadBatch(
            batch_id, staged,
            BatchMaxSlotRequest(read_batcher_index=-1,
                                read_batcher_id=batch_id),
            resend_to)
        for _, state, _ in staged:
            state.batch = batch
        self._read_batches[batch_id] = batch
        for acceptor in quorum:
            self.send(acceptor, batch.request)
        batch.timer = self.timer(
            f"resendMaxSlotBatch{batch_id}",
            self.options.resend_max_slot_requests_period_s,
            lambda: self._resend_read_batch(batch))
        batch.timer.start()

    def _live_reads(self, batch: _ReadBatch) -> list:
        """The reads of ``batch`` that are still its to re-send:
        pending, not answered, given up, backing off or gone on
        alone."""
        states = self.states
        return [read for read in batch.reads
                if states.get(read[0]) is read[1]
                and read[1].batch is batch
                and not read[1].backoff_pending]

    def _close_read_batch(self, batch: _ReadBatch) -> None:
        """Nothing of ``batch`` is left to re-send: let go of its timer
        and its reads (each points back at it), so that nothing waits
        for the cycle collector."""
        if batch.timer is not None:
            batch.timer.stop()
            batch.timer = None
        batch.reads = ()

    def _send_read_request_batch(self, batch: _ReadBatch) -> None:
        batch.unanswered = len(batch.reads)
        self.send(batch.replica, ReadRequestBatch(
            slot=batch.slot,
            commands=tuple(command for _, _, command in batch.reads)))

    def _resend_read_batch(self, batch: _ReadBatch) -> None:
        """A batch's resend timer, either phase. A timeout charges each
        read still pending its own retry; one that exhausts its budget
        completes with RETRY_EXHAUSTED and leaves the batch."""
        batch.reads = [
            read for read in self._live_reads(batch)
            if self._consume_retry(read[0], read[1], "failover")]
        if not batch.reads:
            self._read_batches.pop(batch.id, None)
            self._close_read_batch(batch)
            return
        if batch.replica is None:
            for acceptor in batch.resend_to:
                self.send(acceptor, batch.request)
        else:
            self._send_read_request_batch(batch)
        batch.timer.start()

    def _note_shed_source(self, src: Address, rejected) -> float:
        """Attribute a Rejected to its ingest shard: floor reissue
        backoff against THAT shard only (runs/client.py hook)."""
        if self._fan is None:
            return 0.0
        from frankenpaxos_tpu.ingest.fan import shard_of_address

        shard = shard_of_address(self.config, src)
        if shard < 0:
            return 0.0
        self._fan.note_shed(shard, rejected.retry_after_ms)
        return self._fan.floor_delay_s(shard)

    def _make_read_resend_timer(self, pseudonym: int, replica: Address,
                                request) -> object:
        def resend():
            state = self.states.get(pseudonym)
            if not isinstance(state, _PendingRead) \
                    or not self._consume_retry(pseudonym, state,
                                               "failover"):
                return
            self.send(replica, request)
            timer.start()

        timer = self.timer(f"resendRead{pseudonym}",
                           self.options.resend_read_request_period_s, resend)
        timer.start()
        return timer

    # --- handlers ---------------------------------------------------------
    def receive(self, src: Address, message) -> None:
        # Reads a handler's callbacks issue wait for on_drain
        # (runs/client.py: one batch a pass).
        self._in_pass = True
        if isinstance(message, ClientReply):
            self._handle_client_reply(src, message)
        elif isinstance(message, ClientReplyArray):
            self._handle_client_reply_array(src, message)
        elif isinstance(message, ReadReplyBatch):
            for reply in message.batch:
                self._handle_read_reply(src, reply)
        elif isinstance(message, BatchMaxSlotReply):
            self._handle_batch_max_slot_reply(src, message)
        elif isinstance(message, ReadReply):
            self._handle_read_reply(src, message)
        elif isinstance(message, NotLeaderClient):
            self._handle_not_leader(src, message)
        elif isinstance(message, LeaderInfoReplyClient):
            self._handle_leader_info(src, message)
        elif isinstance(message, Rejected):
            self._handle_rejected(src, message)
        else:
            self.logger.fatal(f"unexpected client message {message!r}")

    # --- paxload retry discipline (runs/client.py, docs/SERVING.md) -------
    # Rejected handling + backoff/reissue scheduling live in
    # RetryAdmissionMixin; only the operation re-send is ours.
    def _reissue(self, pseudonym: int, state) -> None:
        if isinstance(state, _PendingWrite):
            entry = Command(CommandId(self.address, pseudonym, state.id),
                            state.command)
            if self.options.coalesce_writes:
                # Re-enter through the STAGED path: a burst of backoff
                # expiries coalesces back into one ClientRequestArray
                # instead of a retry storm of singles (the storm would
                # re-congest the very leader that just shed us). The
                # write leaves its old batch for the next one.
                if state.batch is not None:
                    self._write_left_batch(state)
                    state.batch = None
                self._stage_write((pseudonym, state, entry))
            else:
                self._send_client_request(ClientRequest(entry))
        elif isinstance(state, _PendingRead):
            batch = state.batch
            if batch is not None and batch.replica is not None:
                # A read its batch's replica refused goes on alone, at
                # the batch's slot and replica, on a timer of its own.
                state.batch = None
                state.replica = batch.replica
                state.request = ReadRequest(
                    slot=batch.slot,
                    command=Command(
                        CommandId(self.address, pseudonym, state.id),
                        state.command))
                state.resend = self._make_read_resend_timer(
                    pseudonym, state.replica, state.request)
            if state.request is not None:
                self.send(state.replica, state.request)

    def _handle_client_reply(self, src: Address, reply: ClientReply) -> None:
        pseudonym = reply.command_id.client_pseudonym
        state = self.states.get(pseudonym)
        if not isinstance(state, _PendingWrite) \
                or reply.command_id.client_id != state.id:
            self.logger.debug(f"stale ClientReply {reply}")
            return
        if state.batch is None:
            state.resend.stop()
        else:
            self._write_left_batch(state)
        self.largest_seen_slots[pseudonym] = max(
            self.largest_seen_slots.get(pseudonym, -1), reply.slot)
        del self.states[pseudonym]
        state.callback(reply.result)

    def _handle_client_reply_array(self, src: Address,
                                   array: ClientReplyArray) -> None:
        """A replica's whole drain of replies to this client in one
        message; per-entry resolution mirrors _handle_client_reply."""
        for pseudonym, client_id, slot, result in array.entries:
            state = self.states.get(pseudonym)
            if not isinstance(state, _PendingWrite) \
                    or client_id != state.id:
                self.logger.debug(
                    f"stale reply-array entry for pseudonym {pseudonym}")
                continue
            if state.batch is None:
                state.resend.stop()
            else:
                self._write_left_batch(state)
            self.largest_seen_slots[pseudonym] = max(
                self.largest_seen_slots.get(pseudonym, -1), slot)
            del self.states[pseudonym]
            state.callback(result)

    def _handle_batch_max_slot_reply(self, src: Address,
                                     reply: BatchMaxSlotReply) -> None:
        batch = self._read_batches.get(reply.read_batcher_id)
        if batch is None:
            self.logger.debug(f"stale BatchMaxSlotReply {reply}")
            return
        replies = batch.replies
        replies[(reply.group_index, reply.acceptor_index)] = reply.slot
        if not self.config.flexible:
            if len(replies) < self.config.f + 1:
                return
        else:
            flat = {g * self._row_size + i for g, i in replies}
            if not self.grid.is_superset_of_read_quorum(flat):
                return

        del self._read_batches[batch.id]
        batch.timer.stop()
        batch.reads = self._live_reads(batch)
        if not batch.reads:
            self._close_read_batch(batch)
            return
        max_slot = max(replies.values())
        if self.options.unsafe_read_at_first_slot:
            batch.slot = 0
        elif self.config.flexible or self.options.unsafe_read_at_i:
            batch.slot = max_slot
        else:
            # Slots round-robin over groups; the true global max voted slot
            # can exceed this group's by at most num_groups - 1.
            batch.slot = max_slot + self.config.num_acceptor_groups - 1
        batch.replica = self._random_replica()
        self._send_read_request_batch(batch)
        batch.timer = self.timer(
            f"resendReadBatch{batch.id}",
            self.options.resend_read_request_period_s,
            lambda: self._resend_read_batch(batch))
        batch.timer.start()

    def _handle_read_reply(self, src: Address, reply: ReadReply) -> None:
        pseudonym = reply.command_id.client_pseudonym
        state = self.states.get(pseudonym)
        if not isinstance(state, _PendingRead) \
                or reply.command_id.client_id != state.id:
            self.logger.debug(f"stale ReadReply {reply}")
            return
        state.resend.stop()
        self.largest_seen_slots[pseudonym] = max(
            self.largest_seen_slots.get(pseudonym, -1), reply.slot)
        del self.states[pseudonym]
        batch = state.batch
        if batch is not None:
            # The count says when to look; the look decides (a read
            # that left the batch another way was counted too).
            batch.unanswered -= 1
            if batch.unanswered <= 0 and not self._live_reads(batch):
                self._close_read_batch(batch)
        state.callback(reply.result)

    def _handle_not_leader(self, src: Address, _: NotLeaderClient) -> None:
        for leader in self.config.leader_addresses:
            self.send(leader, LeaderInfoRequestClient())

    def _handle_leader_info(self, src: Address,
                            reply: LeaderInfoReplyClient) -> None:
        if reply.round <= self.round:
            return
        self.round = reply.round
        # Re-send every pending write to the new round's leader
        # (Client.scala handleLeaderInfoReplyClient).
        for pseudonym, state in self.states.items():
            if isinstance(state, _PendingWrite):
                self._send_client_request(ClientRequest(Command(
                    CommandId(self.address, pseudonym, state.id),
                    state.command)))

"""MultiPaxos Batcher: client writes re-bucketed into batches, a slot
each, for the leader.

Reference behavior: multipaxos/Batcher.scala:67-190. Client requests
append to a batch of at most ``batch_size`` commands, and a batch takes
ONE log slot. Here a batcher also takes a client's coalesced
``ClientRequestArray`` (the writes of one of the client's loop passes;
the client's ring key pins all its arrays to one batcher), and a batch
does not wait to fill: everything staged leaves at the end of the
batcher's loop pass (``on_drain``), cut into batches of at most
``batch_size`` in arrival order, and a pass's batches go to the round's
leader in ONE ``ClientRequestBatchRun``, which the leader proposes as
one run of slots. The flush timer is a safety net for staging that
outlives a pass: armed when staging starts while it is not, so at most
once a period, never once a batch.

On the deployed transport an array lands through the wire sink as
columns over its wire bytes (ingest/columns.py), and a pass's batches
are cut from those bytes with a few numpy operations (``batch_run``):
no command is decoded or re-encoded. A decoded request (a sim, an
unsupported frame shape) is encoded once into the same form.

A NotLeaderBatcher bounce stashes the batch and asks every leader who
leads (LeaderInfoRequestBatcher); the reply updates the round and
re-sends the stashed batches, each alone as a ClientRequestBatch.

Counters: ``multipaxos_batcher_batched_commands_total`` (commands sent
in batches) and ``multipaxos_batcher_batches_sent_total{reason}``
(``full``: a batch of ``batch_size``; ``pass``: the partial batch that
ends a pass; ``timer``: the partial batch the safety net sent). Stages:
``batch`` (staging an array and cutting a pass's batches) and ``flush``
(the message encoded and sent).
"""

from __future__ import annotations

import dataclasses
import struct

import numpy as np

from frankenpaxos_tpu import native
from frankenpaxos_tpu.ingest.columns import (
    CLIENT_ARRAY_TAG,
    COL_ADDR,
    COL_ID,
    COL_LEN,
    COL_PSEUDONYM,
    parse_client_array,
    parse_client_batch,
)
from frankenpaxos_tpu.protocols.multipaxos.config import MultiPaxosConfig
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    ClientRequest,
    ClientRequestArray,
    ClientRequestBatchRun,
    CommandBatch,
    LeaderInfoReplyBatcher,
    LeaderInfoRequestBatcher,
    NotLeaderBatcher,
)
from frankenpaxos_tpu.protocols.multipaxos.wire import (
    encode_value_array,
    LazyValueArray,
)
from frankenpaxos_tpu.roundsystem import ClassicRoundRobin
from frankenpaxos_tpu.runtime import Actor, Collectors, FakeCollectors, Logger
from frankenpaxos_tpu.runtime.paxwire import CLIENT_BATCH_TAG
from frankenpaxos_tpu.runtime.transport import Address, Transport

_I32 = struct.Struct("<i")
#: A one-command value's bytes before its payload: the value's kind and
#: command count (5), then the command's address index, pseudonym, id
#: and payload length (24).
_HEAD = 5
_ENTRY = _HEAD + 24


@dataclasses.dataclass(frozen=True)
class BatcherOptions:
    batch_size: int = 100
    # Safety net: a flush of what is staged this long after staging
    # began (0 disables). Every loop pass flushes in on_drain, so on
    # both transports it finds nothing to send as a rule.
    flush_period_s: float = 0.05
    measure_latencies: bool = True


def batch_run(pieces: list, batch_size: int) -> tuple:
    """The commands of ``pieces``, in order, cut into CommandBatch
    values of at most ``batch_size`` commands: ``(raw, sizes)``, the
    value array's segment (``LazyValueArray.raw``) and each batch's
    command count.

    A piece is ``(raw, cols)``: a value array of one-command batches
    and its columns (``ColumnRun``'s), ``[i32 t][t addresses]`` then a
    command a value, ``[1][i32 1][i32 address index][i64 pseudonym]
    [i64 id][i32 length][payload]``. The address tables are joined, each
    command's index moved by its piece's offset among them, the 5-byte
    value heads dropped and one put before each batch: numpy over the
    bytes, no command decoded."""
    tables, bodies, indices, lengths = [], [], [], []
    offset = 0
    for raw, cols in pieces:
        length = cols[:, COL_LEN]
        start = len(raw) - (_ENTRY * len(cols) + int(length.sum()))
        tables.append(raw[4:start])
        bodies.append(raw[start:])
        indices.append(cols[:, COL_ADDR] + offset)
        lengths.append(length)
        offset += _I32.unpack_from(raw, 0)[0]
    data = np.frombuffer(bytearray(b"".join(bodies)), dtype=np.uint8)
    lengths = np.concatenate(lengths)
    n = len(lengths)
    size = _ENTRY + lengths
    starts = np.cumsum(size) - size
    data[(starts[:, None] + _HEAD + np.arange(4)).ravel()] = (
        np.concatenate(indices).astype("<i4").view(np.uint8))
    keep = np.ones(len(data), dtype=bool)
    keep[(starts[:, None] + np.arange(_HEAD)).ravel()] = False
    count = -(-n // batch_size)
    sizes = np.full(count, batch_size, dtype=np.int64)
    sizes[-1] = n - batch_size * (count - 1)
    heads = np.empty((count, _HEAD), dtype=np.uint8)
    heads[:, 0] = 1
    heads[:, 1:] = sizes.astype("<i4").view(np.uint8).reshape(count, 4)
    rows = np.concatenate(([0], np.cumsum(size - _HEAD)))
    body = np.insert(data[keep],
                     np.repeat(rows[np.arange(count) * batch_size], _HEAD),
                     heads.ravel())
    return _I32.pack(offset) + b"".join(tables) + body.tobytes(), sizes


class Batcher(Actor):
    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MultiPaxosConfig,
                 options: BatcherOptions = BatcherOptions(),
                 collectors: Collectors | None = None):
        super().__init__(address, transport, logger)
        config.check_valid()
        logger.check_ge(options.batch_size, 1)
        self.config = config
        self.options = options
        collectors = collectors or FakeCollectors()
        self.metrics_latency = collectors.summary(
            "multipaxos_batcher_requests_latency_seconds", labels=("type",))
        self.metrics_batched = collectors.counter(
            "multipaxos_batcher_batched_commands_total")
        self.metrics_batches = collectors.counter(
            "multipaxos_batcher_batches_sent_total", labels=("reason",))
        self.round_system = ClassicRoundRobin(config.num_leaders)
        self.round = 0
        # What this pass staged: (raw, cols) pieces in arrival order,
        # their command count, and the commit path (obs/trace.py) of a
        # sampled command among them with its place in that order.
        self._pieces: list = []
        self._staged = 0
        self._path = None
        self.pending_resend_batches: list = []
        # A client's array, alone or in a batch frame, lands as columns
        # over its wire bytes; the handler's time is the stage `batch`.
        self.wire_sinks = {
            CLIENT_ARRAY_TAG: (parse_client_array,
                               self._handle_client_columns, "batch"),
            CLIENT_BATCH_TAG: (parse_client_batch,
                               self._handle_client_columns, "batch"),
        }
        self._flush_timer = None
        self._flush_armed = False
        if options.flush_period_s > 0:
            self._flush_timer = self.timer(
                "batchFlush", options.flush_period_s, self._timer_flush)

    def _leader_address(self) -> Address:
        return self.config.leader_addresses[self.round_system.leader(
            self.round)]

    # --- staging ----------------------------------------------------------
    def _stage(self, raw: bytes, cols: np.ndarray) -> None:
        """Stage one piece (see ``batch_run``) for this pass's flush."""
        path = self.transport.path_in
        if path is not None and self._path is None:
            rows = np.flatnonzero((cols[:, COL_PSEUDONYM] == path.pseudonym)
                                  & (cols[:, COL_ID] == path.client_id))
            if len(rows):
                self._path = (path, self._staged + int(rows[0]))
        if self._flush_timer is not None and not self._flush_armed:
            self._flush_armed = True
            self._flush_timer.start()
        self._pieces.append((raw, cols))
        self._staged += len(cols)

    def _handle_client_columns(self, src: Address, colrun) -> None:
        """Wire-sink handler: a client's array (or a batch frame of
        them) as columns. Only its value segment and columns are kept,
        never the receive buffer the columns' offsets point into."""
        if len(colrun):
            self._stage(colrun.raw, colrun.cols)

    def _stage_commands(self, commands: tuple) -> None:
        """Decoded requests: encoded once into a piece."""
        raw = encode_value_array(
            tuple(CommandBatch((command,)) for command in commands))[8:]
        self._stage(raw, native.value_columns(raw, len(commands)))

    # --- flush ------------------------------------------------------------
    def on_drain(self) -> None:
        if self._pieces:
            self._flush("pass")

    def _timer_flush(self) -> None:
        self._flush_armed = False
        if self._pieces:
            self._flush("timer")

    def _flush(self, reason: str) -> None:
        """Everything staged, as one ClientRequestBatchRun to the
        round's leader."""
        batch_size = self.options.batch_size
        pieces, self._pieces, self._staged = self._pieces, [], 0
        path, self._path = self._path, None
        with self.trace_stage("batch"):
            raw, sizes = batch_run(pieces, batch_size)
        with self.trace_stage("flush"):
            if path is not None:
                # The leader finds the command's batch by its place
                # among the run's (TraceContext.slot, until it is one).
                self.transport.path_out = path[0].onward(
                    "batcher", path[1] // batch_size)
            self.send(self._leader_address(), ClientRequestBatchRun(
                values=LazyValueArray(raw, len(sizes))))
        self.metrics_batched.inc(int(sizes.sum()))
        full = int((sizes == batch_size).sum())
        if full:
            self.metrics_batches.labels("full").inc(full)
        if full < len(sizes):
            self.metrics_batches.labels(reason).inc()

    # --- handlers ---------------------------------------------------------
    def receive(self, src: Address, message) -> None:
        # timed(label) handler latency summaries (Leader.scala:281-293).
        if self.options.measure_latencies:
            with self.receive_timer(self.metrics_latency, message):
                self._receive_impl(src, message)
        else:
            self._receive_impl(src, message)

    def _receive_impl(self, src: Address, message) -> None:
        if isinstance(message, ClientRequestArray):
            with self.trace_stage("batch"):
                self._stage_commands(message.commands)
        elif isinstance(message, ClientRequest):
            with self.trace_stage("batch"):
                self._stage_commands((message.command,))
        elif isinstance(message, NotLeaderBatcher):
            self._handle_not_leader(src, message)
        elif isinstance(message, LeaderInfoReplyBatcher):
            self._handle_leader_info(src, message)
        else:
            self.logger.fatal(f"unexpected batcher message {message!r}")

    def _handle_not_leader(self, src: Address,
                           bounce: NotLeaderBatcher) -> None:
        self.pending_resend_batches.append(bounce.client_request_batch)
        for leader in self.config.leader_addresses:
            self.send(leader, LeaderInfoRequestBatcher())

    def _handle_leader_info(self, src: Address,
                            reply: LeaderInfoReplyBatcher) -> None:
        if reply.round > self.round:
            self.round = reply.round
        for batch in self.pending_resend_batches:
            self.send(self._leader_address(), batch)
        self.pending_resend_batches.clear()

"""A replica's log holds only what it cannot execute yet.

A ``ChosenRun`` is walked once as plain rows (``LazyValueArray.rows``).
An in-order one is executed straight from them and never enters the
log; the rows of one above a hole are parked there and leave once
executed. Every scenario here is held
against ``Oracle``, a replica written a slot at a time with a dict for
a log: the same ``.kvs``, the same ``client_table``, the same replies to
the same addresses in the same order, the same ``ChosenWatermark``s from
the same replicas.
"""

import pickle

import pytest

from frankenpaxos_tpu.protocols.multipaxos import (
    DistributionScheme,
    MultiPaxosConfig,
    Replica,
    ReplicaOptions,
)
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    Chosen,
    ChosenRun,
    ChosenWatermark,
    ClientReply,
    ClientReplyArray,
    Command,
    CommandBatch,
    CommandId,
    NOOP,
    ReadReply,
    ReadRequest,
    ReadRequestBatch,
)
from frankenpaxos_tpu.protocols.multipaxos.wire import (
    decode_value_array,
    encode_value_array,
    LazyValueArray,
    row_value,
    value_row,
)
from frankenpaxos_tpu.runtime import FakeLogger, LogLevel, SimTransport
from frankenpaxos_tpu.statemachine import KeyValueStore
from frankenpaxos_tpu.statemachine.impls import (
    GetReply,
    GetRequest,
    SetReply,
    SetRequest,
)
from frankenpaxos_tpu.wal import MemStorage, Wal

EVERY_N = 4
CLIENTS = ("client-a", ("10.0.0.7", 9000), "client-c")
LEADERS = ["leader-0", "leader-1"]
PROXY = "proxy-leader-0"
SET_REPLY = pickle.dumps(SetReply(), protocol=pickle.HIGHEST_PROTOCOL)


def config() -> MultiPaxosConfig:
    return MultiPaxosConfig(
        f=1,
        batcher_addresses=[],
        read_batcher_addresses=[],
        leader_addresses=LEADERS,
        leader_election_addresses=["election-0", "election-1"],
        proxy_leader_addresses=[PROXY, "proxy-leader-1"],
        acceptor_addresses=[["acceptor-0", "acceptor-1", "acceptor-2"]],
        replica_addresses=["replica-0", "replica-1"],
        proxy_replica_addresses=[],
        distribution_scheme=DistributionScheme.HASH,
    )


class Harness:
    """One replica alone on a SimTransport; what it sends is kept."""

    def __init__(self, index: int = 0, storage=None):
        self.index = index
        self.storage = storage
        self.transport = SimTransport(FakeLogger(LogLevel.FATAL))
        self.sent: list = []
        self.replica = self._build()

    def _build(self) -> Replica:
        wal = None if self.storage is None else Wal(self.storage)
        replica = Replica(
            f"replica-{self.index}", self.transport, self.transport.logger,
            KeyValueStore(), config(),
            ReplicaOptions(send_chosen_watermark_every_n_entries=EVERY_N,
                           unsafe_dont_recover=True),
            wal=wal)
        replica.send = lambda dst, message: self.sent.append((dst, message))
        return replica

    def restart(self) -> None:
        """kill -9 and recover from the WAL's storage."""
        self.transport.crash(self.replica.address)
        self.sent = []
        self.replica = self._build()

    def deliver(self, message) -> list:
        """One message as it comes off the wire, then the drain's end;
        returns what the replica sent for it."""
        codec = self.replica.serializer
        before = len(self.sent)
        self.replica.receive(PROXY, codec.from_bytes(codec.to_bytes(message)))
        self.replica.on_drain()
        return self.sent[before:]


class Oracle:
    """A replica a slot at a time (Replica.scala:300-344, 394-453):
    every chosen value goes into a dict, and the contiguous prefix is
    executed one slot, one command, one watermark test at a time."""

    def __init__(self, index: int):
        self.index = index
        self.log: dict = {}
        self.watermark = 0
        self.kvs: dict = {}
        self.client_table: dict = {}
        self.dirty = False

    def deliver(self, start_slot: int, values, arrays: bool) -> list:
        """-> the (destination, message)s the replica has to send."""
        for slot, value in enumerate(values, start_slot):
            if slot >= self.watermark:
                self.log.setdefault(slot, value)
        replies, watermarks = [], []
        while self.watermark in self.log:
            value = self.log.pop(self.watermark)
            slot = self.watermark
            for command in getattr(value, "commands", ()):
                cid = command.command_id
                key = (cid.client_address, cid.client_pseudonym)
                largest, result = self.client_table.get(key, (-1, None))
                if cid.client_id < largest:
                    continue
                if cid.client_id > largest:
                    request = pickle.loads(command.command)
                    self.kvs.update(request.key_values)
                    result = SET_REPLY
                    self.client_table[key] = (cid.client_id, result)
                    if slot % 2 != self.index:
                        continue
                replies.append((cid, slot, result))
            self.watermark += 1
            self.dirty = True
            if (self.watermark % EVERY_N == 0
                    and (self.watermark // EVERY_N) % 2 == self.index):
                watermarks.append(self.watermark)
        # The drain's tail (Replica.on_drain).
        if (self.dirty and self.watermark % EVERY_N
                and self.watermark % 2 == self.index):
            watermarks.append(self.watermark)
        self.dirty = False
        sent = []
        if arrays:
            by_client: dict = {}
            for cid, slot, result in replies:
                by_client.setdefault(cid.client_address, []).append(
                    (cid.client_pseudonym, cid.client_id, slot, result))
            sent += [(address, ClientReplyArray(entries=tuple(entries)))
                     for address, entries in by_client.items()]
        else:
            sent += [(cid.client_address, ClientReply(cid, slot, result))
                     for cid, slot, result in replies]
        for watermark in watermarks:
            sent += [(leader, ChosenWatermark(slot=watermark))
                     for leader in LEADERS]
        return sent


def split(sent: list) -> tuple:
    """Replies and watermarks apart: each kind keeps its order, and
    which of the two leaves first within one run is not a guarantee."""
    watermarks = [(dst, m) for dst, m in sent
                  if isinstance(m, ChosenWatermark)]
    return [(dst, m) for dst, m in sent
            if not isinstance(m, ChosenWatermark)], watermarks


class Writes:
    """Commands whose ids a closed loop would issue: a client's next
    write has its pseudonym's next id, its value names the write."""

    def __init__(self):
        self.ids: dict = {}
        self.count = 0

    def command(self, client: int, pseudonym: int = 0,
                key: str = "k") -> Command:
        at = (client, pseudonym)
        self.ids[at] = self.ids.get(at, -1) + 1
        self.count += 1
        request = SetRequest(((key, f"w{self.count}"),))
        return Command(
            CommandId(CLIENTS[client], pseudonym, self.ids[at]),
            pickle.dumps(request, protocol=pickle.HIGHEST_PROTOCOL))

    def slot(self, *clients: int) -> CommandBatch:
        return CommandBatch(tuple(self.command(c, pseudonym=c + 10 * n,
                                               key=f"k{c}")
                                  for n, c in enumerate(clients)))

    def slots(self, n: int) -> list:
        return [self.slot(i % len(CLIENTS)) for i in range(n)]


def overlaps_executed_prefix(w):
    values = w.slots(10)
    return [(0, values[:6]), (3, values[3:10])]


def hole_then_fill(w):
    values = w.slots(13)
    return [(0, values[:4]), (8, values[8:13]), (4, values[4:8])]


def resend_of_a_whole_run(w):
    values = w.slots(9)
    return [(0, values[:6]), (0, values[:6]), (6, values[6:])]


def two_proxy_leaders_out_of_order(w):
    values = w.slots(20)
    return [(5, values[5:10]), (0, values[:5]), (15, values[15:]),
            (10, values[10:15])]


def noop_inside_a_run(w):
    values = w.slots(9)
    values[0] = values[4] = values[8] = NOOP
    return [(0, values[:5]), (5, values[5:])]


def several_commands_a_slot(w):
    values = [w.slot(0, 1, 2), w.slot(1), w.slot(2, 2, 0), NOOP,
              w.slot(0, 1), w.slot(1, 1, 1, 1)]
    return [(0, values[:2]), (2, values[2:])]


def duplicate_command_ids(w):
    """A resent command inside one run is executed once and answered
    from the client table both times it is met again; one older than
    the client's newest is dropped."""
    first, second = w.command(0), w.command(0)
    other = w.command(1)
    values = [CommandBatch((first,)), CommandBatch((other, first)),
              CommandBatch((second,)), CommandBatch((first, second)),
              CommandBatch((second,))]
    return [(0, values), (5, [CommandBatch((second, other))])]


def fill_that_meets_parked_entries(w):
    values = w.slots(12)
    return [(0, values[:3]), (6, values[6:9]), (3, values[3:8]),
            (8, values[8:])]


def chosen_beside_runs(w):
    values = w.slots(9)
    return [(2, values[2]), (0, values[:2]), (3, values[3:7]),
            (8, values[8]), (7, values[7])]


SCENARIOS = [overlaps_executed_prefix, hole_then_fill, resend_of_a_whole_run,
             two_proxy_leaders_out_of_order, noop_inside_a_run,
             several_commands_a_slot, duplicate_command_ids,
             fill_that_meets_parked_entries, chosen_beside_runs]


@pytest.mark.parametrize("index", [0, 1])
@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda s: s.__name__)
def test_a_replica_equals_the_slot_by_slot_oracle(scenario, index):
    harness, oracle = Harness(index), Oracle(index)
    replica = harness.replica
    for start_slot, values in scenario(Writes()):
        if isinstance(values, list):
            sent = harness.deliver(ChosenRun(start_slot=start_slot,
                                             values=tuple(values)))
            expected = oracle.deliver(start_slot, values, arrays=True)
        else:
            sent = harness.deliver(Chosen(slot=start_slot, value=values))
            expected = oracle.deliver(start_slot, [values], arrays=False)
        assert split(sent) == split(expected)
        assert replica.executed_watermark == oracle.watermark
        assert replica.state_machine.kvs == oracle.kvs
        assert replica.client_table == oracle.client_table
        # The log holds what waits above a hole, as rows, and nothing
        # else.
        assert dict(replica.log.items()) == {
            slot: value_row(value) for slot, value in oracle.log.items()}
        assert replica.metrics_log_entries.get() == len(oracle.log)
        assert len(replica.log._buffer) - replica.log._buffer.count(
            None) == len(oracle.log)       # under the watermark too
        assert replica.num_chosen == oracle.watermark + len(oracle.log)
    assert oracle.log == {} and oracle.watermark > 0


def test_which_way_a_run_goes_is_counted():
    harness = Harness()
    replica = harness.replica
    values = Writes().slots(12)
    for start, end in ((0, 4), (4, 8)):           # in order
        harness.deliver(ChosenRun(start_slot=start,
                                  values=tuple(values[start:end])))
    assert (replica.metrics_runs_direct.get(),
            replica.metrics_runs_logged.get()) == (2, 0)
    harness.deliver(ChosenRun(start_slot=10, values=tuple(values[10:])))
    assert replica.metrics_runs_logged.get() == 1  # above a hole
    assert replica.metrics_log_entries.get() == 2
    harness.deliver(ChosenRun(start_slot=0, values=tuple(values[:8])))
    assert (replica.metrics_runs_direct.get(),     # a resend: neither
            replica.metrics_runs_logged.get()) == (2, 1)
    harness.deliver(ChosenRun(start_slot=6, values=tuple(values[6:10])))
    assert replica.metrics_runs_direct.get() == 3  # fills the hole
    assert replica.executed_watermark == 12
    assert replica.metrics_log_entries.get() == 0


@pytest.mark.parametrize("wal", [False, True], ids=["no_wal", "wal"])
def test_in_order_runs_leave_the_log_empty(wal):
    """After N runs the log holds nothing and every run went the
    direct way, with a WAL and without one; with one, a replay after a
    restart restores the same state, the same way."""
    harness = Harness(storage=MemStorage() if wal else None)
    writes = Writes()
    runs = 25
    for n in range(runs):
        sent = harness.deliver(ChosenRun(start_slot=6 * n,
                                         values=tuple(writes.slots(6))))
        assert any(isinstance(m, ClientReplyArray) for _, m in sent)
    replica = harness.replica
    assert replica.executed_watermark == 6 * runs
    assert replica.metrics_runs_direct.get() == runs
    assert replica.metrics_runs_logged.get() == 0
    assert replica.metrics_log_entries.get() == 0
    assert list(replica.log.items()) == []
    assert replica.metrics_executed.get() == 6 * runs
    if not wal:
        return
    before = (dict(replica.state_machine.kvs), dict(replica.client_table),
              replica.executed_watermark, replica.num_chosen)
    harness.restart()
    replica = harness.replica
    assert (replica.state_machine.kvs, replica.client_table,
            replica.executed_watermark, replica.num_chosen) == before
    assert replica.metrics_runs_direct.get() == runs
    assert list(replica.log.items()) == []
    # Nothing was answered from the replay, and the replica goes on.
    assert not [m for _, m in harness.sent
                if isinstance(m, ClientReplyArray)]
    harness.deliver(ChosenRun(start_slot=6 * runs,
                              values=tuple(writes.slots(6))))
    assert replica.executed_watermark == 6 * runs + 6


def test_a_wal_replay_of_parked_and_overlapping_runs_restores_the_state():
    """The records of a hole, its fill and an overlapping run replay
    to the state the live replica reached."""
    harness = Harness(storage=MemStorage())
    values = Writes().slots(14)
    for start, end in ((0, 3), (6, 9), (3, 8), (7, 14)):
        harness.deliver(ChosenRun(start_slot=start,
                                  values=tuple(values[start:end])))
    replica = harness.replica
    assert replica.executed_watermark == 14
    before = (dict(replica.state_machine.kvs), dict(replica.client_table))
    harness.restart()
    replica = harness.replica
    assert replica.executed_watermark == replica.num_chosen == 14
    assert (replica.state_machine.kvs, replica.client_table) == before
    assert list(replica.log.items()) == []


@pytest.mark.parametrize("batch", [False, True], ids=["read", "batch"])
def test_a_read_parked_inside_a_run_sees_its_slot_not_the_runs_last(batch):
    harness = Harness()
    replica = harness.replica
    reader = CLIENTS[2]
    writes = Writes()
    values = [CommandBatch((writes.command(0, key="k"),))
              for _ in range(6)]           # k = w1 .. w6 in slots 0 .. 5
    get = Command(CommandId(reader, 5, 0), pickle.dumps(GetRequest(("k",))))
    if batch:
        replica.receive(reader, ReadRequestBatch(slot=2, commands=(get,)))
    else:
        replica.receive(reader, ReadRequest(slot=2, command=get))
    assert replica._deferred_read_count == 1 and harness.sent == []
    sent = harness.deliver(ChosenRun(start_slot=0, values=tuple(values)))
    reads = [m for _, m in sent if isinstance(m, ReadReply)]
    assert len(reads) == 1 and reads[0].slot == 2
    assert pickle.loads(reads[0].result) == GetReply((("k", "w3"),))
    assert replica.state_machine.kvs == {"k": "w6"}
    assert replica._deferred_read_count == 0
    assert replica.deferred_reads.get(2) is None


def test_set_reply_is_encoded_once_and_the_bytes_are_the_same():
    store = KeyValueStore()
    request = pickle.dumps(SetRequest((("a", "1"),)))
    first, second = store.run(request), store.run(request)
    assert first == SET_REPLY and first is second
    assert pickle.loads(first) == SetReply()
    assert store.run(pickle.dumps(GetRequest(("a",)))) == pickle.dumps(
        GetReply((("a", "1"),)), protocol=pickle.HIGHEST_PROTOCOL)


# --- LazyValueArray.rows ----------------------------------------------------


def rows_of(values) -> list:
    """What ``rows`` has to yield for decoded ``values``."""
    return [value_row(value) for value in values]


def test_a_row_and_its_value_are_each_others_inverse():
    writes = Writes()
    for value in (NOOP, writes.slot(0, 1, 2), CommandBatch(())):
        row = value_row(value)
        assert row_value(row) == value
        if value is not NOOP:
            assert row == [
                (c.command_id.client_address, c.command_id.client_pseudonym,
                 c.command_id.client_id, c.command) for c in value.commands]
    assert value_row(pickle.loads(pickle.dumps(NOOP))) is NOOP


def test_rows_yield_the_values_as_plain_columns_and_cache_nothing():
    writes = Writes()
    values = (NOOP, writes.slot(0, 1, 2), writes.slot(1), NOOP,
              CommandBatch(()), writes.slot(2, 2))
    array = decode_value_array(encode_value_array(values))
    assert list(array.rows()) == rows_of(values)
    assert array._values is None            # no Command was made
    assert list(array.rows()) == rows_of(array)
    empty = decode_value_array(encode_value_array(()))
    assert list(empty.rows()) == []


def corruptions() -> dict:
    values = tuple(Writes().slots(4)) + (NOOP,)
    raw = encode_value_array(values)[8:]     # the segment, as decoded
    body = raw.index(b"\x01\x01\x00\x00\x00")  # first value: kind, count
    return {
        "truncated": (raw[:-3], 5),
        "table_count": (b"\xff\xff\xff\x7f" + raw[4:], 5),
        "address_index": (raw[:body + 5] + b"\x09" + raw[body + 6:], 5),
        "torn_entry": (raw[:body + 9], 5),
        "count_past_the_end": (raw, 9),
        "bad_address_text": (raw[:9] + b"\xff\xfe" + raw[11:], 5),
    }


@pytest.mark.parametrize("name", sorted(corruptions()))
def test_a_corrupt_array_raises_value_error_from_rows_as_from_decode(name):
    raw, n = corruptions()[name]
    with pytest.raises(ValueError, match="corrupt value array"):
        LazyValueArray(raw, n)._decode()
    with pytest.raises(ValueError, match="corrupt value array"):
        list(LazyValueArray(raw, n).rows())


def test_a_corrupt_run_executes_nothing():
    harness = Harness()
    replica = harness.replica
    raw, n = corruptions()["truncated"]
    with pytest.raises(ValueError):
        replica.receive(PROXY, ChosenRun(start_slot=0,
                                         values=LazyValueArray(raw, n)))
    assert replica.executed_watermark == 0 and replica.num_chosen == 0
    assert replica.state_machine.kvs == {} and harness.sent == []

"""The linearizable reads one pass of a client issues share one quorum
round and travel as one batch, out and back; the guarantees of a read
do not move with it."""

import pytest

from frankenpaxos_tpu.protocols.multipaxos.messages import (
    BatchMaxSlotReply,
    BatchMaxSlotRequest,
    ReadReply,
    ReadReplyBatch,
    ReadRequest,
    ReadRequestBatch,
)
from frankenpaxos_tpu.runtime.serializer import PickleSerializer
from frankenpaxos_tpu.serve.backoff import RETRY_EXHAUSTED
from frankenpaxos_tpu.serve.messages import Rejected
from frankenpaxos_tpu.statemachine import GetRequest, KeyValueStore, SetRequest
from tests.protocols.multipaxos_harness import make_multipaxos

SER = PickleSerializer()

MAJORITY = dict(f=1)
GRID = dict(f=1, flexible=True, grid_shape=(2, 3))
#: Acceptors a read's quorum round asks: f+1 of the group, a row of the grid.
ASKED = {"majority": 2, "grid": 3}


class Net:
    """Steps a sim's transport message by message and remembers every
    message it saw, so a test can count what was sent, drop a kind, or
    hold what goes to one address."""

    def __init__(self, sim):
        self.sim = sim
        self.seen: dict = {}

    def scan(self) -> None:
        decode = self.sim.clients[0].serializer.from_bytes
        for m in self.sim.transport.messages:
            if m.id not in self.seen:
                self.seen[m.id] = decode(m.data)

    def pump(self, drop: tuple = (), hold: tuple = (),
             hold_kind: tuple = ()) -> None:
        """Deliver until nothing is left, but messages of a kind in
        ``drop`` (lost) and those to an address in ``hold`` (of a kind
        in ``hold_kind``, where given), which stay in the buffer."""
        messages = self.sim.transport.messages
        while True:
            self.scan()
            todo = [m for m in messages if not (
                m.dst in hold and (not hold_kind or isinstance(
                    self.seen[m.id], hold_kind)))]
            if not todo:
                return
            if isinstance(self.seen[todo[0].id], drop):
                messages.remove(todo[0])
            else:
                self.sim.transport.deliver_message(todo[0])

    def sent(self, kind) -> list:
        self.scan()
        return [m for m in self.seen.values() if isinstance(m, kind)]

    def fire(self, prefix: str) -> int:
        timers = [t for t in self.sim.transport.running_timers()
                  if t.name.startswith(prefix)]
        for timer in timers:
            self.sim.transport.trigger_timer(timer.id)
        return len(timers)


def get(key: str) -> bytes:
    return SER.to_bytes(GetRequest((key,)))


def put(key: str, value: str) -> bytes:
    return SER.to_bytes(SetRequest(((key, value),)))


def value_of(result: bytes):
    return SER.from_bytes(result).key_values[0][1]


def a_pass_of_reads(sim, net, pseudonyms, got: dict, key: str = "k",
                    value: str = "v", **pump) -> None:
    """One pass of a closed loop: a write's answer arrives and its
    callback issues a read from each pseudonym. ``got[p]`` collects what
    pseudonym ``p``'s callback was given."""
    client = sim.clients[0]

    def issue(_):
        for p in pseudonyms:
            client.read(p, get(key),
                        lambda r, p=p: got.setdefault(p, []).append(r))

    client.write(1000, put(key, value), issue)
    net.pump(**pump)


def fresh(kwargs):
    sim = make_multipaxos(state_machine_factory=KeyValueStore, **kwargs)
    sim.transport.deliver_all()
    return sim, Net(sim)


@pytest.mark.parametrize("shape", ["majority", "grid"])
def test_a_pass_of_reads_is_one_round_one_request_and_one_reply(shape):
    sim, net = fresh(MAJORITY if shape == "majority" else GRID)
    got: dict = {}
    a_pass_of_reads(sim, net, range(8), got)
    asks = net.sent(BatchMaxSlotRequest)
    assert len(asks) == ASKED[shape]
    assert {(a.read_batcher_index, a.read_batcher_id) for a in asks} == {
        (-1, 0)}
    assert len(net.sent(BatchMaxSlotReply)) == ASKED[shape]
    (request,) = net.sent(ReadRequestBatch)
    assert [c.command_id.client_pseudonym for c in request.commands] == \
        list(range(8))
    (reply,) = net.sent(ReadReplyBatch)
    assert len(reply.batch) == 8
    assert net.sent(ReadRequest) == [] and net.sent(ReadReply) == []
    assert {p: [value_of(r) for r in rs] for p, rs in got.items()} == {
        p: ["v"] for p in range(8)}
    # Nothing is left behind: no state, no batch, no timer.
    client = sim.clients[0]
    assert client.states == {} and client._read_batches == {}
    assert [t.name for t in sim.transport.running_timers()
            if "Batch" in t.name] == []
    replica = [r for r in sim.replicas if r.metrics_reads.get()]
    assert [(r.metrics_reads.get(), r.metrics_read_messages.get())
            for r in replica] == [(8, 1)]


def test_a_read_issued_outside_any_delivery_is_a_batch_of_one():
    sim, net = fresh(MAJORITY)
    client = sim.clients[0]
    client.write(0, put("k", "v"))
    net.pump()
    got = []
    client.read(1, get("k"), got.append)
    assert len(net.sent(BatchMaxSlotRequest)) == 2  # left at once
    net.pump()
    (request,) = net.sent(ReadRequestBatch)
    assert len(request.commands) == 1
    # One reply needs no batch around it.
    assert len(net.sent(ReadReply)) == 1 and net.sent(ReadReplyBatch) == []
    assert [value_of(r) for r in got] == ["v"]


def test_a_max_slot_answer_is_never_used_for_a_read_issued_later():
    sim, net = fresh(MAJORITY)
    client = sim.clients[0]
    got: dict = {}
    acceptors = tuple(a.address for a in sim.acceptors)
    # Pass one: its question is on its way, nothing has answered.
    asks = dict(hold=acceptors, hold_kind=(BatchMaxSlotRequest,))
    a_pass_of_reads(sim, net, range(3), got, **asks)
    assert {a.read_batcher_id for a in net.sent(BatchMaxSlotRequest)} == {0}
    # A read issued now waits for a round of its own.
    client.read(7, get("k"), lambda r: got.setdefault(7, []).append(r))
    assert sorted(a.read_batcher_id
                  for a in net.sent(BatchMaxSlotRequest)) == [0, 0, 1, 1]
    assert sorted(client._read_batches) == [0, 1]
    # Answer the first round only: its request carries its own reads.
    first = [m for m in sim.transport.messages if m.dst in acceptors
             and net.seen[m.id].read_batcher_id == 0]
    for m in first:
        sim.transport.deliver_message(m)
    net.pump(**asks)
    (request,) = net.sent(ReadRequestBatch)
    assert [c.command_id.client_pseudonym for c in request.commands] == \
        [0, 1, 2]
    assert 7 not in got and sorted(got) == [0, 1, 2]
    net.pump()
    assert len(net.sent(ReadRequestBatch)) == 2
    assert [value_of(r) for r in got[7]] == ["v"]


def test_a_write_acknowledged_between_two_passes_is_seen_by_the_later_pass():
    sim, net = fresh(MAJORITY)
    client = sim.clients[0]
    got: dict = {}
    a_pass_of_reads(sim, net, range(4), got, value="old")
    assert {p: [value_of(r) for r in rs] for p, rs in got.items()} == {
        p: ["old"] for p in range(4)}

    # The next write is acknowledged (by the replica that owns its slot)
    # while the other replica, which the reads will go to, has not
    # heard of it.
    next_slot = max(a.max_voted_slot for a in sim.acceptors) + 1
    behind = sim.replicas[(next_slot + 1) % 2]
    client._random_replica = lambda: behind.address
    got.clear()
    a_pass_of_reads(sim, net, range(4), got, value="new",
                    hold=(behind.address,))
    newest = max(a.max_voted_slot for a in sim.acceptors)
    assert behind.executed_watermark <= newest
    # The batch is on its way to the replica that is behind. It names a
    # slot at or above the write's, so the replica parks it whole.
    (request,) = [m for m in net.sent(ReadRequestBatch)
                  if m.slot >= newest]
    held = [m for m in sim.transport.messages
            if isinstance(net.seen[m.id], ReadRequestBatch)]
    assert len(held) == 1
    sim.transport.deliver_message(held[0])
    assert behind._deferred_read_count == 4 and got == {}
    assert len(behind.deferred_reads.get(request.slot)) == 1
    # It hears of the write: the batch is released, with the new value.
    net.pump()
    assert behind.executed_watermark > request.slot
    assert behind._deferred_read_count == 0
    assert {p: [value_of(r) for r in rs] for p, rs in got.items()} == {
        p: ["new"] for p in range(4)}


@pytest.mark.parametrize("shape", ["majority", "grid"])
def test_lost_answers_and_requests_are_resent_by_the_batchs_timers(shape):
    sim, net = fresh(MAJORITY if shape == "majority" else GRID)
    client = sim.clients[0]
    got: dict = {}
    a_pass_of_reads(sim, net, range(5), got, drop=(BatchMaxSlotReply,))
    assert got == {} and sorted(client._read_batches) == [0]
    # The max-slot timer asks every acceptor of the group (the grid).
    assert net.fire("resendMaxSlotBatch") == 1
    everyone = 3 if shape == "majority" else 6
    assert len(net.sent(BatchMaxSlotRequest)) == ASKED[shape] + everyone
    net.pump(drop=(ReadRequestBatch,))
    assert got == {} and client._read_batches == {}
    assert len(net.sent(ReadRequestBatch)) == 1
    # Answers beyond the quorum find no batch and change nothing.
    assert len(net.sent(BatchMaxSlotReply)) == ASKED[shape] + everyone
    # The read timer sends the batch's reads again, to the same replica.
    assert net.fire("resendMaxSlotBatch") == 0
    assert net.fire("resendReadBatch") == 1
    net.pump()
    first, second = net.sent(ReadRequestBatch)
    assert first == second
    assert {p: [value_of(r) for r in rs] for p, rs in got.items()} == {
        p: ["v"] for p in range(5)}
    # A late duplicate of the request is answered again and ignored.
    replica = [r for r in sim.replicas if r.metrics_reads.get()][0]
    replica.receive(client.address, first)
    net.pump()
    assert len(net.sent(ReadReplyBatch)) == 2
    assert all(len(rs) == 1 for rs in got.values())
    assert client.states == {}
    assert net.fire("resendReadBatch") == 0


def test_a_rejected_read_of_a_batch_is_reissued_alone():
    sim, net = fresh(MAJORITY)
    client = sim.clients[0]
    replicas = tuple(r.address for r in sim.replicas)
    got: dict = {}
    a_pass_of_reads(sim, net, range(4), got, hold=replicas,
                    hold_kind=(ReadRequestBatch,))
    (request,) = net.sent(ReadRequestBatch)
    (held,) = [m for m in sim.transport.messages
               if isinstance(net.seen[m.id], ReadRequestBatch)]
    # The replica sheds pseudonym 2's read and loses the rest.
    sim.transport.messages.remove(held)
    client.receive(held.dst, Rejected(entries=((2, 0),), retry_after_ms=1))
    assert client.states[2].backoff_pending
    # While it backs off, the batch's timer re-sends the others only.
    assert net.fire("resendReadBatch") == 1
    again = net.sent(ReadRequestBatch)[1]
    assert [c.command_id.client_pseudonym for c in again.commands] == \
        [0, 1, 3]
    # The backoff over, it goes alone: same slot, same replica.
    assert net.fire("backoff2") == 1
    (alone,) = net.sent(ReadRequest)
    assert alone.slot == request.slot
    assert alone.command == request.commands[2]
    assert client.states[2].batch is None
    assert [m.dst for m in sim.transport.messages
            if isinstance(net.seen[m.id], ReadRequest)] == [held.dst]
    net.pump()
    assert {p: [value_of(r) for r in rs] for p, rs in got.items()} == {
        p: ["v"] for p in range(4)}
    assert client.states == {}


def test_a_batch_keeps_its_timer_while_one_of_its_reads_is_unanswered():
    """Also when a read that had left the count is answered after all
    (refused, then a duplicate of it served)."""
    sim, net = fresh(MAJORITY)
    client = sim.clients[0]
    replicas = tuple(r.address for r in sim.replicas)
    got: dict = {}
    a_pass_of_reads(sim, net, range(4), got, hold=replicas,
                    hold_kind=(ReadRequestBatch,))
    (request,) = net.sent(ReadRequestBatch)
    (held,) = [m for m in sim.transport.messages
               if isinstance(net.seen[m.id], ReadRequestBatch)]
    sim.transport.messages.remove(held)
    client.receive(held.dst, Rejected(entries=((2, 0),), retry_after_ms=1))
    assert net.fire("resendReadBatch") == 1      # re-sends 0, 1 and 3
    sim.transport.messages.clear()
    for p in (2, 0, 1):
        client.receive(held.dst, ReadReply(
            request.commands[p].command_id, request.slot, b"answer"))
    assert sorted(got) == [0, 1, 2] and sorted(client.states) == [3]
    assert net.fire("resendReadBatch") == 1
    again = net.sent(ReadRequestBatch)[-1]
    assert len(sim.transport.messages) == 1
    assert [c.command_id.client_pseudonym for c in again.commands] == [3]
    net.pump()
    assert value_of(got[3][0]) == "v" and client.states == {}
    assert net.fire("resendReadBatch") == 0


def test_a_read_out_of_retries_gives_up_and_its_batch_goes_on():
    sim = make_multipaxos(state_machine_factory=KeyValueStore,
                          client_retry_budget=2, **MAJORITY)
    sim.transport.deliver_all()
    net = Net(sim)
    client = sim.clients[0]
    got: dict = {}
    a_pass_of_reads(sim, net, range(4), got, drop=(BatchMaxSlotReply,))
    client.states[1].attempts = 2     # pseudonym 1 has spent its budget
    assert net.fire("resendMaxSlotBatch") == 1
    assert got == {1: [RETRY_EXHAUSTED]} and 1 not in client.states
    assert [client.states[p].attempts for p in (0, 2, 3)] == [1, 1, 1]
    net.pump()
    (request,) = net.sent(ReadRequestBatch)
    assert [c.command_id.client_pseudonym for c in request.commands] == \
        [0, 2, 3]
    assert {p: [value_of(r) for r in got[p]] for p in (0, 2, 3)} == {
        p: ["v"] for p in (0, 2, 3)}
    assert got[1] == [RETRY_EXHAUSTED] and client.states == {}


def test_reads_from_many_pseudonyms_over_tcp():
    """The same over real sockets: every read of closed read loops is
    answered with the newest value, in batches wider than one."""
    from tests.protocols.tcp_multipaxos import TcpMultiPaxos

    deployment = TcpMultiPaxos.launch({"coalesce_writes": "true"})
    try:
        deployment.closed_loops(1, 1)
        answers = deployment.closed_read_loops(32, 6)
        assert sorted(answers) == list(range(32))
        assert all(values == ["0.0"] * 6 for values in answers.values())
        reads = messages = 0
        for label in ("replica_0", "replica_1"):
            counters = deployment.collectors[label].metrics
            reads += counters[
                "multipaxos_replica_executed_reads_total"].get()
            messages += counters[
                "multipaxos_replica_read_messages_total"].get()
        assert reads == 32 * 6
        assert messages < reads / 2
        client = deployment.client
        assert deployment.on_loop("client", lambda: (
            dict(client.states), dict(client._read_batches))) == ({}, {})
    finally:
        deployment.stop()

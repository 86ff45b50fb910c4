"""A linearizable read at a slot the replica has not executed is parked,
answered exactly once when the slot executes, and let go of."""

import gc
import weakref

from frankenpaxos_tpu.obs.trace import RuntimeMetrics
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    Chosen,
    Command,
    CommandBatch,
    CommandId,
    ReadReply,
    ReadReplyBatch,
    ReadRequest,
    ReadRequestBatch,
)
from frankenpaxos_tpu.runtime import FakeCollectors
from frankenpaxos_tpu.statemachine import ReadableAppendLog
from tests.protocols.multipaxos_harness import make_multipaxos


def replies_to(sim, client) -> list:
    """The read replies on their way to ``client``, those inside a
    ReadReplyBatch (reads answered together) unfolded."""
    decode = sim.clients[0].serializer.from_bytes
    found = [decode(m.data) for m in sim.transport.messages
             if m.dst == client]
    return [r for m in found
            for r in (m.batch if isinstance(m, ReadReplyBatch) else (m,))
            if isinstance(r, ReadReply)]


def test_a_parked_read_is_answered_once_after_its_slot_and_released():
    sim = make_multipaxos(f=1, coalesced=False,
                          state_machine_factory=ReadableAppendLog)
    sim.transport.deliver_all()
    metrics = RuntimeMetrics(FakeCollectors(), "sim")
    sim.transport.runtime_metrics = metrics
    replica = sim.replicas[0]
    client = sim.clients[0].address
    leader = sim.leaders[0].address
    slot = replica.executed_watermark
    reads = [Command(CommandId(client, p, 0), b"r:") for p in range(3)]
    alive = [weakref.ref(read) for read in reads]

    # One read alone and a batch of two, all at a slot not yet executed.
    replica.receive(client, ReadRequest(slot=slot, command=reads[0]))
    replica.receive(client, ReadRequestBatch(slot=slot,
                                             commands=tuple(reads[1:])))
    assert replies_to(sim, client) == []
    assert replica._deferred_read_count == 3
    assert replica.metrics_deferred_reads.get() == 3
    assert replica.metrics_reads.get() == 0
    assert len(replica.deferred_reads.get(slot)) == 2   # a read, a batch

    # The slot is chosen and executes: each read is answered, once.
    write = Command(CommandId(client, 9, 0), b"w")
    replica.receive(leader, Chosen(slot=slot,
                                   value=CommandBatch((write,))))
    assert replica.executed_watermark == slot + 1
    answered = replies_to(sim, client)
    assert sorted(r.command_id.client_pseudonym for r in answered) == [
        0, 1, 2]
    assert replica.metrics_reads.get() == 3
    assert replica._deferred_read_count == 0
    stages = {stage: found for (_, stage), found
              in metrics.read_stages().items()}
    assert stages["read-park-wait"][1] == 2      # one a read or a batch
    assert stages["read-park-wait"][0] >= 0.0
    assert stages["read"][1] == 1                # one scope a slot

    # Nothing of them is kept: not the list, not the commands.
    assert replica.deferred_reads.get(slot) is None
    del reads, answered
    gc.collect()
    assert [ref() for ref in alive] == [None, None, None]

    # Later slots answer nothing again.
    replica.receive(leader, Chosen(
        slot=slot + 1,
        value=CommandBatch((Command(CommandId(client, 9, 1), b"w"),))))
    assert len(replies_to(sim, client)) == 3
    assert replica.metrics_reads.get() == 3


def test_a_read_at_an_executed_slot_is_answered_at_once_and_not_counted():
    sim = make_multipaxos(f=1, coalesced=False,
                          state_machine_factory=ReadableAppendLog)
    sim.transport.deliver_all()
    metrics = RuntimeMetrics(FakeCollectors(), "sim")
    sim.transport.runtime_metrics = metrics
    replica = sim.replicas[0]
    client = sim.clients[0].address
    replica.receive(client, ReadRequest(
        slot=replica.executed_watermark - 1,
        command=Command(CommandId(client, 0, 0), b"r:")))
    assert len(replies_to(sim, client)) == 1
    assert replica.metrics_deferred_reads.get() == 0
    assert replica.metrics_reads.get() == 1
    # It waited for no slot: an observation of 0, so the stage's mean is
    # over all reads.
    stages = {stage: found for (_, stage), found
              in metrics.read_stages().items()}
    assert stages["read-park-wait"] == (0.0, 1)
    assert stages["read"][1] == 1


def test_an_acceptor_counts_and_stages_the_read_paths_question():
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        BatchMaxSlotReply,
        BatchMaxSlotRequest,
    )

    sim = make_multipaxos(f=1, coalesced=False)
    results: list = []
    sim.clients[0].write(0, b"w", results.append)
    sim.transport.deliver_all()
    assert results
    metrics = RuntimeMetrics(FakeCollectors(), "sim")
    sim.transport.runtime_metrics = metrics
    acceptor = sim.acceptors[0]
    client = sim.clients[0].address
    asked = acceptor.metrics_requests.labels("BatchMaxSlotRequest").get()
    acceptor.receive(client, BatchMaxSlotRequest(read_batcher_index=-1,
                                                 read_batcher_id=3))
    assert acceptor.metrics_requests.labels(
        "BatchMaxSlotRequest").get() == asked + 1
    decode = sim.clients[0].serializer.from_bytes
    replies = [m for m in (decode(m.data) for m in sim.transport.messages
                           if m.dst == client)
               if isinstance(m, BatchMaxSlotReply)]
    assert [(r.read_batcher_index, r.read_batcher_id, r.slot)
            for r in replies] == [(-1, 3, acceptor.max_voted_slot)]
    stages = {stage: found for (_, stage), found
              in metrics.read_stages().items()}
    assert stages["max-slot"][1] == 1 and "vote" not in stages

"""Batched MultiPaxos: a client's coalesced arrays go to a batcher, which
re-buckets their commands into batches of at most ``batch_size``, one
log slot each, and sends a pass's batches to the leader as one run.

On the sim transport at toy size with seeded commands, and once over
real sockets, where an array lands at the batcher as columns over its
wire bytes."""

import random
import time

import numpy as np
import pytest

from frankenpaxos_tpu import native
from frankenpaxos_tpu.protocols.multipaxos.batcher import batch_run
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    ClientRequest,
    ClientRequestArray,
    ClientRequestBatch,
    ClientRequestBatchRun,
    Command,
    CommandBatch,
    CommandId,
    NotLeaderBatcher,
    Phase2aRun,
)
from frankenpaxos_tpu.protocols.multipaxos.wire import (
    encode_value_array,
    LazyValueArray,
)
from frankenpaxos_tpu.runtime.serializer import PickleSerializer
from frankenpaxos_tpu.statemachine import SetRequest
from tests.protocols.multipaxos_harness import (
    deliver_and_flush,
    executed_prefix,
    make_multipaxos,
)


def sent(sim, src, kind) -> list:
    """``(destination, message)`` of each message of ``kind`` waiting in
    the sim's buffer that ``src`` sent."""
    decode = sim.transport.actors[src].serializer.from_bytes
    return [(m.dst, message) for m, message in
            ((m, decode(m.data)) for m in sim.transport.messages
             if m.src == src)
            if isinstance(message, kind)]


def received(actor, kind) -> list:
    """From now on, ``(source, message)`` of each message of ``kind``
    that ``actor`` is handed."""
    got: list = []
    receive = actor.receive

    def receiving(src, message):
        if isinstance(message, kind):
            got.append((src, message))
        receive(src, message)

    actor.receive = receiving
    return got


def fire(sim, prefix: str) -> int:
    timers = [t for t in sim.transport.running_timers()
              if t.name.startswith(prefix)]
    for timer in timers:
        sim.transport.trigger_timer(timer.id)
    return len(timers)


def counts(batcher) -> tuple:
    """The batcher's commands batched and its batches by reason."""
    batches = batcher.metrics_batches
    return (batcher.metrics_batched.get(),
            {reason: batches.labels(reason).get()
             for reason in ("full", "pass", "timer")})


def passes_of_writes(sim, rng, passes: int, loops: int, got: dict) -> None:
    """``passes`` loop passes: in each, every client writes once from
    each of its ``loops`` pseudonyms, flushes its array, and the
    deployment runs until quiet. A write's payload names its client,
    loop and place in that loop; ``got`` maps it to its answers."""
    for n in range(passes):
        for c, client in enumerate(sim.clients):
            for p in rng.sample(range(loops), loops):
                payload = b"c%d.p%d.n%d.%d" % (c, p, n, rng.randrange(1000))
                client.write(p, payload,
                             lambda r, w=payload: got.setdefault(
                                 w, []).append(r))
            client.flush_writes()
        deliver_and_flush(sim, coalesced=True)


def loop_order(payloads) -> dict:
    """Each (client, loop)'s writes, in the order ``payloads`` has them,
    as their places in the loop."""
    out: dict = {}
    for payload in payloads:
        c, p, n, _ = payload.split(b".")
        out.setdefault((c, p), []).append(int(n[1:]))
    return out


@pytest.mark.parametrize("quorum_backend", ["dict", "tpu"])
def test_arrays_from_several_clients_through_two_batchers(quorum_backend):
    """Both replicas execute one log in which every write is once, each
    loop's writes in its issue order, and the writes are those the same
    traffic leaves in a deployment with no batchers; slots hold batches
    of several commands, none above ``batch_size``."""
    logs = {}
    for batchers in (2, 0):
        sim = make_multipaxos(f=1, num_batchers=batchers, batch_size=4,
                              num_clients=3, coalesced=True,
                              quorum_backend=quorum_backend)
        sim.transport.deliver_all()
        got: dict = {}
        passes_of_writes(sim, random.Random(42), 4, 6, got)
        assert len(got) == 3 * 6 * 4
        assert all(len(answers) == 1 for answers in got.values())
        values = [executed_prefix(replica) for replica in sim.replicas]
        assert values[0] == values[1]
        ran = [payload for _, payload in sim.replicas[0].execution_record.ran]
        assert sorted(ran) == sorted(got)
        order = loop_order(ran)
        assert all(places == sorted(places) for places in order.values())
        logs[batchers] = (values[0], order)
    batched, unbatched = logs[2][0], logs[0][0]
    sizes = [len(value.commands) for value in batched
             if isinstance(value, CommandBatch)]
    assert max(sizes) == 4 and len(batched) < len(unbatched) / 2
    assert all(len(value.commands) == 1 for value in unbatched
               if isinstance(value, CommandBatch))
    assert logs[2][1] == logs[0][1]


def test_a_clients_arrays_go_to_one_batcher_and_never_to_the_leader():
    sim = make_multipaxos(f=1, num_batchers=2, batch_size=4, num_clients=4,
                          coalesced=True)
    sim.transport.deliver_all()
    for _ in range(3):
        for client in sim.clients:
            for p in range(3):
                client.write(p, b"w", lambda _: None)
            client.flush_writes()
            arrays = sent(sim, client.address, ClientRequestArray)
            assert {dst for dst, _ in arrays} < set(
                sim.config.batcher_addresses)
        first = {client.address: sent(sim, client.address,
                                      ClientRequestArray)[0][0]
                 for client in sim.clients}
        deliver_and_flush(sim, coalesced=True)
    # The same batcher every pass, and the leader got runs, no arrays.
    for client in sim.clients:
        client.write(9, b"w", lambda _: None)
        client.flush_writes()
        ((dst, _),) = sent(sim, client.address, ClientRequestArray)
        assert dst == first[client.address]
    assert sum(b.metrics_batched.get() for b in sim.batchers) == 4 * 3 * 3


def test_a_deployment_with_no_batchers_sends_arrays_to_the_leader():
    sim = make_multipaxos(f=1, coalesced=True)
    sim.transport.deliver_all()
    client = sim.clients[0]
    for p in range(3):
        client.write(p, b"w", lambda _: None)
    client.flush_writes()
    ((dst, array),) = sent(sim, client.address, ClientRequestArray)
    assert dst == sim.leaders[0].address and len(array.commands) == 3


def commands(client: str, n: int, first: int = 0) -> tuple:
    return tuple(Command(CommandId(client, p, 0), b"%s.%d" % (
        client.encode(), p)) for p in range(first, first + n))


def test_batches_never_exceed_batch_size_and_a_partial_one_ends_the_pass():
    sim = make_multipaxos(f=1, num_batchers=2, batch_size=5, num_clients=0)
    sim.transport.deliver_all()
    batcher = sim.batchers[0]
    leader = sim.leaders[0]
    runs = received(leader, ClientRequestBatchRun)
    # Two arrays and a single write in one pass: one run of 5, 5, 3.
    for message in (ClientRequestArray(commands("a", 7)),
                    ClientRequest(commands("b", 1)[0]),
                    ClientRequestArray(commands("c", 5))):
        sim.transport.send("client-x", batcher.address,
                           batcher.serializer.to_bytes(message))
    sim.transport.deliver_all_coalesced()
    ((src, run),) = runs
    assert src == batcher.address
    batches = [value.commands for value in run.values]
    assert [len(b) for b in batches] == [5, 5, 3]
    assert sum(batches, ()) == (commands("a", 7) + commands("b", 1)
                                + commands("c", 5))
    assert counts(batcher) == (13, {"full": 2, "pass": 1, "timer": 0})
    deliver_and_flush(sim)
    assert [len(v.commands) for v in executed_prefix(sim.replicas[0])] == \
        [5, 5, 3]
    # Staged outside a pass, the safety net sends it; armed once.
    batcher.receive("client-x", ClientRequestArray(commands("d", 2)))
    batcher.receive("client-x", ClientRequestArray(commands("e", 1)))
    assert [t.name for t in sim.transport.running_timers()
            if t.address == batcher.address] == ["batchFlush"]
    assert fire(sim, "batchFlush") == 1
    ((_, run),) = sent(sim, batcher.address, ClientRequestBatchRun)
    del runs[:]
    assert [len(value.commands) for value in run.values] == [3]
    assert counts(batcher) == (16, {"full": 2, "pass": 1, "timer": 1})
    # Nothing staged: the next pass sends nothing, the timer is idle.
    batcher.on_drain()
    assert fire(sim, "batchFlush") == 0


def test_a_pass_reaches_the_proxy_leader_as_one_run_of_batches():
    sim = make_multipaxos(f=1, num_batchers=2, batch_size=3, num_clients=1,
                          coalesced=True)
    sim.transport.deliver_all()
    client = sim.clients[0]
    proposed = [received(proxy, Phase2aRun) for proxy in sim.proxy_leaders]
    got: list = []
    for p in range(8):
        client.write(p, b"w%d" % p, got.append)
    client.flush_writes()
    deliver_and_flush(sim, coalesced=True)
    ((src, run),) = sum(proposed, [])
    assert src == sim.leaders[0].address
    assert [len(value.commands) for value in run.values] == [3, 3, 2]
    assert len(got) == 8
    assert [len(v.commands) for v in executed_prefix(sim.replicas[0])] == \
        [3, 3, 2]


def test_a_not_leader_bounce_and_a_client_resend_stay_exactly_once():
    sim = make_multipaxos(f=1, num_batchers=2, batch_size=4, num_clients=2,
                          coalesced=True)
    sim.transport.deliver_all()
    got: dict = {}
    passes_of_writes(sim, random.Random(7), 1, 5, got)
    # Leader 1 takes over; the batchers still send to leader 0.
    sim.leaders[0].leader_change(is_new_leader=False)
    sim.leaders[1].leader_change(is_new_leader=True)
    sim.transport.deliver_all()
    for c, client in enumerate(sim.clients):
        for p in range(5):
            payload = b"c%d.p%d.n9.0" % (c, p)
            client.write(p, payload, lambda r, w=payload: got.setdefault(
                w, []).append(r))
        client.flush_writes()
    # Each client's array timer fires before anything was delivered:
    # every write of the pass travels twice.
    assert fire(sim, "resendWriteBatch") == 2
    for client in sim.clients:
        assert len(sent(sim, client.address, ClientRequestArray)) == 2
    bounced = []
    for batcher in sim.batchers:
        handle = batcher._handle_not_leader
        batcher._handle_not_leader = (
            lambda src, bounce, handle=handle: (bounced.append(bounce),
                                                handle(src, bounce)))
    deliver_and_flush(sim, coalesced=True)
    assert bounced and all(isinstance(b, NotLeaderBatcher) for b in bounced)
    assert all(batcher.round == sim.leaders[1].round
               for batcher in sim.batchers if batcher.round)
    assert len(got) == 2 * 5 * 2
    assert all(len(answers) == 1 for answers in got.values())
    logs = [executed_prefix(replica) for replica in sim.replicas]
    assert logs[0] == logs[1]
    ran = [payload for _, payload in sim.replicas[0].execution_record.ran]
    assert sorted(ran) == sorted(got)
    # Both copies of a write were chosen; the client table ran one.
    chosen = [c.command for v in logs[0] if isinstance(v, CommandBatch)
              for c in v.commands]
    assert any(chosen.count(w) == 2 for w in got)


def test_a_lone_batch_is_proposed_in_a_slot_of_its_own():
    """What a batcher re-sends after a bounce: a ClientRequestBatch."""
    sim = make_multipaxos(f=1, num_clients=0)
    sim.transport.deliver_all()
    leader = sim.leaders[0]
    batch = ClientRequestBatch(CommandBatch(commands("z", 3)))
    sim.transport.send("batcher-z", leader.address,
                       leader.serializer.to_bytes(batch))
    deliver_and_flush(sim)
    (value,) = executed_prefix(sim.replicas[0])
    assert value == batch.batch


def one_command_pieces(rng, clients: int, per_client: int) -> tuple:
    """Value arrays of one-command batches, a client each, as a client's
    array lands at a batcher, and the commands in order."""
    pieces, expected = [], []
    for c in range(clients):
        address = ("10.0.0.%d" % c, 7000 + c) if c % 2 else "client-%d" % c
        cmds = tuple(Command(CommandId(address, rng.randrange(1 << 40),
                                       rng.randrange(1 << 40)),
                             rng.randbytes(rng.randrange(0, 40)))
                     for _ in range(rng.randrange(1, per_client + 1)))
        raw = encode_value_array(tuple(CommandBatch((c,)) for c in cmds))[8:]
        pieces.append((raw, native.value_columns(raw, len(cmds))))
        expected.extend(cmds)
    return pieces, expected


@pytest.mark.parametrize("batch_size", [1, 3, 100])
def test_batch_run_cuts_the_wire_bytes_as_decoding_would(batch_size):
    rng = random.Random(batch_size)
    for _ in range(20):
        pieces, expected = one_command_pieces(rng, rng.randrange(1, 5), 30)
        raw, sizes = batch_run(pieces, batch_size)
        values = tuple(LazyValueArray(raw, len(sizes)))
        assert [len(v.commands) for v in values] == sizes.tolist()
        assert all(0 < size <= batch_size for size in sizes)
        assert sum((v.commands for v in values), ()) == tuple(expected)
        want = tuple(CommandBatch(tuple(expected[i:i + batch_size]))
                     for i in range(0, len(expected), batch_size))
        assert values == want
        # Forwarded unparsed, the segment reads the same at the replica.
        rows = list(LazyValueArray(raw, len(sizes)).rows())
        assert [len(row) for row in rows] == sizes.tolist()
        assert np.array_equal(sizes, [len(v.commands) for v in want])


def test_over_sockets_arrays_land_as_columns_and_slots_hold_batches():
    """Two batchers in a deployment over real sockets: each write once
    in both replicas, batches of several commands, the batcher's stages
    and counters, and a sampled write's path through it."""
    from tests.protocols.tcp_multipaxos import TcpMultiPaxos

    deployment = TcpMultiPaxos.launch(
        {"coalesce_writes": "true", "batch_size": "8"}, batchers=2)
    try:
        deployment.closed_loops(loops=24, writes_each=12)
        replicas = [deployment.actors[f"replica_{i}"][0] for i in range(2)]
        deadline = time.monotonic() + 60
        while [deployment.on_loop(f"replica_{i}",
                                  lambda r=r: r.metrics_executed.get())
               for i, r in enumerate(replicas)] != [24 * 12] * 2:
            assert time.monotonic() < deadline, "a replica fell behind"
            time.sleep(0.05)
        batched, batches = 0, {}
        for index in range(2):
            label = f"batcher_{index}"
            (batcher,) = deployment.actors[label]
            grown, by_reason = deployment.on_loop(
                label, lambda b=batcher: counts(b))
            batched += grown
            for reason, n in by_reason.items():
                batches[reason] = batches.get(reason, 0) + n
            if grown:
                stages = deployment.stage_counts(label)
                assert stages.get("batch") and stages.get("flush")
        # The deployment's own probe-free traffic: every write batched.
        assert batched == 24 * 12
        assert sum(batches.values()) < batched / 2
        assert batches["timer"] == 0
        stores = [deployment.on_loop(f"replica_{i}",
                                     lambda r=r: dict(r.state_machine.kvs))
                  for i, r in enumerate(replicas)]
        assert stores[0] == stores[1] and stores[0]["k"]
        # One sampled write in 64 carries its path through the batcher.
        waits = {stage for index in range(2)
                 for stage in deployment.stage_counts(f"batcher_{index}")}
        assert {"hop-client-wait", "hold-client-wait"} <= waits
        assert "hop-batcher-wait" in deployment.stage_counts("leader_0")
    finally:
        deployment.stop()


def test_the_payloads_of_a_key_value_write_survive_the_bytes_cut():
    """A batched SetRequest is what the client wrote, byte for byte."""
    ser = PickleSerializer()
    writes = [ser.to_bytes(SetRequest((("k", "v%d" % i),))) for i in range(7)]
    cmds = tuple(Command(CommandId(("127.0.0.1", 9), i, i), w)
                 for i, w in enumerate(writes))
    raw = encode_value_array(tuple(CommandBatch((c,)) for c in cmds))[8:]
    raw, sizes = batch_run([(raw, native.value_columns(raw, 7))], 4)
    values = LazyValueArray(raw, len(sizes))
    assert [ser.from_bytes(c.command) for v in values for c in v.commands] \
        == [SetRequest((("k", "v%d" % i),)) for i in range(7)]

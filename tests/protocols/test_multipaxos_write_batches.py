"""The coalesced writes one pass of a client sends share their array's
resend timer: one timer a pass, stopped once all of them are answered,
and a timeout re-sends the writes still pending as one array, each
charged its own retry."""

import threading

from frankenpaxos_tpu.protocols.multipaxos.messages import (
    ClientReply,
    ClientRequest,
    ClientRequestArray,
)
from frankenpaxos_tpu.runtime.serializer import PickleSerializer
from frankenpaxos_tpu.serve.backoff import RETRY_EXHAUSTED
from frankenpaxos_tpu.serve.messages import Rejected
from frankenpaxos_tpu.statemachine import SetRequest
from tests.protocols.multipaxos_harness import (
    deliver_and_flush,
    make_multipaxos,
)


def client_sent(sim, kind) -> list:
    """The messages of ``kind`` waiting in the sim's buffer that the
    client sent."""
    client = sim.clients[0]
    decode = client.serializer.from_bytes
    return [message for message in
            (decode(m.data) for m in sim.transport.messages
             if m.src == client.address)
            if isinstance(message, kind)]


def pseudonyms_of(array: ClientRequestArray) -> list:
    return [c.command_id.client_pseudonym for c in array.commands]


def resend_timers(sim) -> list:
    return sorted(t.name for t in sim.transport.running_timers()
                  if t.name.startswith("resendWrite"))


def fire(sim, prefix: str) -> int:
    timers = [t for t in sim.transport.running_timers()
              if t.name.startswith(prefix)]
    for timer in timers:
        sim.transport.trigger_timer(timer.id)
    return len(timers)


def fresh(**kwargs):
    """A deployment whose leader has finished its first round."""
    sim = make_multipaxos(f=1, **kwargs)
    sim.transport.deliver_all()
    return sim


def a_pass_of_writes(sim, pseudonyms, got: dict) -> None:
    """One pass of writes, one from each pseudonym, flushed as one
    array; ``got[p]`` collects what ``p``'s callback was given."""
    client = sim.clients[0]
    for p in pseudonyms:
        client.write(p, b"w%d" % p,
                     lambda r, p=p: got.setdefault(p, []).append(r))
    client.flush_writes()


def test_a_pass_of_writes_is_one_array_and_one_timer():
    sim = fresh(coalesced=True)
    client = sim.clients[0]
    got: dict = {}
    a_pass_of_writes(sim, range(8), got)
    (array,) = client_sent(sim, ClientRequestArray)
    assert pseudonyms_of(array) == list(range(8))
    assert client_sent(sim, ClientRequest) == []
    assert resend_timers(sim) == ["resendWriteBatch0"]
    assert (client.write_batches_armed, client.write_batch_writes,
            client.write_batch_resends) == (1, 8, 0)
    deliver_and_flush(sim, coalesced=True)
    assert sorted(got) == list(range(8))
    assert all(len(results) == 1 for results in got.values())
    # Answering all of them stopped the one timer; nothing is left.
    assert client.states == {} and resend_timers(sim) == []
    assert client._write_timers == {}
    # The next pass is a batch of its own.
    a_pass_of_writes(sim, range(8, 12), got)
    assert resend_timers(sim) == ["resendWriteBatch1"]
    assert (client.write_batches_armed, client.write_batch_writes) == \
        (2, 12)
    deliver_and_flush(sim, coalesced=True)
    assert len(got) == 12 and resend_timers(sim) == []


def test_a_fired_batch_resends_exactly_the_live_writes_as_one_array():
    sim = fresh(coalesced=True, client_retry_budget=2)
    client = sim.clients[0]
    got: dict = {}
    a_pass_of_writes(sim, range(6), got)
    (array,) = client_sent(sim, ClientRequestArray)
    sim.transport.messages.clear()          # the array is lost
    # Pseudonym 0 is answered after all (a copy got through), and
    # pseudonym 1 has spent its budget.
    client.receive(sim.replicas[0].address,
                   ClientReply(array.commands[0].command_id, 0, b"0"))
    client.states[1].attempts = 2
    assert fire(sim, "resendWrite") == 1
    assert got[0] == [b"0"] and got[1] == [RETRY_EXHAUSTED]
    (again,) = client_sent(sim, ClientRequestArray)
    assert pseudonyms_of(again) == [2, 3, 4, 5]
    assert list(again.commands) == list(array.commands[2:])
    assert [client.states[p].attempts for p in (2, 3, 4, 5)] == [1] * 4
    assert client.write_batch_resends == 1
    # The same timer was armed again, and goes once they are answered.
    assert resend_timers(sim) == ["resendWriteBatch0"]
    deliver_and_flush(sim, coalesced=True)
    assert {p: len(got[p]) for p in range(6)} == {p: 1 for p in range(6)}
    assert client.states == {} and resend_timers(sim) == []


def test_a_write_out_of_retries_at_every_timeout_closes_its_batch():
    sim = fresh(coalesced=True, client_retry_budget=1)
    client = sim.clients[0]
    got: dict = {}
    a_pass_of_writes(sim, range(3), got)
    sim.transport.messages.clear()
    assert fire(sim, "resendWrite") == 1     # each write's one retry
    sim.transport.messages.clear()
    assert fire(sim, "resendWrite") == 1     # the budget is spent
    assert got == {p: [RETRY_EXHAUSTED] for p in range(3)}
    assert client.states == {} and resend_timers(sim) == []
    assert client_sent(sim, ClientRequestArray) == []


def test_a_rejected_write_backs_off_alone_and_rejoins_a_later_batch():
    sim = fresh(coalesced=True)
    client = sim.clients[0]
    got: dict = {}
    a_pass_of_writes(sim, range(4), got)
    sim.transport.messages.clear()
    leader = sim.leaders[0].address
    client.receive(leader, Rejected(entries=((2, 0),), retry_after_ms=1))
    assert client.states[2].backoff_pending
    # While it backs off, the batch's timer re-sends the others only.
    assert fire(sim, "resendWriteBatch") == 1
    (again,) = client_sent(sim, ClientRequestArray)
    assert pseudonyms_of(again) == [0, 1, 3]
    sim.transport.messages.clear()
    # The backoff over, it is staged for the next pass's batch.
    assert fire(sim, "backoff2") == 1
    client.flush_writes()
    (alone,) = client_sent(sim, ClientRequestArray)
    assert pseudonyms_of(alone) == [2]
    assert client.states[2].batch is not client.states[0].batch
    assert resend_timers(sim) == ["resendWriteBatch0", "resendWriteBatch1"]
    assert client.write_batches_armed == 2
    # Its old batch no longer re-sends it.
    assert fire(sim, "resendWriteBatch0") == 1
    assert [pseudonyms_of(a) for a in client_sent(
        sim, ClientRequestArray)] == [[2], [0, 1, 3]]
    deliver_and_flush(sim, coalesced=True)
    assert sorted(got) == [0, 1, 2, 3]
    assert all(len(results) == 1 for results in got.values())
    # Both batches are gone once everything is answered.
    assert client.states == {} and resend_timers(sim) == []


def test_writes_sent_alone_keep_their_own_timers():
    sim = fresh()
    client = sim.clients[0]
    got: dict = {}
    for p in range(3):
        client.write(p, b"w%d" % p,
                     lambda r, p=p: got.setdefault(p, []).append(r))
    assert len(client_sent(sim, ClientRequest)) == 3
    assert resend_timers(sim) == ["resendWrite0", "resendWrite1",
                                  "resendWrite2"]
    assert client.write_batches_armed == 0
    assert all(s.batch is None for s in client.states.values())
    deliver_and_flush(sim)
    assert sorted(got) == [0, 1, 2] and resend_timers(sim) == []


def test_a_pass_of_writes_over_tcp_schedules_a_constant_number_of_callbacks():
    """On a real event loop a timer's start and stop each post a
    callback through ``call_soon_threadsafe`` (a write to the loop's
    self-pipe). A pass of N coalesced writes, answered, posts the same
    few whatever N: the flush, the batch timer's start and its stop."""
    from tests.protocols.tcp_multipaxos import TcpMultiPaxos

    deployment = TcpMultiPaxos.launch({"coalesce_writes": "true"})
    try:
        client = deployment.client
        command = PickleSerializer().to_bytes(SetRequest((("k", "v"),)))
        loop = deployment.transports["client"].loop
        loop_thread = deployment.on_loop("client", threading.get_ident)
        posted: list = []
        post = loop.call_soon_threadsafe

        def counting(callback, *args, **kwargs):
            if threading.get_ident() == loop_thread:
                posted.append(callback)
            return post(callback, *args, **kwargs)

        loop.call_soon_threadsafe = counting
        per_pass: dict = {}
        first = 0
        for n in (8, 64):
            answered = threading.Event()
            left = [n]

            def done(_):
                left[0] -= 1
                if not left[0]:
                    answered.set()

            def a_pass(first=first, n=n, done=done):
                posted.clear()
                for p in range(first, first + n):
                    client.write(p, command, done)

            deployment.on_loop("client", a_pass)
            assert answered.wait(60), f"{left[0]} writes never answered"
            per_pass[n] = deployment.on_loop("client", lambda: len(posted))
            first += n
        del loop.call_soon_threadsafe
        assert per_pass == {8: 3, 64: 3}
        assert deployment.on_loop("client", lambda: (
            client.write_batches_armed, client.write_batch_writes,
            client.write_batch_resends, dict(client.states))) == \
            (2, 72, 0, {})
    finally:
        deployment.stop()

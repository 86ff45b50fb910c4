"""TcpTransport: framing, lazy connect, flush coalescing, timers —
echo and unreplicated over real localhost sockets."""

import socket
import threading
import time

import pytest

from frankenpaxos_tpu.protocols.echo import EchoClient, EchoServer
from frankenpaxos_tpu.protocols.unreplicated import (
    UnreplicatedClient,
    UnreplicatedServer,
)
from frankenpaxos_tpu.runtime import FakeLogger
from frankenpaxos_tpu.runtime.tcp_transport import _encode_frame, TcpTransport
from frankenpaxos_tpu.statemachine import AppendLog


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for(predicate, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return False


@pytest.fixture
def transports():
    created = []

    def make(address=None):
        t = TcpTransport(address, FakeLogger())
        t.start()
        created.append(t)
        return t

    yield make
    for t in created:
        t.stop()


def test_frame_encoding_roundtrip():
    frame = _encode_frame(("127.0.0.1", 9000), b"payload")
    import struct
    (length,) = struct.unpack(">I", frame[:4])
    assert length == len(frame) - 4
    (hlen,) = struct.unpack(">I", frame[4:8])
    assert frame[8:8 + hlen] == b"127.0.0.1:9000"
    assert frame[8 + hlen:] == b"payload"


def test_oversized_frame_rejected():
    with pytest.raises(ValueError):
        _encode_frame(("h", 1), b"x" * (10 * 1024 * 1024 + 1))


def test_echo_over_tcp(transports):
    server_addr = ("127.0.0.1", free_port())
    client_addr = ("127.0.0.1", free_port())
    server_t = transports(server_addr)
    client_t = transports(client_addr)
    logger = FakeLogger()
    server = EchoServer(server_addr, server_t, logger)
    client = EchoClient(client_addr, client_t, logger, server_addr)

    got = []
    client.echo("over tcp", got.append)
    assert wait_for(lambda: got == ["over tcp"])
    assert server.num_messages_received == 1


def test_unreplicated_over_tcp_with_batching(transports):
    server_addr = ("127.0.0.1", free_port())
    client_addr = ("127.0.0.1", free_port())
    server_t = transports(server_addr)
    client_t = transports(client_addr)
    logger = FakeLogger()
    server = UnreplicatedServer(server_addr, server_t, logger, AppendLog(),
                                flush_every_n=4)
    client = UnreplicatedClient(client_addr, client_t, logger, server_addr,
                                resend_period_s=30.0)

    # Four pipelined command streams (pseudonyms), two rounds each: every
    # round of four replies fills the server's flush batch exactly.
    results = []
    done = threading.Event()

    def on_reply(pseudonym, round, result):
        results.append((pseudonym, round, result))
        if len(results) == 8:
            done.set()
        elif round == 0:
            client.propose(pseudonym, b"cmd-%d-1" % pseudonym,
                           lambda r, p=pseudonym: on_reply(p, 1, r))

    def propose_round_0():
        for p in range(4):
            client.propose(p, b"cmd-%d-0" % p,
                           lambda r, p=p: on_reply(p, 0, r))

    # In one pass of the client's loop, so that the four leave in one
    # write and the server answers all four before any second-round
    # proposal can exist: proposed one by one from this thread, a
    # descheduled caller let pseudonym 0's whole round trip overtake
    # pseudonym 3's first proposal, the server's count of four then
    # straddled the rounds, and the last replies sat unflushed.
    client_t.loop.call_soon_threadsafe(propose_round_0)
    assert done.wait(timeout=10)
    assert len(server.state_machine.get()) == 8
    assert {(p, r) for p, r, _ in results} == {(p, r) for p in range(4)
                                              for r in range(2)}


def test_timer_fires_and_resets(transports):
    t = transports(("127.0.0.1", free_port()))
    fired = []
    timer = t.timer(("x", 0), "t", 0.05, lambda: fired.append(1))
    timer.start()
    assert wait_for(lambda: fired == [1])
    # One-shot: doesn't refire on its own.
    time.sleep(0.1)
    assert fired == [1]
    timer.start()
    assert wait_for(lambda: fired == [1, 1])


def test_timer_stop_prevents_fire(transports):
    t = transports(("127.0.0.1", free_port()))
    fired = []
    timer = t.timer(("x", 0), "t", 0.2, lambda: fired.append(1))
    timer.start()
    timer.stop()
    time.sleep(0.35)
    assert fired == []


def test_connect_failure_drops_and_logs(transports):
    logger = FakeLogger()
    t = TcpTransport(("127.0.0.1", free_port()), logger)
    t.start()
    try:
        dead = ("127.0.0.1", free_port())  # nobody listening
        t.send(t.listen_address, dead, b"hello?")
        assert wait_for(lambda: any("connect" in m for _, m in logger.records))
    finally:
        t.stop()


def test_burst_beyond_scanner_frame_cap():
    """A single flush of more frames than one native scan pass returns
    (4096) must still dispatch every frame -- the receive loop re-scans
    the backlog instead of waiting for more bytes."""
    import threading

    from frankenpaxos_tpu.bench.harness import free_port
    from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
    from frankenpaxos_tpu.runtime.actor import Actor

    logger = FakeLogger(LogLevel.FATAL)
    a_addr = ("127.0.0.1", free_port())
    b_addr = ("127.0.0.1", free_port())
    ta = TcpTransport(a_addr, logger)
    ta.start()
    tb = TcpTransport(b_addr, logger)
    tb.start()
    n = 6000
    got = []
    done = threading.Event()

    class Sink(Actor):
        def receive(self, src, message):
            got.append(message)
            if len(got) == n:
                done.set()

    class Src(Actor):
        def receive(self, src, message):
            pass

    Sink(b_addr, tb, logger)
    src = Src(a_addr, ta, logger)

    def send():
        for i in range(n):
            src.send_no_flush(b_addr, b"m%d" % i)
        src.flush(b_addr)

    try:
        ta.loop.call_soon_threadsafe(send)
        assert done.wait(30), f"only {len(got)}/{n} delivered"
    finally:
        ta.stop()
        tb.stop()


def test_corrupt_frame_drops_connection_not_server(transports):
    """ADVICE r3: a corrupt frame (header length exceeding the frame)
    must log + drop that connection, not kill the accept loop; a fresh
    connection afterwards still works."""
    import struct

    server_addr = ("127.0.0.1", free_port())
    client_addr = ("127.0.0.1", free_port())
    server_t = transports(server_addr)
    logger = FakeLogger()
    server = EchoServer(server_addr, server_t, logger)

    # Hand-craft a frame whose declared header length exceeds the frame.
    payload = b"xx"
    bad_inner = struct.pack(">I", 9999) + payload
    bad = struct.pack(">I", len(bad_inner)) + bad_inner
    with socket.create_connection(server_addr) as s:
        s.sendall(bad)
        # Server closes on the corrupt frame.
        s.settimeout(5)
        assert s.recv(1) == b""
    assert wait_for(lambda: any("corrupt frame" in m
                                for _, m in server_t.logger.records))

    # The transport still accepts and serves new connections.
    client_t = transports(client_addr)
    client = EchoClient(client_addr, client_t, logger, server_addr)
    got = []
    client.echo("still alive", got.append)
    assert wait_for(lambda: got == ["still alive"])
    assert server.num_messages_received == 1


# --- the wire-sink path under a tracer ---------------------------------------


def _sink_batches(transports, traced: bool, batches: int = 5):
    """Send ``batches`` control batch frames of vote acks to an actor
    with a wire sink; returns (sink handler calls, per-message receive
    calls, the receiver's tracer or None, its stage series)."""
    from frankenpaxos_tpu.ingest.columns import parse_ack_batch
    from frankenpaxos_tpu.obs import RuntimeMetrics, Tracer
    from frankenpaxos_tpu.protocols.multipaxos.messages import Phase2bRange
    from frankenpaxos_tpu.runtime import FakeCollectors
    from frankenpaxos_tpu.runtime.actor import Actor
    from frankenpaxos_tpu.runtime.paxwire import CONTROL_BATCH_TAG

    sunk, received = [], []

    class Sink(Actor):
        def __init__(self, address, transport, logger):
            super().__init__(address, transport, logger)
            self.wire_sinks = {CONTROL_BATCH_TAG: (
                parse_ack_batch, lambda src, acks: sunk.append(acks.count),
                "vote-intake")}

        def receive(self, src, message):
            received.append(message)

    class Source(Actor):
        def receive(self, src, message):
            pass

    logger = FakeLogger()
    sink_address = ("127.0.0.1", free_port())
    receiver = transports(sink_address)
    collectors = FakeCollectors()
    receiver.runtime_metrics = RuntimeMetrics(collectors, "sink")
    if traced:
        receiver.tracer = Tracer(role="sink",
                                 runtime_metrics=receiver.runtime_metrics)
    Sink(sink_address, receiver, logger)
    source_address = ("127.0.0.1", free_port())
    source = Source(source_address, transports(source_address), logger)
    for batch in range(batches):
        # Adjacent ranges have no coalescer: they leave as one control
        # batch frame, as an acceptor's acks of several runs do. Sent
        # in ONE pass of the source's loop, as a role sends: from this
        # thread each message would be a loop callback of its own, and
        # a connect or a flush landing between two of them splits the
        # frame.
        source.transport.loop.call_soon_threadsafe(
            source.send_batch, sink_address, [
                Phase2bRange(group_index=0, acceptor_index=i,
                             slot_start_inclusive=8 * batch,
                             slot_end_exclusive=8 * batch + 4, round=0)
                for i in range(3)])
        assert wait_for(lambda: len(sunk) == batch + 1), (sunk, received)
    return sunk, received, receiver.tracer, \
        collectors.metrics["fpx_runtime_drain_stage_seconds"]


def test_a_tracer_rides_the_wire_sink_path(transports):
    """Attaching a tracer no longer switches the sink fast path off: a
    traced and an untraced run deliver the same number of sink batches,
    none falls back to per-message delivery, and each traced batch is
    one receive span with ``vote-intake`` as its stage."""
    plain, plain_received, _, plain_series = _sink_batches(transports,
                                                           False)
    traced, traced_received, tracer, traced_series = _sink_batches(
        transports, True)
    assert plain == traced == [3] * 5
    assert plain_received == traced_received == []
    for series in (plain_series, traced_series):
        assert series.labels("sink", "vote-intake").get_count() == 5
        assert series.labels("sink", "handler").get_count() == 0
        # One decode a chunk read; a chunk held at least one batch.
        assert 1 <= series.labels("sink", "decode").get_count() <= 5
    spans = list(tracer.spans)
    receives = [s for s in spans if s.cat == "receive"]
    assert len(receives) == 5
    assert all(s.name.startswith("receive:AckColumns@") for s in receives)
    stages = [s for s in spans if s.name == "stage:vote-intake"]
    assert sorted(s.parent_id for s in stages) == sorted(
        s.span_id for s in receives)


def test_the_loops_selector_times_every_wait_as_loop_wait():
    """``_TimedSelector``: one ``loop-wait`` scope a select, on the
    injected clock; with no metrics attached it is the plain selector.
    A stage opened between two waits and the waits add up to the
    clock's whole reading: busy and waiting are told apart."""
    import types

    from frankenpaxos_tpu.obs import RuntimeMetrics, VirtualClock
    from frankenpaxos_tpu.runtime import FakeCollectors
    from frankenpaxos_tpu.runtime.tcp_transport import _TimedSelector

    collectors = FakeCollectors()
    clock = VirtualClock(tick_s=1.0)
    transport = types.SimpleNamespace(runtime_metrics=None)
    selector = _TimedSelector(transport)
    try:
        assert selector.select(0) == []
        assert clock.now == 0.0
        transport.runtime_metrics = metrics = RuntimeMetrics(
            collectors, "r0", clock=clock)
        series = collectors.metrics["fpx_runtime_drain_stage_seconds"]
        wait = series.labels("r0", "loop-wait")
        assert selector.select(0) == [] and selector.select(0.001) == []
        assert (wait.get_count(), wait.get_sum()) == (2, 2.0)
        with metrics.stage("handler"):
            pass
        assert selector.select(0) == []
        assert (wait.get_count(), wait.get_sum()) == (3, 3.0)
        assert series.labels("r0", "handler").get_sum() == 1.0
        # Eight readings, four scopes: between a scope's two readings
        # lies a tick, and between scopes another that no stage holds.
        assert clock.now == 8.0
    finally:
        selector.close()


def test_a_peer_that_closed_is_dropped_before_the_next_send(transports):
    """An outbound connection whose peer went away is dropped when the
    peer closes it, not at the next write, so that the first message to
    the peer's next life is not the one that finds the loss (when a whole
    tier restarts at once no survivor covers that message)."""
    server_addr = ("127.0.0.1", free_port())
    client_addr = ("127.0.0.1", free_port())
    client_t = transports(client_addr)
    logger = FakeLogger()
    client = EchoClient(client_addr, client_t, logger, server_addr)
    got: list = []

    def life(word: str) -> TcpTransport:
        server_t = TcpTransport(server_addr, FakeLogger())
        server_t.start()
        EchoServer(server_addr, server_t, logger)
        client_t.loop.call_soon_threadsafe(client.echo, word, got.append)
        assert wait_for(lambda: word in got), got
        return server_t

    first = life("to the first life")
    conn = client_t._conn_for(client_addr, server_addr)
    assert conn.writer is not None
    first.stop()
    # Nothing is sent meanwhile: the close alone drops the connection.
    assert wait_for(lambda: conn.writer is None)
    life("to the second life").stop()
    assert got == ["to the first life", "to the second life"]
    assert not any("write failed" in m for _, m in logger.records)


def test_a_durable_role_writes_its_acks_before_it_compacts(transports):
    """A send waits in ``pending`` for the flush at the end of its loop
    pass, and a WAL compaction blocks the loop inside that pass: a
    durable role therefore pushes the acks its drain released to the
    wire (``flush_sends``) before it starts one, and its peer has them
    while the rewrite runs, not after it."""
    from frankenpaxos_tpu.protocols.echo import EchoReply
    from frankenpaxos_tpu.runtime import Actor
    from frankenpaxos_tpu.wal import (
        DurableRole,
        MemStorage,
        Wal,
        WalPromise,
        WalSnapshot,
    )

    got: list = []

    class DurableEcho(Actor, DurableRole):
        def __init__(self, address, transport, logger):
            super().__init__(address, transport, logger)
            # One 17-byte record a drain: the second drain compacts.
            self._wal_init(Wal(MemStorage(), compact_every_bytes=30))
            self.peer_had_the_ack = None

        def receive(self, src, message):
            self.wal.append(WalPromise(round=1))
            self._wal_send(src, EchoReply(msg=message.msg))

        def on_drain(self):
            self._wal_drain()

        def _wal_compact(self):
            # On the loop's thread, which answers nothing meanwhile;
            # the peer's loop is another thread.
            self.peer_had_the_ack = wait_for(lambda: len(got) == 2, 2.0)
            self.wal.compact(WalSnapshot(payload=b""), [])

    server_addr = ("127.0.0.1", free_port())
    client_addr = ("127.0.0.1", free_port())
    server_t = transports(server_addr)
    client_t = transports(client_addr)
    logger = FakeLogger()
    server = DurableEcho(server_addr, server_t, logger)
    client = EchoClient(client_addr, client_t, logger, server_addr)
    # The first answer opens the server's connection to the client.
    client_t.loop.call_soon_threadsafe(client.echo, "first", got.append)
    assert wait_for(lambda: got == ["first"])
    assert server.wal.metrics.compactions == 0
    client_t.loop.call_soon_threadsafe(client.echo, "second", got.append)
    assert wait_for(lambda: server.wal.metrics.compactions == 1)
    assert server.peer_had_the_ack is True
    assert got == ["first", "second"]

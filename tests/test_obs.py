"""paxtrace (obs/): context codec, deterministic sim traces against a
golden file, flight-recorder crash survival, Perfetto export, critical
paths, frame-layer propagation over real TCP, and the metrics-only
stage path."""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from frankenpaxos_tpu.obs import (
    FlightRecorder,
    latency_breakdown,
    RuntimeMetrics,
    to_chrome_trace,
    trace_tree,
    TraceContext,
    Tracer,
    VirtualClock,
)
from frankenpaxos_tpu.obs.trace import stage_scope
from frankenpaxos_tpu.protocols.echo import EchoClient, EchoServer
from frankenpaxos_tpu.runtime import (
    Actor,
    FakeCollectors,
    FakeLogger,
    LogLevel,
    SimTransport,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "sim_echo_trace.json")


class TestTraceContext:
    def test_encode_decode_round_trip(self):
        ctx = TraceContext(trace_id=0x2ECAC21000000001,
                           span_id=0xDEADBEEF00000007, sampled=True)
        assert TraceContext.decode(ctx.encode()) == ctx
        off = TraceContext(trace_id=1, span_id=2, sampled=False)
        assert TraceContext.decode(off.encode()) == off

    def test_encode_avoids_header_separators(self):
        ctx = TraceContext(trace_id=2**64 - 1, span_id=0, sampled=True)
        assert ":" not in ctx.encode()
        assert "|" not in ctx.encode()

    def test_decode_garbage_is_none(self):
        assert TraceContext.decode("") is None
        assert TraceContext.decode("nope") is None
        assert TraceContext.decode("xx.yy.1") is None
        assert TraceContext.decode("1.2") is None
        assert TraceContext.decode("1.2.1.3.4.leader.5.6") is None
        assert TraceContext.decode("1.2.1.x.4.leader.5.6.7") is None

    def test_a_commit_path_round_trips_without_its_local_fields(self):
        """The path's instants (integer nanoseconds on the wire), the
        sender's kind and the command's identity cross the header; the
        holder's own ``came_from`` / ``arrived_s`` do not."""
        path = TraceContext(trace_id=0xABC, span_id=0, sampled=True,
                            root_s=1234.5, sent_s=1234.75,
                            kind="proxy_leader", pseudonym=3,
                            client_id=17, slot=-1)
        text = path.encode()
        assert ":" not in text and "|" not in text
        assert TraceContext.decode(text) == path
        held = TraceContext.decode(dataclasses.replace(
            path, came_from="leader", arrived_s=99.0).encode())
        assert held == path and held.came_from == ""
        onward = path.onward("acceptor", 41)
        assert (onward.kind, onward.slot, onward.root_s) == (
            "acceptor", 41, 1234.5)
        assert onward.onward("replica").slot == 41


class TestTracer:
    def test_sampling_one_in_n_at_roots(self):
        tracer = Tracer(role="r", clock=VirtualClock(),
                        sample_rate=0.25)
        sampled = []
        for _ in range(8):
            with tracer.receive_span("a", "M", None) as span:
                sampled.append(span.ctx.sampled)
        assert sampled == [True, False, False, False] * 2

    def test_propagated_context_keeps_root_decision(self):
        tracer = Tracer(role="r", clock=VirtualClock(),
                        sample_rate=0.0)
        ctx = TraceContext(trace_id=9, span_id=1, sampled=True)
        with tracer.receive_span("a", "M", ctx) as span:
            assert span.ctx.sampled
            assert span.ctx.trace_id == 9
        assert tracer.spans  # recorded despite local rate 0

    def test_unsampled_spans_record_nothing(self):
        tracer = Tracer(role="r", clock=VirtualClock(),
                        sample_rate=0.0)
        with tracer.receive_span("a", "M", None):
            pass
        with tracer.drain_span("a"):
            pass
        assert tracer.spans == []

    def test_drain_parent_is_per_actor(self):
        """Colocated actors share one tracer (sims, supernode): actor
        A's drain must adopt A's last sampled receive, never B's, and
        B's drain still gets its own."""
        tracer = Tracer(role="r", clock=VirtualClock())
        with tracer.receive_span("A", "M", None) as ra:
            pass
        with tracer.receive_span("B", "M", None) as rb:
            pass
        with tracer.drain_span("A") as da:
            assert da.parent_id == ra.ctx.span_id
            assert da.ctx.trace_id == ra.ctx.trace_id
        with tracer.drain_span("B") as db:
            assert db.parent_id == rb.ctx.span_id
            assert db.ctx.trace_id == rb.ctx.trace_id

    def test_instance_salt_separates_incarnations(self):
        """A relaunched role (same name, new pid) must not regenerate
        the dead incarnation's ids into the appended trace file."""
        life1 = Tracer(role="acceptor_1", clock=VirtualClock(),
                       instance=1234)
        life2 = Tracer(role="acceptor_1", clock=VirtualClock(),
                       instance=5678)
        ids1 = {life1._new_id() for _ in range(50)}
        ids2 = {life2._new_id() for _ in range(50)}
        assert not ids1 & ids2
        # Default instance (sims) keeps the golden-traced salt.
        assert Tracer(role="sim")._salt == \
            Tracer(role="sim", instance=0)._salt

    def test_sampling_does_not_starve_runtime_metrics(self):
        """With a sampling tracer attached, the fpx_runtime_* stage
        histograms must still see EVERY stage, not 1-in-N -- the
        Grafana row charts all fsyncs."""
        collectors = FakeCollectors()
        metrics = RuntimeMetrics(collectors, "r0")
        tracer = Tracer(role="r0", clock=VirtualClock(),
                        sample_rate=0.0, runtime_metrics=metrics)
        for _ in range(5):
            with tracer.receive_span("a", "M", None):
                with tracer.stage("wal-fsync"):
                    pass
        assert tracer.spans == []  # nothing sampled...
        fsync = collectors.metrics["fpx_runtime_wal_fsync_seconds"]
        assert fsync.labels("r0").get_count() == 5  # ...all observed

    def test_current_context_restored_on_exit(self):
        tracer = Tracer(role="r", clock=VirtualClock())
        assert tracer.current is None
        with tracer.receive_span("a", "M", None) as outer:
            assert tracer.current is outer.ctx
            with tracer.stage("handler") as inner:
                assert tracer.current is inner.ctx
            assert tracer.current is outer.ctx
        assert tracer.current is None


def traced_echo_spans(payloads):
    logger = FakeLogger()
    transport = SimTransport(logger)
    EchoServer("server", transport, logger)
    client = EchoClient("client", transport, logger, "server")
    transport.tracer = Tracer(role="sim", clock=VirtualClock())
    for payload in payloads:
        client.echo(payload)
    transport.deliver_all()
    return transport.tracer.spans


class TestDeterministicSimTrace:
    def test_echo_trace_matches_golden(self):
        """THE golden test: the sim's virtual clock + counter ids make
        a trace a pure function of the command sequence; any change to
        span structure, parenting, ids, or timing shows up here as a
        diff against the committed golden file."""
        spans = [s.to_json() for s in traced_echo_spans(["one", "two"])]
        with open(GOLDEN) as f:
            golden = json.load(f)
        assert spans == golden

    def test_trace_is_reproducible_across_fresh_harnesses(self):
        a = [s.to_json() for s in traced_echo_spans(["x", "y", "z"])]
        b = [s.to_json() for s in traced_echo_spans(["x", "y", "z"])]
        assert a == b

    def test_multipaxos_coalesced_trace_deterministic(self):
        """The full coalesced multipaxos pipeline traces
        deterministically too (drain spans, wal-less): two fresh
        harnesses, identical span dumps."""
        from tests.protocols.multipaxos_harness import make_multipaxos

        def run():
            sim = make_multipaxos(f=1, coalesced=True)
            sim.transport.tracer = Tracer(role="sim",
                                          clock=VirtualClock())
            results: list = []
            for wave in range(3):
                for p in range(4):
                    sim.clients[0].write(p, b"v%d.%d" % (wave, p),
                                         results.append)
                sim.clients[0].flush_writes()
                sim.transport.deliver_all_coalesced()
            assert len(results) == 12
            return [s.to_json() for s in sim.transport.tracer.spans]

        first, second = run(), run()
        assert first == second
        # The pipeline's drain stages actually appear.
        names = {row["name"] for row in first}
        assert any(n.startswith("stage:handler") for n in names)
        assert any(n.startswith("drain@") for n in names)

    def test_end_to_end_trace_crosses_roles(self):
        """A sampled client command's trace id reaches the replica's
        drain and the reply's receive back at the client."""
        from tests.protocols.multipaxos_harness import make_multipaxos

        sim = make_multipaxos(f=1, coalesced=True)
        tracer = Tracer(role="sim", clock=VirtualClock())
        sim.transport.tracer = tracer
        results: list = []
        sim.clients[0].write(0, b"cmd", results.append)
        sim.clients[0].flush_writes()
        sim.transport.deliver_all_coalesced()
        assert results
        receives = [s for s in tracer.spans if s.cat == "receive"]
        root_traces = {s.trace_id for s in receives
                       if s.parent_id == 0}
        # The client's initial send had no context: exactly the write
        # (plus any timer-born traces) roots here; its trace must span
        # multiple actors end to end.
        assert root_traces
        main = max(root_traces,
                   key=lambda t: sum(1 for s in tracer.spans
                                     if s.trace_id == t))
        actors = {s.name.rpartition("@")[2] for s in tracer.spans
                  if s.trace_id == main and s.cat == "receive"}
        assert len(actors) >= 3, actors  # leader, acceptor, replica...


#: The commit path of one write in the deployment's shape: the eleven
#: segments in chain order, then the replica's whole path, then (in a
#: sim, whose roles share one RuntimeMetrics) the client's own hop.
CHAIN = ["client-wait", "hop-client-wait", "hold-client-wait",
         "hop-leader-wait", "hold-leader-wait",
         "hop-proxy_leader-wait", "hold-proxy_leader-wait",
         "hop-acceptor-wait", "hold-acceptor-wait",
         "hop-proxy_leader-wait", "hold-proxy_leader-wait"]


class TestCommitPath:
    """obs/trace.py's commit path through the coalesced MultiPaxos
    pipeline on SimTransport, under a VirtualClock."""

    @staticmethod
    def pipeline(monkeypatch, num_clients: int = 1):
        """A settled pipeline whose client samples every write it may,
        and the (stage, seconds) observations in the order made."""
        from frankenpaxos_tpu.protocols.multipaxos.client import Client
        from tests.protocols.multipaxos_harness import make_multipaxos

        monkeypatch.setattr(Client, "PATH_EVERY", 1)
        sim = make_multipaxos(f=1, coalesced=True, num_clients=num_clients)
        sim.transport.deliver_all_coalesced()  # Phase1
        sim.transport.path_clock = VirtualClock()
        metrics = RuntimeMetrics(FakeCollectors(), "sim")
        seen: list = []
        observe = metrics.observe_stage

        def record(stage: str, dur_s: float) -> None:
            seen.append((stage, dur_s))
            observe(stage, dur_s)

        metrics.observe_stage = record
        sim.transport.runtime_metrics = metrics
        return sim, seen

    def test_one_write_telescopes_to_its_path(self, monkeypatch):
        sim, seen = self.pipeline(monkeypatch)
        results: list = []
        sim.clients[0].write(0, b"cmd", results.append)
        sim.clients[0].flush_writes()
        sim.transport.deliver_all_coalesced()
        assert results
        assert [stage for stage, _ in seen] == \
            CHAIN + ["path-wait", "hop-replica-wait"]
        assert all(dur > 0 for _, dur in seen)
        chain = [dur for _, dur in seen[:len(CHAIN)]]
        assert sum(chain) == pytest.approx(seen[len(CHAIN)][1],
                                           rel=1e-12)

    def test_a_client_samples_no_write_while_one_is_outstanding(
            self, monkeypatch):
        sim, seen = self.pipeline(monkeypatch)
        client = sim.clients[0]
        for p in range(4):
            client.write(p, b"v%d" % p)
        client.flush_writes()
        sim.transport.deliver_all_coalesced()
        assert [s for s, _ in seen].count("path-wait") == 1
        # Answered: the next write is sampled again.
        client.write(0, b"again")
        client.flush_writes()
        sim.transport.deliver_all_coalesced()
        assert [s for s, _ in seen].count("path-wait") == 2

    def test_two_paths_that_meet_in_one_frame_leave_one_chain(
            self, monkeypatch):
        """Two sampled writes in one array (the client is made to
        sample the second while the first is outstanding): the frame
        carries one context, so one chain goes on whole and the other
        ends at the client."""
        sim, seen = self.pipeline(monkeypatch)
        client = sim.clients[0]
        client.write(0, b"first")
        client._path_write = (None, None)
        client.write(1, b"second")
        client.flush_writes()
        sim.transport.deliver_all_coalesced()
        assert [stage for stage, _ in seen] == \
            CHAIN + ["path-wait", "hop-replica-wait"]
        chain = [dur for _, dur in seen[:len(CHAIN)]]
        assert sum(chain) == pytest.approx(seen[len(CHAIN)][1],
                                           rel=1e-12)

    def test_an_unsampled_write_carries_no_context(self):
        """No sample: no context object is made and none rides any
        frame, and no clock is read for it."""
        from tests.protocols.multipaxos_harness import make_multipaxos

        sim = make_multipaxos(f=1, coalesced=True)
        sim.transport.deliver_all_coalesced()
        reads = []
        sim.transport.path_clock = lambda: reads.append(1) or 0.0
        sent: list = []
        send = sim.transport.send

        def spy(src, dst, data):
            send(src, dst, data)
            sent.append(sim.transport.messages[-1].trace)

        sim.transport.send = spy
        client = sim.clients[0]
        for n in range(client.PATH_EVERY - 1):
            client._path_gap = client.PATH_EVERY  # never reaches 0 here
            client.write(n % 4, b"w%d" % n)
            client.flush_writes()
            sim.transport.deliver_all_coalesced()
        assert sent and all(trace is None for trace in sent)
        assert not reads and client._path_write == (None, None)


class TestFlightRecorder:
    def test_ring_wraps_and_orders(self):
        ring = FlightRecorder(slots=4, slot_size=64)
        for i in range(10):
            ring.record(float(i), f"event {i}")
        got = ring.records()
        assert [seq for seq, _, _ in got] == [7, 8, 9, 10]
        assert [text for _, _, text in got] == [
            "event 6", "event 7", "event 8", "event 9"]

    def test_mmap_ring_survives_abandonment(self, tmp_path):
        """The SIGKILL contract in miniature: write records, DROP the
        object without close/flush, read the file back cold."""
        path = str(tmp_path / "role.flight")
        ring = FlightRecorder(path, slots=8, slot_size=64)
        for i in range(5):
            ring.record(i * 0.5, f"act {i}")
        del ring  # no close(): the crash
        got = FlightRecorder.read(path)
        assert [text for _, _, text in got] == [
            f"act {i}" for i in range(5)]
        assert got[2][1] == pytest.approx(1.0)

    def test_restart_reuses_ring_and_keeps_crash_records(self,
                                                        tmp_path):
        path = str(tmp_path / "role.flight")
        ring = FlightRecorder(path, slots=8, slot_size=64)
        ring.record(1.0, "before crash")
        del ring
        again = FlightRecorder(path, slots=8, slot_size=64)
        again.record(2.0, "after restart")
        got = FlightRecorder.read(path)
        assert [text for _, _, text in got] == [
            "before crash", "after restart"]
        assert [seq for seq, _, _ in got] == [1, 2]

    def test_long_text_truncates_not_corrupts(self, tmp_path):
        path = str(tmp_path / "role.flight")
        ring = FlightRecorder(path, slots=2, slot_size=48)
        ring.record(0.0, "x" * 500)
        ring.record(1.0, "short")
        got = FlightRecorder.read(path)
        assert len(got) == 2
        assert len(got[0][2]) == 48 - 18  # slot minus record header
        assert got[1][2] == "short"

    def test_dump_file_writes_post_mortem_json(self, tmp_path):
        path = str(tmp_path / "role.flight")
        ring = FlightRecorder(path, slots=4, slot_size=64)
        ring.record(0.25, "hello")
        ring.close()
        out = str(tmp_path / "post.json")
        dump = FlightRecorder.dump_file(path, out)
        assert dump["records"][0]["text"] == "hello"
        with open(out) as f:
            assert json.load(f) == dump

    def test_read_rejects_garbage(self, tmp_path):
        path = str(tmp_path / "bad.flight")
        with open(path, "wb") as f:
            f.write(b"not a flight ring")
        with pytest.raises(ValueError):
            FlightRecorder.read(path)

    def test_tracer_feeds_flight(self):
        ring = FlightRecorder(slots=16, slot_size=128)
        tracer = Tracer(role="r", clock=VirtualClock(), flight=ring)
        with tracer.receive_span("a", "M", None):
            pass
        tracer.event("recovered 12 records")
        texts = [text for _, _, text in ring.records()]
        assert any("receive:M@a" in t for t in texts)
        assert any("event recovered 12 records" in t for t in texts)


class TestPerfettoExport:
    def test_chrome_trace_shape(self):
        spans = traced_echo_spans(["one"])
        trace = to_chrome_trace(spans)
        events = trace["traceEvents"]
        complete = [e for e in events if e["ph"] == "X"]
        meta = [e for e in events if e["ph"] == "M"]
        assert len(complete) == len(spans)
        assert meta and meta[0]["args"]["name"] == "sim"
        for event in complete:
            assert event["ts"] >= 0 and event["dur"] > 0
            assert len(event["args"]["trace_id"]) == 16
        # Valid JSON end to end.
        json.loads(json.dumps(trace))

    def test_latency_breakdown_buckets_by_stage(self):
        spans = traced_echo_spans(["one", "two"])
        table = latency_breakdown(spans)
        assert set(table) == {"decode", "handler", "receive", "drain"}
        assert table["decode"]["count"] == 4
        assert table["receive"]["mean_us"] == pytest.approx(5.0)

    def test_trace_tree_critical_path(self):
        spans = traced_echo_spans(["one", "two"])
        trace_id = spans[0].trace_id
        tree = trace_tree(spans, trace_id)
        path = tree["critical_path"]
        assert path[0].cat == "receive"  # the root
        # The path ends at the command's latest consequence: the
        # client-side drain after the reply.
        assert path[-1].name == "drain@client"

    def test_jsonl_round_trip(self, tmp_path):
        from frankenpaxos_tpu.obs import load_jsonl

        spans = traced_echo_spans(["one"])
        path = str(tmp_path / "t.trace.jsonl")
        logger = FakeLogger()
        transport = SimTransport(logger)
        transport.tracer = Tracer(role="sim", clock=VirtualClock())
        transport.tracer.spans = spans
        transport.tracer.dump_jsonl(path)
        # A torn final line (chaos kill mid-write) must not poison the
        # loader.
        with open(path, "a") as f:
            f.write('{"name": "torn')
        back = load_jsonl(path)
        assert [s.to_json() for s in back] == [s.to_json()
                                               for s in spans]


class TestTcpPropagation:
    def test_trace_context_crosses_real_tcp(self):
        """Frame-layer propagation end to end: server receive roots a
        trace; the reply's receive at the client carries the SAME
        trace id -- the context rode the ``host:port|ctx`` header, not
        any codec."""
        from frankenpaxos_tpu.bench.harness import free_port
        from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

        logger = FakeLogger(LogLevel.FATAL)
        saddr = ("127.0.0.1", free_port())
        caddr = ("127.0.0.1", free_port())
        ts = TcpTransport(saddr, logger)
        tc = TcpTransport(caddr, logger)
        ts.tracer = Tracer(role="server")
        tc.tracer = Tracer(role="client")
        ts.start()
        tc.start()
        try:
            EchoServer(saddr, ts, logger)
            client = EchoClient(caddr, tc, logger, saddr)
            done = threading.Event()
            tc.loop.call_soon_threadsafe(
                client.echo, "hello", lambda _: done.set())
            assert done.wait(15), "echo never completed"
            deadline = 50
            while deadline and not any(
                    s.cat == "receive" for s in tc.tracer.spans):
                import time as _t
                _t.sleep(0.1)
                deadline -= 1
            server_recv = [s for s in ts.tracer.spans
                           if s.cat == "receive"]
            client_recv = [s for s in tc.tracer.spans
                           if s.cat == "receive"]
            assert server_recv and client_recv
            assert server_recv[0].parent_id == 0  # root at the edge
            assert client_recv[0].trace_id == server_recv[0].trace_id
            assert client_recv[0].parent_id != 0
        finally:
            ts.stop()
            tc.stop()


class _PathRecorder(Actor):
    """Keeps the commit path each delivery came with."""

    def __init__(self, address, transport, logger):
        super().__init__(address, transport, logger)
        self.paths: list = []
        self.got = threading.Event()

    def receive(self, src, message) -> None:
        self.paths.append(self.transport.path_in)
        self.got.set()


#: A sender in a process of its own: one frame whose context is a
#: client's commit path, to the port in argv[1].
PATH_SENDER = """
import sys, time
from frankenpaxos_tpu.bench.harness import free_port
from frankenpaxos_tpu.obs.trace import TraceContext
from frankenpaxos_tpu.protocols.echo import EchoRequest
from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport
t = TcpTransport(("127.0.0.1", free_port()), FakeLogger(LogLevel.FATAL))
t.start()
t.path_out = TraceContext(trace_id=7, span_id=0, sampled=True,
                          root_s=time.monotonic(), kind="client",
                          pseudonym=2, client_id=5)
t.send(t.listen_address, ("127.0.0.1", int(sys.argv[1])),
       DEFAULT_SERIALIZER.to_bytes(EchoRequest(msg="x")))
time.sleep(1.0)
t.stop()
"""


class TestCommitPathOverTcp:
    def test_a_path_header_round_trips_and_is_stamped_at_the_write(self):
        from frankenpaxos_tpu.bench.harness import free_port
        from frankenpaxos_tpu.protocols.echo import EchoRequest
        from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
        from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

        logger = FakeLogger(LogLevel.FATAL)
        raddr = ("127.0.0.1", free_port())
        receiver = TcpTransport(raddr, logger)
        sender = TcpTransport(("127.0.0.1", free_port()), logger)
        receiver.start()
        sender.start()
        try:
            recorder = _PathRecorder(raddr, receiver, logger)
            path = TraceContext(trace_id=9, span_id=0, sampled=True,
                                root_s=time.monotonic(), kind="leader",
                                pseudonym=4, client_id=8, slot=77,
                                came_from="client",
                                arrived_s=time.monotonic())
            sender.path_out = path
            before = time.monotonic()
            sender.send(sender.listen_address, raddr,
                        DEFAULT_SERIALIZER.to_bytes(EchoRequest(msg="a")))
            assert sender.path_out is None  # the send took it
            assert recorder.got.wait(10)
            (got,) = recorder.paths
            assert got is not None and receiver.path_in is None
            assert (got.trace_id, got.kind, got.pseudonym, got.client_id,
                    got.slot) == (9, "leader", 4, 8, 77)
            assert got.root_s == pytest.approx(path.root_s, abs=1e-9)
            assert before <= got.sent_s <= got.arrived_s
            assert got.came_from == "leader"
        finally:
            receiver.stop()
            sender.stop()

    def test_an_unsampled_frame_is_todays_frame(self):
        """A plain send's bytes on the wire are exactly the frame the
        transport wrote before the commit path: ``host:port`` and the
        payload, no context."""
        import socket

        from frankenpaxos_tpu.bench.harness import free_port
        from frankenpaxos_tpu.runtime.tcp_transport import (
            _encode_frame,
            TcpTransport,
        )

        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        dst = listener.getsockname()
        sender = TcpTransport(("127.0.0.1", free_port()),
                              FakeLogger(LogLevel.FATAL))
        sender.start()
        try:
            data = b"\x05plain payload"
            sender.send(sender.listen_address, dst, data)
            conn, _ = listener.accept()
            conn.settimeout(10)
            want = _encode_frame(sender.listen_address, data)
            got = b""
            while len(got) < len(want):
                got += conn.recv(65536)
            assert got == want
            assert b"|" not in got
            conn.close()
        finally:
            sender.stop()
            listener.close()

    def test_a_path_frame_keeps_the_batch_it_sits_in_whole(self):
        """A sampled message between plain ones of the same type leaves
        the one batch frame the flush makes without it, under the
        path's header: batching, and so the runs a leader proposes,
        do not change with sampling."""
        import socket
        import struct

        from frankenpaxos_tpu.bench.harness import free_port
        from frankenpaxos_tpu.protocols.echo import EchoRequest
        from frankenpaxos_tpu.runtime import paxwire
        from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
        from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

        listener = socket.socket()
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        dst = listener.getsockname()
        sender = TcpTransport(("127.0.0.1", free_port()),
                              FakeLogger(LogLevel.FATAL))
        sender.start()
        datas = [DEFAULT_SERIALIZER.to_bytes(EchoRequest(msg=m))
                 for m in ("a", "b", "c")]
        path = TraceContext(trace_id=1, span_id=0, sampled=True,
                            root_s=time.monotonic(), kind="leader",
                            pseudonym=0, client_id=0, slot=5)
        try:
            sender.send(sender.listen_address, dst, b"\x05warm")
            conn, _ = listener.accept()
            conn.settimeout(10)
            conn.recv(65536)
            done = threading.Event()

            def one_pass():
                for k, data in enumerate(datas):
                    if k == 1:
                        sender.path_out = path
                    sender.send(sender.listen_address, dst, data)
                done.set()

            sender.loop.call_soon_threadsafe(one_pass)
            assert done.wait(10)
            plain = paxwire.plan_flush([(b"h", d) for d in datas])
            payload = b"".join(plain.segments)[9:]  # less len, hlen, "h"
            got = b""
            while len(got) < 8 or len(got) < 4 + struct.unpack_from(
                    ">I", got)[0]:
                got += conn.recv(65536)
            inner, hlen = struct.unpack_from(">II", got)
            assert len(got) == 4 + inner  # one frame
            header = got[8:8 + hlen].decode()
            ctx = TraceContext.decode(header.partition("|")[2])
            assert (ctx.kind, ctx.slot) == ("leader", 5)
            assert got[8 + hlen:] == payload
            conn.close()
        finally:
            sender.stop()
            listener.close()

    def test_a_hop_between_two_processes_is_not_negative(self):
        """CLOCK_MONOTONIC is one clock for every process of the host:
        the hop from a sender process's write to this one's read, and
        the client's own pass, read at or above zero."""
        from frankenpaxos_tpu.bench.harness import free_port
        from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

        collectors = FakeCollectors()
        raddr = ("127.0.0.1", free_port())
        receiver = TcpTransport(raddr, FakeLogger(LogLevel.FATAL))
        receiver.runtime_metrics = RuntimeMetrics(collectors, "leader_0")
        receiver.start()
        try:
            recorder = _PathRecorder(raddr, receiver, receiver.logger)
            root = os.path.dirname(os.path.dirname(__file__))
            subprocess.run([sys.executable, "-c", PATH_SENDER,
                            str(raddr[1])], cwd=root, check=True,
                           timeout=60, env=dict(os.environ,
                                                PYTHONPATH=root))
            assert recorder.got.wait(10)
            stages = receiver.runtime_metrics.read_stages()
            for stage in ("hop-client-wait", "client-wait"):
                seconds, count = stages[("leader_0", stage)]
                assert count == 1 and seconds >= 0.0, stage
        finally:
            receiver.stop()


class TestMetricsOnlyStages:
    def test_stage_scope_feeds_histogram_without_tracer(self):
        collectors = FakeCollectors()
        metrics = RuntimeMetrics(collectors, "acceptor_0")
        with stage_scope(None, metrics, "wal-fsync"):
            pass
        hist = collectors.metrics["fpx_runtime_drain_stage_seconds"]
        assert hist.labels("acceptor_0", "wal-fsync").get_count() == 1
        fsync = collectors.metrics["fpx_runtime_wal_fsync_seconds"]
        assert fsync.labels("acceptor_0").get_count() == 1

    def test_stage_scope_noop_without_sinks(self):
        scope = stage_scope(None, None, "decode")
        with scope:
            pass
        from frankenpaxos_tpu.obs.trace import NOOP_SCOPE

        assert scope is NOOP_SCOPE

    def test_tracer_stages_feed_runtime_metrics(self):
        collectors = FakeCollectors()
        metrics = RuntimeMetrics(collectors, "r0")
        tracer = Tracer(role="r0", clock=VirtualClock(),
                        runtime_metrics=metrics)
        with tracer.receive_span("a", "M", None):
            with tracer.stage("handler"):
                pass
        hist = collectors.metrics["fpx_runtime_drain_stage_seconds"]
        assert hist.labels("r0", "handler").get_count() == 1

    def test_wal_drain_stages_via_actor(self, tmp_path):
        """A durable multipaxos sim with metrics attached observes
        real wal-fsync stage latencies through Actor.trace_stage."""
        from tests.protocols.multipaxos_harness import make_multipaxos

        sim = make_multipaxos(f=1, coalesced=True, wal=True)
        collectors = FakeCollectors()
        sim.transport.runtime_metrics = RuntimeMetrics(collectors,
                                                       "sim")
        results: list = []
        sim.clients[0].write(0, b"cmd", results.append)
        sim.clients[0].flush_writes()
        sim.transport.deliver_all_coalesced()
        assert results
        fsync = collectors.metrics["fpx_runtime_wal_fsync_seconds"]
        assert fsync.labels("sim").get_count() > 0


class TestStageAccounting:
    """One stage accounting: self time per thread in plain
    accumulators, the series made from them at scrape time, one clock
    pair a scope, annotations only where the chip is."""

    SERIES = "fpx_runtime_drain_stage_seconds"

    def make(self, role="r0", **kwargs):
        collectors = FakeCollectors()
        clock = VirtualClock(tick_s=1.0)
        metrics = RuntimeMetrics(collectors, role, clock=clock, **kwargs)
        return collectors.metrics[self.SERIES], clock, metrics

    def test_nested_stages_record_self_time(self):
        """Parent + children = the outer scope's duration: every tick
        of a VirtualClock between the outer enter and exit is in
        exactly one stage."""
        series, clock, metrics = self.make()
        with metrics.stage("handler"):
            t_in = clock.now
            with metrics.stage("log"):
                pass
            with metrics.stage("execute"):
                with metrics.stage("reply"):
                    pass
        outer = clock.now - t_in
        got = {stage: series.labels("r0", stage).get_sum()
               for stage in ("handler", "log", "execute", "reply")}
        assert got == {"handler": 3.0, "log": 1.0, "execute": 2.0,
                       "reply": 1.0}
        assert sum(got.values()) == outer == 7.0
        # A sibling opened after a closed scope has no parent left
        # over from it.
        with metrics.stage("flush"):
            pass
        assert series.labels("r0", "flush").get_sum() == 1.0
        assert series.labels("r0", "handler").get_sum() == 3.0

    def test_a_stage_reentered_on_its_thread_keeps_both_activations(self):
        """The scope object is reused, so one opened inside itself has
        to put the outer activation aside: both count, and the sum is
        the outer duration."""
        series, clock, metrics = self.make()
        with metrics.stage("fan-out"):
            t_in = clock.now
            with metrics.stage("fan-out"):
                pass
        child = series.labels("r0", "fan-out")
        assert (child.get_count(), child.get_sum()) == (2, 3.0)
        assert clock.now - t_in == 3.0

    def test_stages_of_two_threads_do_not_nest(self):
        """A helper thread has accumulators of its own: what it times
        subtracts nothing from the stage the event loop has open
        meanwhile, and both show under the one series."""
        series, _, metrics = self.make()

        def collect():
            stages = metrics.thread_stages()
            with stages.stage("collect"):
                pass
            stages.stage("dispatch-wait").add(5.0)

        with metrics.stage("drain"):
            worker = threading.Thread(target=collect)
            worker.start()
            worker.join(timeout=10)
            assert not worker.is_alive()
        assert series.labels("r0", "collect").get_sum() == 1.0
        assert series.labels("r0", "dispatch-wait").get_sum() == 5.0
        assert series.labels("r0", "drain").get_sum() == 3.0

    def test_a_collection_is_taken_out_of_the_stage_it_interrupted(self):
        """``gc`` nests like any scope: on the thread it stopped, its
        time leaves the open stage's self time. On a thread with no
        stages of its own it is counted all the same."""
        series, _, metrics = self.make()
        metrics.bind_loop_thread()
        with metrics.stage("execute"):
            metrics._on_gc("start", {"generation": 2})
            metrics._on_gc("stop", {"generation": 2})
        assert series.labels("r0", "gc").get_sum() == 1.0
        assert series.labels("r0", "execute").get_sum() == 2.0
        elsewhere = threading.Thread(target=lambda: (
            metrics._on_gc("start", {}), metrics._on_gc("stop", {})))
        elsewhere.start()
        elsewhere.join(timeout=10)
        assert not elsewhere.is_alive()
        child = series.labels("r0", "gc")
        assert (child.get_count(), child.get_sum()) == (2, 2.0)

    def test_watch_gc_counts_the_interpreters_own_collections(self):
        import gc

        series, _, metrics = self.make()
        metrics.bind_loop_thread()
        metrics.watch_gc()
        try:
            gc.collect()
            gc.collect(0)
        finally:
            gc.callbacks.remove(metrics._on_gc)
        assert series.labels("r0", "gc").get_count() == 2

    @pytest.mark.parametrize("backend", ["fake", "prometheus"])
    def test_the_scrape_time_series_equal_the_accumulators(self, backend):
        """``_sum`` and ``_count`` of every (role, stage) are read from
        the accumulators when scraped, with no observation per scope
        in between: a scrape after more scopes reads more."""
        from frankenpaxos_tpu.bench.metrics import parse_exposition
        from frankenpaxos_tpu.runtime.monitoring import (
            PrometheusCollectors,
        )

        if backend == "fake":
            collectors = FakeCollectors()

            def scrape():
                return {
                    f'{self.SERIES}_{part}{{role="{role}",'
                    f'stage="{stage}"}}': float(value)
                    for (role, stage), pair in
                    collectors.metrics[self.SERIES].read().items()
                    for part, value in zip(("sum", "count"), pair)}
        else:
            import prometheus_client

            registry = prometheus_client.CollectorRegistry()
            collectors = PrometheusCollectors(registry)

            def scrape():
                found = parse_exposition(
                    prometheus_client.generate_latest(registry).decode())
                return {name: value for name, value in found.items()
                        if name.startswith(self.SERIES)}

        metrics = RuntimeMetrics(collectors, "r0",
                                 clock=VirtualClock(tick_s=0.5))
        helper = metrics.thread_stages()
        assert scrape() == {}
        for _ in range(3):
            with metrics.stage("decode"):
                with metrics.stage("handler"):
                    pass
        with helper.stage("decode"):
            pass
        metrics.observe_stage("handback-wait", 0.25)

        def series(stage, seconds, count):
            labels = f'{{role="r0",stage="{stage}"}}'
            return {f"{self.SERIES}_sum{labels}": seconds,
                    f"{self.SERIES}_count{labels}": float(count)}

        assert scrape() == {**series("decode", 3 * 1.0 + 0.5, 4),
                            **series("handler", 3 * 0.5, 3),
                            **series("handback-wait", 0.25, 1)}
        assert metrics.read_stages() == {
            ("r0", "decode"): (3.5, 4), ("r0", "handler"): (1.5, 3),
            ("r0", "handback-wait"): (0.25, 1)}
        with metrics.stage("handler"):
            pass
        assert scrape()[f'{self.SERIES}_count{{role="r0",'
                        f'stage="handler"}}'] == 4.0

    def test_one_clock_pair_feeds_stage_and_summary(self):
        """``share_clock``: the open stage's own duration reaches the
        second series, whole (not self time), with no clock read of
        its own; a stage of another name, or one that already feeds a
        sink, declines, and the next scope of the stage feeds
        nothing."""
        collectors = FakeCollectors()
        clock = VirtualClock(tick_s=1.0)
        metrics = RuntimeMetrics(collectors, "r0", clock=clock)
        summary = collectors.summary("role_latency").labels("Phase2a")
        assert not metrics.share_clock("handler", summary)  # none open
        with metrics.stage("handler"):
            assert not metrics.share_clock("drain", summary)
            assert metrics.share_clock("handler", summary)
            assert not metrics.share_clock("handler", summary)
            reads = clock.now
            with metrics.stage("log"):
                pass
        assert clock.now - reads == 3.0  # log's pair and handler's exit
        series = collectors.metrics[self.SERIES]
        assert series.labels("r0", "handler").get_sum() == 2.0
        assert (summary.get_count(), summary.get_sum()) == (1, 3.0)
        with metrics.stage("handler"):
            pass
        assert summary.get_count() == 1

    def test_an_actor_times_its_receive_on_the_handler_stages_clock(self):
        """``Actor.receive_timer``: inside the transport's ``handler``
        scope the role's per-type summary costs no clock read and no
        ``labels()`` call a delivery; outside one it times itself."""
        from frankenpaxos_tpu.runtime import Actor

        class Role(Actor):
            def receive(self, src, message):
                with self.receive_timer(summary, message):
                    reads.append(clock.now)

        class CountingSummary(type(FakeCollectors().summary("x"))):
            lookups = 0

            def labels(self, *values):
                CountingSummary.lookups += 1
                return super().labels(*values)

        collectors = FakeCollectors()
        clock = VirtualClock(tick_s=1.0)
        transport = SimTransport(FakeLogger(LogLevel.FATAL))
        transport.runtime_metrics = RuntimeMetrics(collectors, "r0",
                                                   clock=clock)
        summary = CountingSummary()
        reads: list = []
        role = Role("role", transport, FakeLogger(LogLevel.FATAL))
        for _ in range(3):
            with transport.runtime_metrics.stage("handler"):
                before = clock.now
                role.receive("src", "a message")
            assert clock.now - before == 1.0  # the scope's exit alone
        child = summary.labels("str")
        assert (child.get_count(), child.get_sum()) == (3, 3.0)
        assert CountingSummary.lookups == 2  # the first delivery's, mine
        role.receive("src", "outside any handler scope")
        assert child.get_count() == 4

    def test_a_sampled_tracer_stage_is_the_same_scope(self):
        """Traced or not, a stage feeds the accumulators through the
        one self-time scope: a traced parent and its traced child, then
        the same pair unsampled."""
        series, _, metrics = self.make()
        tracer = Tracer(role="r0", clock=VirtualClock(),
                        runtime_metrics=metrics)
        with tracer.receive_span("a", "M", None):
            with tracer.stage("handler"):
                with tracer.stage("log"):
                    pass
        assert series.labels("r0", "handler").get_sum() == 2.0
        assert series.labels("r0", "log").get_sum() == 1.0
        names = [s.name for s in tracer.spans]
        assert names == ["stage:log", "stage:handler", "receive:M@a"]
        tracer.sample_every = 0
        with tracer.receive_span("a", "M", None):
            with tracer.stage("handler"):
                with tracer.stage("log"):
                    pass
        assert series.labels("r0", "handler").get_sum() == 4.0
        assert series.labels("r0", "log").get_count() == 2
        assert len(tracer.spans) == 3

    def test_a_stage_scope_imports_no_jax_without_a_claimed_device(
            self, monkeypatch):
        """A process that never claimed a device (every role but the
        chip owner) opens its stages, times its selector and accounts
        its collections with JAX's profiler out of reach; only
        ``device_clock=True`` asks for it."""
        import sys

        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        series, _, metrics = self.make("acceptor_0")
        with metrics.stage("handler"):
            with stage_scope(None, metrics, "flush"):
                metrics._on_gc("start", {})
                metrics._on_gc("stop", {})
        with metrics.loop_wait():
            pass
        with metrics.thread_stages().stage("collect"):
            pass
        assert series.labels("acceptor_0", "flush").get_count() == 1
        with pytest.raises(ImportError):
            RuntimeMetrics(FakeCollectors(), "proxy_leader_0_1",
                           device_clock=True)

    def test_fpx_annotations_exist_only_while_a_device_trace_runs(
            self, tmp_path):
        """On the chip owner (``device_clock=True``) a scope is also a
        profiler annotation ``fpx.<stage>``, built only while a trace
        runs: the loop's thread learns that once a selector wait, a
        helper thread when it refreshes."""
        import jax
        from jax.profiler import ProfileData

        series, _, metrics = self.make("proxy_leader_0_1",
                                       device_clock=True)
        helper = metrics.thread_stages()
        with metrics.loop_wait():
            pass
        assert metrics._loop.annotation is None
        with metrics.stage("drain") as scope:
            assert scope.span is None
        jax.profiler.start_trace(str(tmp_path))
        try:
            with metrics.stage("drain") as scope:
                assert scope.span is None  # not asked since
            with metrics.loop_wait():
                pass
            with metrics.stage("drain"):
                with metrics.stage("fan-out"):
                    pass
            helper.refresh()
            with helper.stage("collect"):
                pass
        finally:
            jax.profiler.stop_trace()
        with metrics.loop_wait():
            pass
        with metrics.stage("drain") as scope:
            assert scope.span is None
        assert series.labels("proxy_leader_0_1", "drain").get_count() == 4
        found = [os.path.join(base, name)
                 for base, _, names in os.walk(tmp_path)
                 for name in names if name.endswith(".xplane.pb")]
        assert len(found) == 1
        events = [event.name
                  for plane in ProfileData.from_file(found[0]).planes
                  for line in plane.lines for event in line.events]
        assert events.count("fpx.drain") == 1
        assert events.count("fpx.fan-out") == 1
        assert events.count("fpx.collect") == 1
        assert events.count("fpx.loop-wait") == 1


SERVED = {"quorum_backend": "tpu", "tpu_window": "4096",
          "coalesce_writes": "true"}


@pytest.fixture(scope="class")
def served():
    """The served MultiPaxos path at toy size, in-process over
    TcpTransport: tpu tracker (CPU XLA here), coalesced writes."""
    from tests.protocols.tcp_multipaxos import TcpMultiPaxos

    deployment = TcpMultiPaxos.launch(SERVED)
    try:
        deployment.closed_loops(1, 1)  # warm: the first commit
        yield deployment
    finally:
        deployment.stop()


class TestServedPathStages:
    #: Rounds of closed loops to try before giving up on acks arriving
    #: as column batches (one was enough in every run seen).
    ROUNDS = 50

    def test_each_stage_is_observed_once_per_unit_of_work(self, served):
        from frankenpaxos_tpu.runtime.paxwire import CONTROL_BATCH_TAG

        owner = served.collectors[served.OWNER].metrics
        requests = owner["multipaxos_proxy_leader_requests_total"]
        assert CONTROL_BATCH_TAG in served.actors[served.OWNER][0].wire_sinks
        # Until acks have arrived as column batches (the wire-sink
        # path), so that the test cannot pass on the slow path.
        for _ in range(self.ROUNDS):
            served.closed_loops(24, 10)
            if requests.labels("AckColumns").get() >= 3:
                break
        assert requests.labels("AckColumns").get() >= 3
        served.settle()

        # Read on the owner's loop, between deliveries.
        latency = owner["multipaxos_proxy_leader_requests_latency_seconds"]
        stages, delivered = served.on_loop(served.OWNER, lambda: (
            served.stage_counts(served.OWNER),
            sum(child.count for child in latency._children.values())))
        # vote-intake: one a sink batch or packed-votes message.
        assert stages["vote-intake"] == (
            requests.labels("AckColumns").get()
            + requests.labels("Phase2bVotes").get())
        # drain: one a tracker drain that had votes; each dispatched
        # once, waited once, collected once, on one clock pair with
        # the collect summary.
        drains = owner["multipaxos_proxy_leader_tpu_drains_total"]
        had_votes = drains.labels("device").get()
        dispatched = owner[
            "multipaxos_proxy_leader_tpu_dispatches_total"].get()
        collect = owner["multipaxos_proxy_leader_tpu_collect_seconds"]
        assert stages["drain"] == had_votes == dispatched
        assert stages["dispatch-wait"] == dispatched
        assert stages["collect"] == collect.get_count() == dispatched
        series = served.collectors[served.OWNER].metrics[
            "fpx_runtime_drain_stage_seconds"]
        assert series.labels(served.OWNER, "collect").get_sum() == \
            pytest.approx(collect.get_sum())
        # fan-out: one a hand-back (a collection that chose something),
        # and one a drain whose host side chose something.
        assert 0 < stages["handback-wait"] <= stages["collect"]
        assert stages["handback-wait"] <= stages["fan-out"] \
            <= stages["handback-wait"] + stages["drain"]
        assert "quorum-kernel" not in stages
        # handler: one a message, sharing its clock pair with the
        # role's per-type latency summary (so equal counts).
        assert stages["handler"] == delivered
        # A replica: log and execute once a ChosenRun, reply when it
        # executed something.
        for label in ("replica_0", "replica_1"):
            replica, runs = served.on_loop(label, lambda: (
                served.stage_counts(label),
                served.collectors[label].metrics[
                    "multipaxos_replica_requests_latency_seconds"].labels(
                        "ChosenRun").get_count()))
            assert replica["log"] == replica["execute"] == runs > 0
            assert 0 < replica["reply"] <= runs
            assert replica["handler"] >= runs
        # Every process: a decode a chunk read, a flush a pass that
        # sent, a loop-wait a selector call.
        for label in served.transports:
            counts = served.stage_counts(label)
            assert counts["loop-wait"] > 0 and counts["decode"] > 0, label
        for label in (served.OWNER, "replica_0", "acceptor_0", "client",
                      "leader_0"):
            assert served.stage_counts(label)["flush"] > 0, label

    def test_the_tracker_publishes_what_it_counts_and_one_path(
            self, served):
        """``BoardDriver.publish_counts``: the colocated trackers'
        drains, votes and launches, summed, are what ``/metrics``
        serves, every drain launched, and ``device`` is the only child of ``path`` (no host
        tally is left to publish one)."""
        served.closed_loops(8, 5)
        served.settle()
        owner = served.collectors[served.OWNER].metrics
        drains = owner["multipaxos_proxy_leader_tpu_drains_total"]
        votes = owner["multipaxos_proxy_leader_tpu_votes_total"]
        launches = owner["multipaxos_proxy_leader_tpu_launches_total"]
        published, counted = served.on_loop(served.OWNER, lambda: (
            (drains.labels("device").get(), votes.labels("device").get(),
             launches.get()),
            tuple(sum(getattr(proxy.tracker, name)
                      for proxy in served.actors[served.OWNER])
                  for name in ("device_drains", "device_votes",
                               "device_launches"))))
        assert published == counted
        assert counted[2] >= counted[0] > 0 and counted[1] >= counted[0]
        assert set(drains._children) == set(votes._children) == {
            ("device",)}

    def test_the_handler_stage_and_the_role_summary_share_a_clock(
            self, served):
        """The role's summaries ride the handler scope's clock pair and
        are given its WHOLE duration; the stages hold self time. An
        acceptor opens ``vote`` (and, for reads, ``max-slot``) inside
        its handlers, so the handler stage's self time plus theirs is
        the summaries' total."""
        served.closed_loops(4, 5)
        served.settle()
        acceptor = served.collectors["acceptor_0"].metrics
        latency = acceptor["multipaxos_acceptor_requests_latency_seconds"]
        stages = [acceptor["fpx_runtime_drain_stage_seconds"].labels(
            "acceptor_0", stage) for stage in ("handler", "vote",
                                                "max-slot")]
        # Read on the acceptor's loop, between deliveries.
        handlers, inside, summaries = served.on_loop("acceptor_0", lambda: (
            stages[0].get_count(),
            [(stage.get_count(), stage.get_sum()) for stage in stages],
            [(child.count, child.value)
             for child in latency._children.values()]))
        assert handlers == sum(count for count, _ in summaries) > 0
        assert inside[1][0] > 0                  # votes were cast
        assert sum(s for _, s in inside) == pytest.approx(
            sum(s for _, s in summaries))

    def test_every_segment_of_a_path_is_observed_once_per_sample(
            self, served):
        """Over real sockets, with frames batched as the flush batches
        them: each sampled write is seen once at every segment of its
        chain, whichever acceptor and replica it went through."""
        chain = [("leader_0", "client-wait"),
                 ("leader_0", "hop-client-wait"),
                 ("leader_0", "hold-client-wait"),
                 (served.OWNER, "hop-leader-wait"),
                 (served.OWNER, "hold-leader-wait"),
                 ("acceptor_*", "hop-proxy_leader-wait"),
                 ("acceptor_*", "hold-proxy_leader-wait"),
                 (served.OWNER, "hop-acceptor-wait"),
                 (served.OWNER, "hold-acceptor-wait"),
                 ("replica_*", "hop-proxy_leader-wait"),
                 ("replica_*", "hold-proxy_leader-wait"),
                 ("replica_*", "path-wait")]

        def counts() -> list:
            out = []
            for label, stage in chain:
                labels = ([label] if not label.endswith("*") else
                          [x for x in served.transports
                           if x.startswith(label[:-1])])
                out.append(sum(served.on_loop(
                    x, lambda: served.stage_counts(x)).get(stage, 0)
                    for x in labels))
            return out

        served.settle()
        before = counts()
        served.client.PATH_EVERY = 3
        try:
            served.closed_loops(16, 12)
            served.settle()
        finally:
            served.client.PATH_EVERY = type(served.client).PATH_EVERY
        grown = [b - a for a, b in zip(before, counts())]
        assert grown[0] > 0 and len(set(grown)) == 1, grown

    def test_a_collection_on_a_loops_thread_is_stage_gc(self, served):
        """``watch_gc`` in a served role: a collection that stops the
        replica's loop is counted there, under the replica's label."""
        import gc

        metrics = served.metrics["replica_0"]
        before = served.stage_counts("replica_0").get("gc", 0)
        metrics.watch_gc()
        try:
            assert served.on_loop("replica_0", gc.collect) is not None
        finally:
            gc.callbacks.remove(metrics._on_gc)
        assert served.stage_counts("replica_0")["gc"] > before


class TestWalSeries:
    """A write-ahead log's own counts and its two stages beside
    wal-fsync: on /metrics of a role that has a log, and absent from one
    that has none (obs._WalSeries, wal.DurableRole)."""

    SERIES = ("fpx_runtime_wal_synced_bytes_total",
              "fpx_runtime_wal_synced_records_total",
              "fpx_runtime_wal_compactions_total",
              "fpx_runtime_wal_compaction_seconds",
              "fpx_runtime_wal_recovered_records_total")
    STAGES = "fpx_runtime_drain_stage_seconds"

    def served(self, wal: bool, writes: int = 1):
        from tests.protocols.multipaxos_harness import make_multipaxos

        sim = make_multipaxos(f=1, coalesced=True, wal=wal)
        collectors = FakeCollectors()
        sim.transport.runtime_metrics = RuntimeMetrics(collectors, "sim")
        results: list = []
        for n in range(writes):
            sim.clients[0].write(0, b"cmd %d" % n, results.append)
            sim.clients[0].flush_writes()
            sim.transport.deliver_all_coalesced()
        assert len(results) == writes
        return sim, collectors

    def test_a_role_with_a_log_exports_its_counts(self):
        sim, collectors = self.served(wal=True)
        synced = sum(role.wal.metrics.bytes_synced
                     for role in (*sim.acceptors, *sim.replicas))
        records = sum(role.wal.metrics.records_synced
                      for role in (*sim.acceptors, *sim.replicas))
        assert synced > 0 and records > 0
        read = {name: collectors.metrics[name].labels("sim")
                for name in self.SERIES}
        assert read[self.SERIES[0]].get() == synced
        assert read[self.SERIES[1]].get() == records
        assert read[self.SERIES[2]].get() == 0
        stages = collectors.metrics[self.STAGES]
        # Every drain of a role with a log passes the check for a due
        # compaction; none recovered anything.
        assert stages.labels("sim", "wal-compact").get_count() > 0
        assert stages.labels("sim", "wal-recover").get_count() == 0

    def test_a_role_without_a_log_exports_none_of_them(self):
        _, collectors = self.served(wal=False)
        assert not set(self.SERIES) & set(collectors.metrics)
        stages = collectors.metrics[self.STAGES].read()
        assert stages and not {
            ("sim", "wal-compact"), ("sim", "wal-recover"),
            ("sim", "wal-fsync")} & set(stages)

    def test_a_compaction_is_counted_and_timed(self):
        sim, collectors = self.served(wal=True)
        roles = (*sim.acceptors, *sim.replicas)
        for role in roles:
            role.wal.compact_every_bytes = 1
        results: list = []
        sim.clients[0].write(0, b"one more", results.append)
        sim.clients[0].flush_writes()
        sim.transport.deliver_all_coalesced()
        done = sum(role.wal.metrics.compactions for role in roles)
        assert results and done > 0
        assert collectors.metrics[self.SERIES[2]].labels("sim").get() == done
        assert collectors.metrics[self.SERIES[3]].labels(
            "sim").get_count() == done
        # What a compaction wrote is in the synced bytes too.
        assert collectors.metrics[self.SERIES[0]].labels("sim").get() == sum(
            role.wal.metrics.bytes_synced for role in roles)

    def test_recovery_is_a_stage_and_a_count(self):
        from tests.protocols.multipaxos_harness import (
            crash_restart_acceptor,
            crash_restart_replica,
        )

        sim, collectors = self.served(wal=True, writes=3)
        crash_restart_acceptor(sim, 0)
        crash_restart_replica(sim, 0)
        replayed = (sim.acceptors[0].wal.metrics.recovered_records
                    + sim.replicas[0].wal.metrics.recovered_records)
        assert replayed > 0
        assert collectors.metrics[self.SERIES[4]].labels(
            "sim").get() == replayed
        stages = collectors.metrics[self.STAGES]
        assert stages.labels("sim", "wal-recover").get_count() == 2

"""A whole MultiPaxos deployment in one process over real sockets.

The shape ``cli.py`` gives a deployed cluster, without the processes:
every role instance on a ``TcpTransport`` (an event-loop thread) of its
own, the proxy leaders colocated on one as in a deployment with
``quorum_backend=tpu``, each transport with ``FakeCollectors`` and an
``obs.RuntimeMetrics`` attached the way the CLI attaches them. What
the stage tests drive.
"""

from __future__ import annotations

import socket
import threading
import time

from frankenpaxos_tpu.deploy import DeployCtx, get_protocol
from frankenpaxos_tpu.obs import RuntimeMetrics
from frankenpaxos_tpu.runtime import FakeCollectors, FakeLogger, LogLevel
from frankenpaxos_tpu.runtime.serializer import PickleSerializer
from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport
from frankenpaxos_tpu.statemachine import GetRequest, SetRequest

STAGE_SERIES = "fpx_runtime_drain_stage_seconds"


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TcpMultiPaxos:
    """f=1: 2 leaders, 2 proxy leaders on one transport, 3 acceptors, 2
    replicas, one client. ``collectors[label]`` holds a process's
    metrics, ``metrics[label]`` its RuntimeMetrics."""

    OWNER = "proxy_leader_0_1"

    def __init__(self, overrides: dict, tracer_for=None):
        """``tracer_for(label, metrics)`` may give a process's transport
        an ``obs.Tracer``."""
        self.protocol = get_protocol("multipaxos")
        raw = self.protocol.cluster(1, lambda: ["127.0.0.1", free_port()])
        self.config = self.protocol.load_config(raw)
        self.logger = FakeLogger(LogLevel.FATAL)
        self.overrides = overrides
        self.tracer_for = tracer_for
        self.transports: dict = {}
        self.collectors: dict = {}
        self.metrics: dict = {}
        self.actors: dict = {}
        self._serializer = PickleSerializer()
        try:
            for name in ("acceptor", "replica", "leader"):
                role = self.protocol.roles[name]
                for index, address in enumerate(role.addresses(self.config)):
                    self._host(f"{name}_{index}", address,
                               [(role, address, index)])
            role = self.protocol.roles["proxy_leader"]
            self._host(self.OWNER, None, [
                (role, address, index) for index, address in
                enumerate(role.addresses(self.config))])
            address = ("127.0.0.1", free_port())
            transport = self._transport("client", address)
            self.client = self.protocol.make_client(
                self._ctx(transport, "client"), address)
        except BaseException:
            self.stop()
            raise

    @classmethod
    def launch(cls, overrides: dict, attempts: int = 5, **kwargs):
        """A deployment that came up. Its two dozen ports are picked
        free and bound a moment later, so beside other tests one of
        them is now and then taken in between: such a deployment is
        stopped and another tried on new ports (as the benchmark's
        ``launch_with_retry`` does)."""
        for attempt in range(attempts):
            try:
                return cls(overrides, **kwargs)
            except (OSError, RuntimeError):
                if attempt == attempts - 1:
                    raise

    def _transport(self, label: str, address) -> TcpTransport:
        transport = TcpTransport(address, self.logger)
        self.collectors[label] = FakeCollectors()
        self.metrics[label] = transport.runtime_metrics = RuntimeMetrics(
            self.collectors[label], label)
        if self.tracer_for is not None:
            transport.tracer = self.tracer_for(label, self.metrics[label])
        self.transports[label] = transport
        transport.start()
        return transport

    def _ctx(self, transport: TcpTransport, label: str) -> DeployCtx:
        return DeployCtx(config=self.config, transport=transport,
                         logger=self.logger, overrides=self.overrides,
                         seed=len(self.transports),
                         state_machine="KeyValueStore",
                         collectors=self.collectors[label])

    def _host(self, label: str, listen, hosted: list) -> None:
        transport = self._transport(label, listen)
        if listen is None:
            for _, address, _ in hosted:
                transport.listen_on(address)
        ctx = self._ctx(transport, label)
        self.actors[label] = [role.make(ctx, address, index)
                              for role, address, index in hosted]

    def closed_loops(self, loops: int, writes_each: int,
                     timeout: float = 120.0) -> None:
        """``loops`` pseudonyms, each writing ``writes_each`` times,
        the next as soon as the last is acknowledged (the shape of the
        benchmark's generator): several runs in flight at once, so
        that an acceptor's acks of them share a frame. Returns when
        every write is acknowledged."""
        left = [loops]
        done = threading.Event()
        loop = self.transports["client"].loop

        def issue(pseudonym: int, n: int) -> None:
            if n == writes_each:
                left[0] -= 1
                if not left[0]:
                    done.set()
                return
            self.client.write(
                pseudonym, self._serializer.to_bytes(
                    SetRequest((("k", f"{pseudonym}.{n}"),))),
                lambda _: loop.call_soon(issue, pseudonym, n + 1))

        for pseudonym in range(loops):
            loop.call_soon_threadsafe(issue, pseudonym, 0)
        assert done.wait(timeout), f"{left[0]} loops never finished"

    def closed_read_loops(self, loops: int, reads_each: int,
                          timeout: float = 120.0) -> dict:
        """``loops`` pseudonyms, each reading key ``k`` linearizably
        ``reads_each`` times, the next as soon as the last is answered:
        the reads one pass issues travel as one batch. Returns
        ``{pseudonym: [the values it read]}`` once all are answered."""
        left = [loops]
        done = threading.Event()
        loop = self.transports["client"].loop
        command = self._serializer.to_bytes(GetRequest(("k",)))
        answers: dict = {p: [] for p in range(loops)}

        def issue(pseudonym: int) -> None:
            if len(answers[pseudonym]) == reads_each:
                left[0] -= 1
                if not left[0]:
                    done.set()
                return
            self.client.read(pseudonym, command,
                             lambda r: answered(pseudonym, r))

        def answered(pseudonym: int, result: bytes) -> None:
            answers[pseudonym].append(
                self._serializer.from_bytes(result).key_values[0][1])
            loop.call_soon(issue, pseudonym)

        for pseudonym in range(loops):
            loop.call_soon_threadsafe(issue, pseudonym)
        assert done.wait(timeout), f"{left[0]} loops never finished"
        return answers

    def on_loop(self, label: str, f, timeout: float = 30.0):
        """Run ``f()`` on a process's event loop, behind everything
        already posted to it, and wait for its result: the barrier the
        tests read counts behind."""
        done = threading.Event()
        out: list = []

        def run():
            out.append(f())
            done.set()

        self.transports[label].loop.call_soon_threadsafe(run)
        assert done.wait(timeout), f"{label}'s loop did not come round"
        return out[0]

    def settle(self, timeout: float = 60.0) -> None:
        """Every write is acknowledged; now let what is still in flight
        inside the chip owner land: each tracker's dispatches
        collected, and what the collector threads handed back run on
        the loops. Conditions, no sleeps: the collector queues drain, a
        callback posted to a loop runs behind the hand-backs, and a
        collector thread feeds the collect summary last of all."""
        owner = self.collectors[self.OWNER].metrics
        dispatched = owner["multipaxos_proxy_leader_tpu_dispatches_total"]
        collect = owner["multipaxos_proxy_leader_tpu_collect_seconds"]

        def collected() -> bool:
            return (all(proxy._collector.empty() and not proxy._collecting
                        for proxy in self.actors[self.OWNER])
                    and collect.get_count() == dispatched.get())

        deadline = time.monotonic() + timeout
        while not collected():
            assert time.monotonic() < deadline, "dispatches never collected"
            time.sleep(0.01)
        for label in self.transports:
            self.on_loop(label, lambda: None)

    def stage_counts(self, label: str) -> dict:
        """``{stage: observations}`` of one process, as a scrape would
        read them."""
        return {stage: count for (_, stage), (_, count) in
                self.collectors[label].metrics[STAGE_SERIES].read().items()}

    def stop(self) -> None:
        for transport in self.transports.values():
            transport.stop()

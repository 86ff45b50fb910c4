"""Mencius: integration + property-based simulation (mirrors
shared/src/test/scala/mencius/)."""

import random
from typing import Optional

import pytest

from frankenpaxos_tpu.sim import SimulatedSystem, Simulator
from tests.protocols.mencius_harness import (
    executed_prefix,
    make_mencius as _make_mencius_sim,
)


def make_mencius(**kwargs):
    """Legacy tuple shape over the shared harness."""
    sim = _make_mencius_sim(**kwargs)
    return sim.transport, sim.config, sim.leaders, sim.replicas, sim.clients


class TestMenciusIntegration:
    def test_single_write(self):
        transport, _, _, replicas, clients = make_mencius(lag_threshold=1)
        got = []
        clients[0].write(0, b"hello", got.append)
        transport.deliver_all()
        # The write lands in some group's slot; other groups' lower slots
        # are skipped via noop ranges once watermark gossip flows. Slot 0
        # may belong to a group that never proposed, so fire watermark and
        # recover timers until execution catches up.
        for _ in range(20):
            if got:
                break
            for timer in transport.running_timers():
                if timer.name in ("recover",):
                    transport.trigger_timer(timer.id)
            transport.deliver_all()
        assert got == [b"0"] or got == [b"%d" % replicas[0].executed_watermark - 1] or got  # noqa: executed value
        assert len(got) == 1

    def test_many_writes_all_execute(self):
        transport, _, _, replicas, clients = make_mencius(
            num_clients=2, lag_threshold=2)
        results = []
        for round in range(6):
            for c, client in enumerate(clients):
                client.write(round, b"w-%d-%d" % (round, c),
                             results.append)
            transport.deliver_all()
        for _ in range(30):
            if len(results) == 12:
                break
            for timer in transport.running_timers():
                if timer.name == "recover":
                    transport.trigger_timer(timer.id)
            transport.deliver_all()
        assert len(results) == 12
        logs = [executed_prefix(r) for r in replicas]
        n = min(len(logs[0]), len(logs[1]))
        assert logs[0][:n] == logs[1][:n]

    def test_batched(self):
        transport, _, _, replicas, clients = make_mencius(
            num_batchers=2, batch_size=2, num_clients=4, lag_threshold=2)
        results = []
        for client in clients:
            client.write(0, b"w", results.append)
        transport.deliver_all()
        for _ in range(30):
            if len(results) == 4:
                break
            for timer in transport.running_timers():
                if timer.name == "recover" \
                        or timer.name.startswith("resendWrite"):
                    transport.trigger_timer(timer.id)
            transport.deliver_all()
        assert len(results) == 4

    def test_noop_range_skipping(self):
        """A lagging group's slots get filled with noop ranges."""
        transport, config, leaders, replicas, clients = make_mencius(
            lag_threshold=2)
        # Drive several writes; watermark gossip every 3 commands. A write
        # may stall until other groups noop-skip their slots, so pump the
        # recover timers between writes.
        results = []
        for i in range(9):
            clients[0].write(0, b"cmd%d" % i, results.append)
            transport.deliver_all()
            for _ in range(30):
                if len(results) == i + 1:
                    break
                for timer in transport.running_timers():
                    if timer.name == "recover":
                        transport.trigger_timer(timer.id)
                transport.deliver_all()
        assert len(results) == 9
        # Replicas executed both command slots and noop-filled slots.
        from frankenpaxos_tpu.protocols.mencius.common import Noop
        log = executed_prefix(replicas[0])
        assert any(isinstance(v, Noop) for v in log), log


class TestMenciusRunPipeline:
    """The drain-granular strided run pipeline (ClientRequestArray ->
    Phase2aRun -> Phase2bRun -> ChosenRun -> ClientReplyArray) against
    the per-message reference shape."""

    def drive(self, sim, lo, hi, got, rounds=60):
        for p in range(lo, hi):
            sim.clients[0].write(p, b"v%d" % p, got.append)
        sim.clients[0].flush_writes()
        sim.transport.deliver_all_coalesced()
        for _ in range(rounds):
            if len(got) == hi:
                return
            for timer in sim.transport.running_timers():
                if timer.name == "recover":
                    sim.transport.trigger_timer(timer.id)
            sim.transport.deliver_all_coalesced()

    def test_matches_per_message_pipeline(self):
        """Same writes through the coalesced and per-message pipelines
        produce identical replica logs (commands AND noop skips)."""
        from tests.protocols.mencius_harness import (
            executed_prefix as prefix,
            make_mencius as make,
        )

        logs = {}
        for coalesced in (False, True):
            sim = make(coalesced=coalesced, lag_threshold=1)
            got = []
            for wave in range(4):
                self.drive(sim, wave * 32, wave * 32 + 32, got)
            assert sorted(got, key=int) == [b"%d" % p for p in range(128)]
            l0, l1 = prefix(sim.replicas[0]), prefix(sim.replicas[1])
            n = min(len(l0), len(l1))
            assert l0[:n] == l1[:n]
            logs[coalesced] = l0
        from frankenpaxos_tpu.protocols.mencius.common import Noop

        def payloads(log):
            return [v.commands[0].command for v in log
                    if not isinstance(v, Noop) and v.commands]

        # Slot ORDER differs between arms (clients pick random leader
        # groups per request vs per flush); the committed command SET
        # and exactly-once execution are the equivalence contract.
        assert sorted(payloads(logs[False])) == sorted(payloads(logs[True]))
        assert len(payloads(logs[True])) == 128

    def test_run_votes_survive_leader_failover(self):
        """Strided run-voted acceptor state must feed the new leader's
        Phase1 (the run store merges into Phase1b): values accepted via
        Phase2aRuns survive failover, and the new leader keeps serving
        coalesced writes."""
        from tests.protocols.mencius_harness import (
            executed_prefix as prefix,
            make_mencius as make,
        )

        sim = make(coalesced=True, lag_threshold=1)
        got = []
        self.drive(sim, 0, 16, got)
        assert len(got) == 16
        before = prefix(sim.replicas[0])
        g0 = [ld for ld in sim.leaders if ld.group_index == 0]
        g0[1].leader_change(is_new_leader=True, recover_slot=-1)
        g0[0].leader_change(is_new_leader=False, recover_slot=-1)
        sim.transport.deliver_all_coalesced()
        after = prefix(sim.replicas[0])
        assert after[:len(before)] == before  # nothing lost or rewritten
        self.drive(sim, 16, 24, got)
        for _ in range(40):
            if len(got) == 24:
                break
            for timer in sim.transport.running_timers():
                if timer.name == "recover" \
                        or timer.name.startswith("resendWrite"):
                    sim.transport.trigger_timer(timer.id)
            sim.transport.deliver_all_coalesced()
        assert len(got) == 24
        l0, l1 = prefix(sim.replicas[0]), prefix(sim.replicas[1])
        n = min(len(l0), len(l1))
        assert l0[:n] == l1[:n]

    def test_acceptor_phase1b_merges_strided_run_votes(self):
        """An acceptor reports strided run-voted slots in Phase1b with
        the highest round winning over per-slot votes, and a shorter
        same-start replacement preserves the longer run's tail."""
        from frankenpaxos_tpu.protocols.mencius.common import (
            CommandBatch,
            Phase1a,
            Phase2a,
            Phase2aRun,
        )
        from tests.protocols.mencius_harness import make_mencius as make

        sim = make()
        acceptor = sim.acceptors[0]
        v = lambda tag: CommandBatch((tag,))  # noqa: E731
        # Run at slots 10, 12, 14 (stride 2).
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=10, stride=2, round=0,
            values=(v("a"), v("b"), v("c"))))
        # Per-slot re-vote of slot 12 at a higher round shadows the run.
        acceptor.receive("proxy-leader-0",
                         Phase2a(slot=12, round=1, value=v("b2")))
        # Same-start SHORTER run at a higher round truncates: the tail
        # (slot 14) must survive recovery with its round-0 vote.
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=10, stride=2, round=2, values=(v("a2"),)))
        acceptor.receive("leader-0-1", Phase1a(round=3,
                                               chosen_watermark=10))
        sent = [m for m in sim.transport.messages if m.dst == "leader-0-1"]
        assert sent, "acceptor must answer Phase1a"
        phase1b = acceptor.serializer.from_bytes(sent[-1].data)
        info = {i.slot: (i.vote_round, i.vote_value) for i in phase1b.info}
        assert info[10] == (2, v("a2"))
        assert info[12] == (1, v("b2"))  # higher round wins
        assert info[14] == (0, v("c"))   # truncated tail preserved

    def test_proxy_leader_round_monotone_run_eviction(self):
        """A same-start higher-round Phase2aRun evicts the stale pending
        record and is proposed; duplicates and stale rounds are ignored;
        straggler acks of the evicted round don't fatal or emit."""
        from frankenpaxos_tpu.protocols.mencius.common import (
            Command,
            CommandBatch,
            CommandId,
            Phase2aRun,
            Phase2bRun,
        )
        from tests.protocols.mencius_harness import make_mencius as make

        sim = make()
        proxy = sim.proxy_leaders[0]
        v = lambda i: CommandBatch((Command(  # noqa: E731
            CommandId("client-0", 0, 0), i.encode()),))
        run0 = Phase2aRun(start_slot=0, stride=2, round=0,
                          values=(v("a"), v("b")))
        sim.transport.messages.clear()
        proxy.receive("leader-0-0", run0)
        forwards = len(sim.transport.messages)
        assert forwards == sim.config.f + 1
        proxy.receive("leader-0-0", run0)  # duplicate: ignored
        assert len(sim.transport.messages) == forwards
        run1 = Phase2aRun(start_slot=0, stride=2, round=1,
                          values=(v("a"), v("b")))
        proxy.receive("leader-0-1", run1)  # higher round: proposed
        assert len(sim.transport.messages) == 2 * forwards
        assert proxy._runs[0][0] == 1
        sim.transport.messages.clear()
        # Straggler acks of the evicted round 0: swallowed quietly.
        proxy.receive("acceptor-0-0-0", Phase2bRun(
            acceptor_group_index=0, acceptor_index=0, start_slot=0,
            count=2, stride=2, round=0))
        assert [m for m in sim.transport.messages
                if m.dst.startswith("replica")] == []
        # Round-1 quorum completes: one ChosenRun per replica.
        for acc in (0, 1):
            proxy.receive(f"acceptor-0-0-{acc}", Phase2bRun(
                acceptor_group_index=0, acceptor_index=acc, start_slot=0,
                count=2, stride=2, round=1))
        chosen = [proxy.serializer.from_bytes(m.data)
                  for m in sim.transport.messages if m.dst == "replica-0"]
        assert [(c.start_slot, c.stride, len(c.values))
                for c in chosen] == [(0, 2, 2)]
        assert 0 not in proxy._runs
        # A re-ack of the RETIRED round is recognized (no fatal).
        proxy.receive("acceptor-0-0-2", Phase2bRun(
            acceptor_group_index=0, acceptor_index=2, start_slot=0,
            count=2, stride=2, round=1))


class WriteCmd:
    def __init__(self, client, pseudonym, payload):
        self.client = client
        self.pseudonym = pseudonym
        self.payload = payload

    def __repr__(self):
        return f"Write({self.client}, {self.pseudonym}, {self.payload!r})"


class TransportCmd:
    def __init__(self, command):
        self.command = command

    def __repr__(self):
        return f"Transport({self.command!r})"


class FlushCmd:
    """Ship one coalescing client's staged writes (flush_writes) as its
    OWN random command -- several writes stage before a flush, so
    request arrays (and the strided Phase2aRuns they become) carry
    k > 1 commands into the adversarial interleaving of drops,
    partitions, and leader changes (same pattern as the MultiPaxos
    adversarial sim)."""

    def __init__(self, client):
        self.client = client

    def __repr__(self):
        return f"Flush({self.client})"


class MenciusSimulated(SimulatedSystem):
    def __init__(self, **kwargs):
        self.kwargs = kwargs

    def new_system(self, seed):
        transport, config, leaders, replicas, clients = make_mencius(
            seed=seed, num_clients=2, **self.kwargs)
        return dict(transport=transport, replicas=replicas,
                    clients=clients, counter=0)

    def generate_command(self, system, rng: random.Random):
        choices = []
        idle = [(c, p) for c, client in enumerate(system["clients"])
                for p in range(4) if p not in client.states]
        if idle:
            choices.extend(["write"] * 2)
        staged = [c for c, client in enumerate(system["clients"])
                  if getattr(client, "_staged_writes", None)]
        if staged:
            choices.append("flush")
        transport_cmd = system["transport"].generate_command(rng)
        if transport_cmd is not None:
            choices.extend(["transport"] * 6)
        if not choices:
            return None
        kind = rng.choice(choices)
        if kind == "write":
            client, pseudonym = rng.choice(idle)
            system["counter"] += 1
            return WriteCmd(client, pseudonym, b"w%d" % system["counter"])
        if kind == "flush":
            return FlushCmd(rng.choice(staged))
        return TransportCmd(transport_cmd)

    def run_command(self, system, command):
        if isinstance(command, WriteCmd):
            client = system["clients"][command.client]
            if command.pseudonym not in client.states:
                client.write(command.pseudonym, command.payload)
        elif isinstance(command, FlushCmd):
            system["clients"][command.client].flush_writes()
        else:
            system["transport"].run_command(command.command)
        return system

    def state_invariant(self, system) -> Optional[str]:
        logs = [executed_prefix(r) for r in system["replicas"]]
        for i in range(len(logs)):
            for j in range(i + 1, len(logs)):
                n = min(len(logs[i]), len(logs[j]))
                if logs[i][:n] != logs[j][:n]:
                    return (f"replica logs diverge: {logs[i]!r} vs "
                            f"{logs[j]!r}")
        return None


@pytest.mark.parametrize("kwargs", [
    dict(num_leader_groups=1),
    dict(num_leader_groups=2, lag_threshold=2),
    dict(num_leader_groups=3, num_acceptor_groups=2, lag_threshold=3),
    dict(num_leader_groups=2, lag_threshold=2, coalesced=True),
    dict(num_leader_groups=1, coalesced=True),
    dict(num_leader_groups=2, lag_threshold=2, coalesced="mixed"),
    # Multiple acceptor groups + coalesced clients: the leader's
    # per-slot fallback path under the same adversarial schedule.
    dict(num_leader_groups=2, num_acceptor_groups=2, lag_threshold=2,
         coalesced=True),
], ids=["groups1", "groups2", "groups3x2", "coalesced", "coalesced-g1",
        "coalesced-mixed", "coalesced-groups2x2"])
def test_simulation_no_divergence(kwargs):
    failure = Simulator(MenciusSimulated(**kwargs), run_length=150,
                        num_runs=15).run(seed=0)
    assert failure is None, str(failure)


def test_a_read_goes_through_the_log():
    """``MenciusClient.read`` proposes the read in a slot like a write:
    its answer is the state machine's at that place in the log, after
    the write before it."""
    from frankenpaxos_tpu.runtime.serializer import PickleSerializer
    from frankenpaxos_tpu.statemachine import (
        GetRequest,
        KeyValueStore,
        SetRequest,
    )
    from tests.protocols.mencius_harness import make_mencius as make

    sim = make(num_leader_groups=3, coalesced=True, lag_threshold=1,
               state_machine_factory=KeyValueStore)
    serializer = PickleSerializer()
    client = sim.clients[0]
    got = []

    def settle():
        sim.transport.deliver_all_coalesced()
        for _ in range(30):
            if len(got) == 2:
                return
            for timer in sim.transport.running_timers():
                if timer.name == "recover":
                    sim.transport.trigger_timer(timer.id)
            sim.transport.deliver_all_coalesced()

    client.write(0, serializer.to_bytes(SetRequest((("k", "v1"),))),
                 got.append)
    client.flush_writes()
    settle()
    client.read(0, serializer.to_bytes(GetRequest(("k",))), got.append)
    client.flush_writes()
    settle()
    assert len(got) == 2
    assert dict(serializer.from_bytes(got[1]).key_values) == {"k": "v1"}
    # The read took a slot of its own in the log.
    from frankenpaxos_tpu.protocols.mencius.common import Noop

    assert sum(len(v.commands) for v in executed_prefix(sim.replicas[0])
               if not isinstance(v, Noop)) == 2


def test_a_group_that_enters_phase_2_late_skips_what_it_heard():
    """A leader still in phase 1 when the others' high watermarks reach
    it skips its slots below them as it enters phase 2: the writes held
    up behind its holes send no further watermark, so waiting for one
    would leave them to a replica's recover timer."""
    from frankenpaxos_tpu.protocols.mencius.common import Noop, Phase1b
    from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
    from tests.protocols.mencius_harness import make_mencius as make

    sim = make(num_leader_groups=3, coalesced=True, lag_threshold=1)
    transport = sim.transport
    late = sim.config.leader_addresses[1][0]
    client = sim.clients[0]
    client._random_group_leader = lambda: client._leader_of_group(0)
    got = []
    for p in range(4):
        client.write(p, b"v%d" % p, got.append)
    client.flush_writes()

    def held(message) -> bool:
        return message.dst == late and isinstance(
            DEFAULT_SERIALIZER.from_bytes(message.data), Phase1b)

    while True:
        transport._compact_messages()
        ready = [m for m in transport.messages if not held(m)]
        if not ready:
            break
        transport.deliver_message(ready[0])
    assert len(got) < 4  # group 1's slots are holes
    transport.deliver_all()  # its phase 1 completes; no timer fires
    assert len(got) == 4
    log = executed_prefix(sim.replicas[0])
    assert any(isinstance(v, Noop) for v in log[1::3])

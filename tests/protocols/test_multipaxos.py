"""MultiPaxos: end-to-end integration over SimTransport, plus the
property-based simulation with the reference's invariants
(multipaxos/MultiPaxos.scala:291-318: replica executed-log prefixes
mutually compatible; logs only grow)."""

import random
from typing import Optional

import pytest

from frankenpaxos_tpu.runtime import PickleSerializer
from frankenpaxos_tpu.sim import SimulatedSystem, Simulator
from frankenpaxos_tpu.statemachine import GetRequest, KeyValueStore, SetRequest
from tests.protocols.multipaxos_harness import (
    deliver_and_flush,
    drain_and_collect,
    executed_prefix,
    make_multipaxos,
)

SER = PickleSerializer()


def run_write(sim, client_index, pseudonym, payload):
    got = []
    sim.clients[client_index].write(pseudonym, payload, got.append)
    deliver_and_flush(sim)
    return got


def board_launches(sim) -> list[int]:
    """Kernel launches of each proxy leader's device tracker."""
    return [p.tracker.device_launches for p in sim.proxy_leaders]


class TestMultiPaxosIntegration:
    def test_single_write(self):
        sim = make_multipaxos(f=1)
        got = run_write(sim, 0, 0, b"hello")
        assert got == [b"0"]
        for replica in sim.replicas:
            assert replica.state_machine.get() == [b"hello"]

    def test_sequential_writes_agree(self):
        sim = make_multipaxos(f=1)
        for i in range(10):
            assert run_write(sim, 0, 0, b"cmd%d" % i) == [b"%d" % i]
        logs = [executed_prefix(r) for r in sim.replicas]
        assert logs[0] == logs[1]
        assert len(logs[0]) == 10

    def test_multiple_clients_pseudonyms(self):
        sim = make_multipaxos(f=1, num_clients=3)
        results = []
        for i, client in enumerate(sim.clients):
            client.write(0, b"c%d-p0" % i, results.append)
            client.write(1, b"c%d-p1" % i, results.append)
        sim.transport.deliver_all()
        assert len(results) == 6
        for replica in sim.replicas:
            assert len(replica.state_machine.get()) == 6

    def test_f2(self):
        sim = make_multipaxos(f=2)
        assert run_write(sim, 0, 0, b"x") == [b"0"]

    def test_multiple_acceptor_groups(self):
        sim = make_multipaxos(f=1, num_acceptor_groups=3)
        for i in range(6):
            assert run_write(sim, 0, 0, b"cmd%d" % i) == [b"%d" % i]
        # Slots round-robin over groups: every GROUP voted (individual
        # acceptors may be skipped by thrifty f+1 sampling).
        for g in range(3):
            group = sim.acceptors[g * 3:(g + 1) * 3]
            assert any(a.max_voted_slot >= 0 for a in group), g

    def test_flexible_grid(self):
        sim = make_multipaxos(f=1, flexible=True, grid_shape=(2, 3))
        for i in range(5):
            assert run_write(sim, 0, 0, b"cmd%d" % i) == [b"%d" % i]

    def test_batchers(self):
        sim = make_multipaxos(f=1, num_batchers=2, batch_size=2,
                              num_clients=4)
        results = []
        for client in sim.clients:
            client.write(0, b"w", results.append)
        sim.transport.deliver_all()
        # Partial batches can strand below batch_size until client resends
        # top them up (batchers only flush on size, Batcher.scala:148-163).
        for _ in range(5):
            if len(results) == 4:
                break
            for timer in sim.transport.running_timers():
                if timer.name.startswith("resendWrite"):
                    sim.transport.trigger_timer(timer.id)
            sim.transport.deliver_all()
        assert len(results) == 4
        assert len(sim.replicas[0].state_machine.get()) == 4

    def test_proxy_replicas(self):
        sim = make_multipaxos(f=1, num_proxy_replicas=2)
        assert run_write(sim, 0, 0, b"via-proxy") == [b"0"]

    def test_tpu_quorum_backend_matches(self):
        sim = make_multipaxos(f=1, quorum_backend="tpu")
        for i in range(5):
            assert run_write(sim, 0, 0, b"cmd%d" % i) == [b"%d" % i]
        logs = [executed_prefix(r) for r in sim.replicas]
        assert logs[0] == logs[1] and len(logs[0]) == 5
        assert sum(board_launches(sim)) >= 5

    def test_tpu_backend_flexible_grid(self):
        sim = make_multipaxos(f=1, flexible=True, grid_shape=(2, 3),
                              quorum_backend="tpu")
        for i in range(4):
            assert run_write(sim, 0, 0, b"cmd%d" % i) == [b"%d" % i]
        assert sum(board_launches(sim)) >= 4

    def test_tpu_phase1_recovery_preserves_log(self):
        """Failover with phase1_backend=tpu: the new leader's batched
        safe_values recovery must preserve every chosen value."""
        sim = make_multipaxos(f=1, phase1_backend="tpu")
        for i in range(4):
            assert run_write(sim, 0, 0, b"cmd%d" % i) == [b"%d" % i]
        # Fail leader 0 over to leader 1; the new leader's Phase1 re-reads
        # acceptor votes and re-proposes the whole recovered window through
        # the device argmax path.
        sim.leaders[0].leader_change(is_new_leader=False)
        sim.leaders[1].leader_change(is_new_leader=True)
        sim.transport.deliver_all()
        assert run_write(sim, 0, 0, b"after") == [b"4"]
        logs = [executed_prefix(r) for r in sim.replicas]
        assert logs[0] == logs[1] and len(logs[0]) >= 5

    def test_recover_values_tpu_matches_host(self):
        """_recover_values oracle equivalence: host per-slot scan vs the
        one-shot device masked argmax, across groups and vote patterns."""
        from frankenpaxos_tpu.protocols.multipaxos.leader import _Phase1
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            NOOP,
            Phase1b,
            Phase1bSlotInfo,
        )

        rng = random.Random(11)
        for num_groups in (1, 2):
            sim_host = make_multipaxos(f=1,
                                       num_acceptor_groups=num_groups,
                                       phase1_backend="host")
            sim_tpu = make_multipaxos(f=1, num_acceptor_groups=num_groups,
                                      phase1_backend="tpu")
            max_slot = 12
            phase1bs = [{} for _ in range(num_groups)]
            for group_index in range(num_groups):
                for acceptor_index in range(3):
                    infos = []
                    for slot in range(max_slot + 1):
                        if slot % num_groups != group_index:
                            continue
                        if rng.random() < 0.5:
                            continue  # this acceptor has no vote for slot
                        infos.append(Phase1bSlotInfo(
                            slot=slot,
                            vote_round=rng.randrange(3),
                            vote_value=b"v%d" % rng.randrange(4)))
                    phase1bs[group_index][acceptor_index] = Phase1b(
                        group_index=group_index,
                        acceptor_index=acceptor_index,
                        round=0, info=tuple(infos))
            phase1 = _Phase1(phase1bs=phase1bs, phase1b_acceptors=set(),
                             pending_batches=[], resend_phase1as=None)
            host_leader = sim_host.leaders[0]
            tpu_leader = sim_tpu.leaders[0]
            host_leader.chosen_watermark = 2
            tpu_leader.chosen_watermark = 2
            host = host_leader._recover_values(phase1, max_slot)
            tpu = tpu_leader._recover_values(phase1, max_slot)
            # Ties between equal vote rounds with different values cannot
            # occur in Paxos (same round implies same value); the random
            # pattern above can produce them, so compare only where the
            # host answer is unambiguous.
            assert len(host) == len(tpu) == max_slot - 1
            for slot, (h, t) in enumerate(zip(host, tpu), start=2):
                group = phase1bs[slot % num_groups]
                votes = [(i.vote_round, i.vote_value)
                         for p in group.values() for i in p.info
                         if i.slot == slot]
                if not votes:
                    assert h is NOOP and t is NOOP
                    continue
                top = max(r for r, _ in votes)
                top_values = {v for r, v in votes if r == top}
                assert h in top_values and t in top_values
                if len(top_values) == 1:
                    assert h == t

    def test_kv_store_write_and_read(self):
        sim = make_multipaxos(f=1, state_machine_factory=KeyValueStore)
        client = sim.clients[0]
        got = []
        client.write(0, SER.to_bytes(SetRequest((("k", "v"),))),
                     got.append)
        sim.transport.deliver_all()
        assert len(got) == 1

        reads = []
        client.read(1, SER.to_bytes(GetRequest(("k",))),
                    lambda r: reads.append(SER.from_bytes(r)))
        sim.transport.deliver_all()
        assert len(reads) == 1
        assert reads[0].key_values == (("k", "v"),)

    def test_sequential_and_eventual_reads(self):
        sim = make_multipaxos(f=1, state_machine_factory=KeyValueStore)
        client = sim.clients[0]
        client.write(0, SER.to_bytes(SetRequest((("k", "v"),))))
        sim.transport.deliver_all()
        seq, ev = [], []
        client.sequential_read(1, SER.to_bytes(GetRequest(("k",))),
                               lambda r: seq.append(SER.from_bytes(r)))
        client.eventual_read(2, SER.to_bytes(GetRequest(("k",))),
                             lambda r: ev.append(SER.from_bytes(r)))
        sim.transport.deliver_all()
        assert seq and seq[0].key_values == (("k", "v"),)
        assert ev and ev[0].key_values == (("k", "v"),)

    def test_read_batcher_linearizable(self):
        from frankenpaxos_tpu.protocols.multipaxos import ReadBatchingScheme

        sim = make_multipaxos(f=1, state_machine_factory=KeyValueStore,
                              num_read_batchers=2,
                              read_batching_scheme=ReadBatchingScheme(
                                  kind="size", batch_size=2))
        client = sim.clients[0]
        client.write(0, SER.to_bytes(SetRequest((("k", "v"),))))
        sim.transport.deliver_all()
        reads = []
        # Two reads from two pseudonyms fill one batch of two.
        client.read(1, SER.to_bytes(GetRequest(("k",))),
                    lambda r: reads.append(SER.from_bytes(r)))
        client.read(2, SER.to_bytes(GetRequest(("k",))),
                    lambda r: reads.append(SER.from_bytes(r)))
        sim.transport.deliver_all()
        for _ in range(5):
            if len(reads) == 2:
                break
            for timer in sim.transport.running_timers():
                if "Timer" in timer.name or timer.name.startswith(
                        "resendRead"):
                    sim.transport.trigger_timer(timer.id)
            sim.transport.deliver_all()
        assert len(reads) == 2
        assert all(r.key_values == (("k", "v"),) for r in reads)

    def test_read_batcher_adaptive(self):
        from frankenpaxos_tpu.protocols.multipaxos import ReadBatchingScheme

        sim = make_multipaxos(f=1, state_machine_factory=KeyValueStore,
                              num_read_batchers=2,
                              read_batching_scheme=ReadBatchingScheme(
                                  kind="adaptive"))
        client = sim.clients[0]
        client.write(0, SER.to_bytes(SetRequest((("k", "v"),))))
        sim.transport.deliver_all()
        reads = []
        client.read(1, SER.to_bytes(GetRequest(("k",))),
                    lambda r: reads.append(SER.from_bytes(r)))
        sim.transport.deliver_all()
        assert len(reads) == 1
        assert reads[0].key_values == (("k", "v"),)

    def test_write_resend_is_deduplicated(self):
        sim = make_multipaxos(f=1)
        got = []
        sim.clients[0].write(0, b"once", got.append)
        # Fire the client's resend timer before any delivery.
        for timer in sim.transport.running_timers():
            if timer.name.startswith("resendWrite"):
                sim.transport.trigger_timer(timer.id)
        sim.transport.deliver_all()
        assert got == [b"0"]
        # Executed exactly once despite duplicate ClientRequests.
        assert sim.replicas[0].state_machine.get() == [b"once"]

    def test_pending_pseudonym_rejected(self):
        sim = make_multipaxos(f=1)
        sim.clients[0].write(0, b"a")
        with pytest.raises(RuntimeError):
            sim.clients[0].write(0, b"b")


# --- property-based simulation ---------------------------------------------


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
    """Ship one coalescing client's staged writes (flush_writes).

    Flushing is its OWN random command -- several writes stage before a
    flush, so request arrays (and the Phase2aRuns they become) carry
    k > 1 commands INTO the adversarial interleaving of drops,
    partitions, and leader changes, instead of degenerating to k=1
    arrays that never exercise run-store edge paths."""

    def __init__(self, client):
        self.client = client

    def __repr__(self):
        return f"Flush({self.client})"


def prefixes_compatible(a: list, b: list) -> bool:
    n = min(len(a), len(b))
    return a[:n] == b[:n]


class MultiPaxosSimulated(SimulatedSystem):
    """Random writes interleaved with arbitrary deliveries/timer firings
    (the reference interleaves the same way,
    multipaxos/MultiPaxos.scala:229-268)."""

    def __init__(self, **harness_kwargs):
        self.harness_kwargs = harness_kwargs

    def new_system(self, seed):
        sim = make_multipaxos(seed=seed, num_clients=2,
                              **self.harness_kwargs)
        sim._counter = 0
        return sim

    def generate_command(self, sim, rng: random.Random):
        choices = []
        # Writes are only possible for idle pseudonyms. More pseudonyms
        # than a coalescing client can flush at once, so k > 1 writes
        # stage between flushes.
        idle = [(c, p) for c, client in enumerate(sim.clients)
                for p in range(4) if p not in client.states]
        if idle:
            choices.extend(["write"] * 2)
        staged = [c for c, client in enumerate(sim.clients)
                  if getattr(client, "_staged_writes", None)]
        if staged:
            choices.append("flush")
        transport_cmd = sim.transport.generate_command(rng)
        if transport_cmd is not None:
            # Weight transport activity higher: most steps move messages.
            choices.extend(["transport"] * 6)
        if not choices:
            return None
        kind = rng.choice(choices)
        if kind == "write":
            client, pseudonym = rng.choice(idle)
            sim._counter += 1
            return WriteCmd(client, pseudonym,
                            b"w%d" % sim._counter)
        if kind == "flush":
            return FlushCmd(rng.choice(staged))
        return TransportCmd(transport_cmd)

    def run_command(self, sim, command):
        if isinstance(command, WriteCmd):
            client = sim.clients[command.client]
            if command.pseudonym not in client.states:
                client.write(command.pseudonym, command.payload)
        elif isinstance(command, FlushCmd):
            sim.clients[command.client].flush_writes()
        else:
            sim.transport.run_command(command.command)
        return sim

    def get_state(self, sim):
        return tuple(tuple(executed_prefix(r)) for r in sim.replicas)

    def state_invariant(self, sim) -> Optional[str]:
        logs = [executed_prefix(r) for r in sim.replicas]
        for i in range(len(logs)):
            for j in range(i + 1, len(logs)):
                if not prefixes_compatible(logs[i], logs[j]):
                    return (f"replica logs diverge: {logs[i]!r} vs "
                            f"{logs[j]!r}")
        return None

    def step_invariant(self, old_state, new_state) -> Optional[str]:
        for old_log, new_log in zip(old_state, new_state):
            if list(new_log[:len(old_log)]) != list(old_log):
                return f"replica log shrank/rewrote: {old_log} -> {new_log}"
        return None


@pytest.mark.parametrize("kwargs", [
    dict(f=1),
    dict(f=1, num_acceptor_groups=2),
    dict(f=1, flexible=True, grid_shape=(2, 2)),
    dict(f=1, num_batchers=2, batch_size=2),
    dict(f=2),
    dict(f=1, coalesced=True),
    dict(f=1, coalesced=True, flexible=True, grid_shape=(2, 2)),
    dict(f=1, coalesced="mixed"),
], ids=["f1", "groups2", "grid", "batched", "f2", "coalesced",
        "coalesced-grid", "coalesced-mixed"])
def test_simulation_no_divergence(kwargs):
    simulated = MultiPaxosSimulated(**kwargs)
    failure = Simulator(simulated, run_length=150, num_runs=20).run(seed=0)
    assert failure is None, str(failure)


class TestCoalescedRunPipeline:
    """The drain-granular run pipeline (ClientRequestArray ->
    Phase2aRun -> Phase2bRange -> ChosenRun -> ClientReplyArray)
    against the per-message reference shape."""

    def drive(self, sim, lo, hi, got):
        for p in range(lo, hi):
            sim.clients[0].write(p, b"v%d" % p, got.append)
        sim.clients[0].flush_writes()
        deliver_and_flush(sim, coalesced=True)

    @pytest.mark.parametrize("backend", ["dict", "tpu"])
    def test_matches_per_message_pipeline(self, backend):
        """Same writes through the coalesced and per-message pipelines
        produce identical replica logs and replies."""
        logs = {}
        for coalesced in (False, True):
            sim = make_multipaxos(f=1, coalesced=coalesced,
                                  quorum_backend=backend)
            got = []
            for wave in range(4):
                self.drive(sim, wave * 50, wave * 50 + 50, got)
            # Reply ORDER across pseudonyms is not a guarantee (the
            # coalesced path delivers one array per owning replica, so
            # even slots' replies arrive together); the reply SET is.
            assert sorted(got, key=int) == [b"%d" % p
                                            for p in range(200)]
            assert executed_prefix(sim.replicas[0]) \
                == executed_prefix(sim.replicas[1])
            logs[coalesced] = executed_prefix(sim.replicas[0])
            if backend == "tpu":
                assert sum(board_launches(sim)) > 0
        assert len(logs[False]) == len(logs[True]) == 200
        assert logs[False] == logs[True]

    def test_survives_leader_failover(self):
        """Run-voted acceptor state must be recovered by a new leader's
        Phase1 (the run store feeds Phase1b): values accepted via
        Phase2aRuns survive failover byte-identically, and the new
        leader keeps serving coalesced writes."""
        sim = make_multipaxos(f=1, coalesced=True)
        got = []
        self.drive(sim, 0, 32, got)
        assert len(got) == 32
        before = executed_prefix(sim.replicas[0])
        assert len(before) == 32

        # Leader 1 takes over (round 1); its Phase1 must recover every
        # run-voted slot from the acceptors' run stores.
        sim.leaders[1].leader_change(is_new_leader=True)
        sim.leaders[0].leader_change(is_new_leader=False)
        sim.transport.deliver_all_coalesced()
        after = executed_prefix(sim.replicas[0])
        assert after[:len(before)] == before  # nothing lost or rewritten
        assert executed_prefix(sim.replicas[1])[:len(before)] == before

        # New writes: the client discovers the new leader via the
        # NotLeader bounce and the pipeline keeps moving.
        self.drive(sim, 32, 48, got)
        assert len(got) == 48
        from frankenpaxos_tpu.protocols.multipaxos.messages import Noop

        final = executed_prefix(sim.replicas[0])
        assert executed_prefix(sim.replicas[1]) == final
        payloads = [v.commands[0].command for v in final
                    if not isinstance(v, Noop) and v.commands]
        assert set(b"v%d" % p for p in range(48)) <= set(payloads)

    def test_proxy_leader_partial_run_emission_and_stray_acks(self):
        """Run-store edge paths: a run whose quorum completes in two
        pieces emits two ChosenRuns covering it exactly once; stray
        re-acks for a RETIRED run are recognized (no fatal, no
        re-emission)."""
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            Command,
            CommandBatch,
            CommandId,
            Phase2aRun,
            Phase2b,
            Phase2bRange,
        )

        sim = make_multipaxos(f=1)
        proxy = sim.proxy_leaders[0]
        v = lambda i: CommandBatch((Command(  # noqa: E731
            CommandId("client-0", i, 0), b"v%d" % i),))
        proxy.receive("leader-0", Phase2aRun(
            start_slot=0, round=0, values=tuple(v(i) for i in range(8))))
        sim.transport.messages.clear()  # drop the quorum forwards

        def ack(acc, lo, hi):
            proxy.receive(f"acceptor-0-{acc}", Phase2bRange(
                group_index=0, acceptor_index=acc,
                slot_start_inclusive=lo, slot_end_exclusive=hi, round=0))

        # First piece: slots [0, 5) reach quorum; [5, 8) have 1 vote.
        ack(0, 0, 8)
        ack(1, 0, 5)
        proxy.on_drain()
        chosen1 = [proxy.serializer.from_bytes(m.data)
                   for m in sim.transport.messages
                   if m.dst == "replica-0"]
        assert [(c.start_slot, len(c.values)) for c in chosen1] == [(0, 5)]
        sim.transport.messages.clear()
        # Second piece completes; run retires.
        ack(1, 5, 8)
        proxy.on_drain()
        chosen2 = [proxy.serializer.from_bytes(m.data)
                   for m in sim.transport.messages
                   if m.dst == "replica-0"]
        assert [(c.start_slot, len(c.values)) for c in chosen2] == [(5, 3)]
        assert proxy._runs == {} and proxy._run_starts == []
        assert proxy._done_runs == [(0, 8, 0)]
        sim.transport.messages.clear()
        # Stray re-acks for the retired run: ranged AND single-slot
        # (the single-slot path runs the fatal check) -- must be
        # swallowed without fatal or re-emission.
        ack(2, 2, 6)
        proxy.receive("acceptor-0-2", Phase2b(
            group_index=0, acceptor_index=2, slot=3, round=0))
        proxy.on_drain()
        assert [m for m in sim.transport.messages
                if m.dst.startswith("replica")] == []

    def test_proxy_leader_duplicate_run_ignored(self):
        """A resent Phase2aRun for a start slot already pending must
        not re-forward or double-register."""
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            Command,
            CommandBatch,
            CommandId,
            Phase2aRun,
        )

        sim = make_multipaxos(f=1)
        proxy = sim.proxy_leaders[0]
        run = Phase2aRun(start_slot=0, round=0,
                         values=(CommandBatch((Command(
                             CommandId("client-0", 0, 0), b"a"),)),))
        sim.transport.messages.clear()  # drop startup Phase1a traffic
        proxy.receive("leader-0", run)
        forwards = len(sim.transport.messages)
        assert forwards == sim.config.f + 1
        proxy.receive("leader-0", run)
        assert len(sim.transport.messages) == forwards
        assert len(proxy._run_starts) == 1

    def test_proxy_leader_higher_round_run_evicts_stale_pending(self):
        """A same-start HIGHER-round Phase2aRun must evict the stale
        pending record and be proposed (round-monotone, mirroring the
        acceptor); same-round duplicates stay ignored, and straggler
        acks of the evicted round are recognized (no fatal)."""
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            Command,
            CommandBatch,
            CommandId,
            Phase2aRun,
            Phase2b,
            Phase2bRange,
        )

        sim = make_multipaxos(f=1)
        proxy = sim.proxy_leaders[0]
        v = lambda i: CommandBatch((Command(  # noqa: E731
            CommandId("client-0", i, 0), b"v%d" % i),))
        run0 = Phase2aRun(start_slot=0, round=0,
                          values=(v(0), v(1), v(2)))
        sim.transport.messages.clear()
        proxy.receive("leader-0", run0)
        forwards = len(sim.transport.messages)
        assert forwards == sim.config.f + 1
        proxy.receive("leader-0", run0)  # same round: ignored
        assert len(sim.transport.messages) == forwards
        run1 = Phase2aRun(start_slot=0, round=1,
                          values=(v(0), v(1), v(2)))
        proxy.receive("leader-1", run1)  # higher round: proposed
        assert len(sim.transport.messages) == 2 * forwards
        assert proxy._runs[0][1] == 1 and len(proxy._run_starts) == 1
        sim.transport.messages.clear()
        # Straggler acks of the evicted round 0 (ranged AND single-slot,
        # the latter running the stray-ack fatal check): swallowed.
        proxy.receive("acceptor-0-0", Phase2bRange(
            group_index=0, acceptor_index=0, slot_start_inclusive=0,
            slot_end_exclusive=3, round=0))
        proxy.receive("acceptor-0-0", Phase2b(
            group_index=0, acceptor_index=0, slot=1, round=0))
        proxy.on_drain()
        assert [m for m in sim.transport.messages
                if m.dst.startswith("replica")] == []
        # The round-1 quorum completes and emits ChosenRuns normally.
        for acc in (0, 1):
            proxy.receive(f"acceptor-0-{acc}", Phase2bRange(
                group_index=0, acceptor_index=acc,
                slot_start_inclusive=0, slot_end_exclusive=3, round=1))
        proxy.on_drain()
        chosen = [proxy.serializer.from_bytes(m.data)
                  for m in sim.transport.messages if m.dst == "replica-0"]
        assert [(c.start_slot, len(c.values)) for c in chosen] == [(0, 3)]

    def test_failover_with_proposals_stuck_at_proxies(self):
        """Proposals die at PARTITIONED proxy leaders mid-run; a
        failover plus client resends must still commit every write
        exactly once, with replicas agreeing."""
        sim = make_multipaxos(f=1, coalesced=True)
        got = []
        for p in range(16):
            sim.clients[0].write(p, b"q%d" % p, got.append)
        sim.clients[0].flush_writes()
        for proxy in sim.config.proxy_leader_addresses:
            sim.transport.partition(proxy)
        sim.transport.deliver_all_coalesced()
        assert got == []  # proposals stuck at the partitioned proxies
        # Fail over and heal; clients resend on discovery (the resend
        # path is per-request ClientRequests to the new round leader).
        sim.leaders[1].leader_change(is_new_leader=True)
        sim.leaders[0].leader_change(is_new_leader=False)
        for proxy in sim.config.proxy_leader_addresses:
            sim.transport.heal(proxy)
        sim.transport.deliver_all_coalesced()
        for t in list(sim.transport.running_timers()):
            if t.name.startswith("resendWrite"):
                t.run()
        sim.transport.deliver_all_coalesced()
        assert len(got) == 16
        assert executed_prefix(sim.replicas[0]) \
            == executed_prefix(sim.replicas[1])
        # Exactly-once EXECUTION: a resend may legitimately occupy two
        # log slots, but the client table must execute each write once
        # (Replica.scala:300-344) -- the SM sees every payload exactly
        # once.
        executed = sim.replicas[0].state_machine.get()
        for p in range(16):
            assert executed.count(b"q%d" % p) == 1, (p, executed)

    def test_acceptor_phase1b_merges_run_votes(self):
        """An acceptor reports run-voted slots in Phase1b with the
        highest round winning over per-slot votes."""
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            CommandBatch,
            Phase1a,
            Phase2a,
            Phase2aRun,
        )

        sim = make_multipaxos(f=1)
        acceptor = sim.acceptors[0]
        v = lambda tag: CommandBatch((tag,))  # noqa: E731
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=10, round=0, values=(v("a"), v("b"), v("c"))))
        # Per-slot re-vote of slot 11 at a higher round shadows the run.
        acceptor.receive("proxy-leader-0",
                         Phase2a(slot=11, round=1, value=v("b2")))
        acceptor.receive("leader-1", Phase1a(round=2, chosen_watermark=10))
        sent = [m for m in sim.transport.messages
                if m.dst == "leader-1"]
        assert sent, "acceptor must answer Phase1a"
        phase1b = acceptor.serializer.from_bytes(sent[-1].data)
        info = {i.slot: (i.vote_round, i.vote_value) for i in phase1b.info}
        assert info[10] == (0, v("a"))
        assert info[11] == (1, v("b2"))  # higher round wins
        assert info[12] == (0, v("c"))


class TestAcceptorSameStartTruncation:
    """Round-5 advisor fix: a shorter same-start Phase2aRun replacing a
    longer record must reinsert the non-overlapped voted tail
    [new_end, old_end) -- a truncation that dropped it would erase
    quorum evidence for tail slots, and a later leader change could
    recover Noop over a CHOSEN value."""

    def _v(self, tag):
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            Command,
            CommandBatch,
            CommandId,
        )

        return CommandBatch((Command(CommandId("client-0", 0, 0),
                                     tag.encode()),))

    def _info(self, acceptor, round, watermark):
        from frankenpaxos_tpu.protocols.multipaxos.messages import Phase1a

        acceptor.receive("leader-1", Phase1a(round=round,
                                             chosen_watermark=watermark))

    def test_truncation_across_leader_change_preserves_tail(self):
        """The leader-change scenario: leader A's run [10, 18) is voted;
        a delayed shorter same-start re-proposal [10, 13) from leader B
        (round 1) lands after it; leader C's Phase1 (round 2) must still
        see the round-0 tail [13, 18) -- and a real Leader fed those
        Phase1bs must re-propose the tail VALUES, not Noop."""
        from frankenpaxos_tpu.protocols.multipaxos.leader import _Phase1
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            NOOP,
            Phase2aRun,
        )

        sim = make_multipaxos(f=1, coalesced=True)
        acceptor = sim.acceptors[0]
        long_run = Phase2aRun(start_slot=10, round=0, values=tuple(
            self._v("a%d" % i) for i in range(8)))
        short_run = Phase2aRun(start_slot=10, round=1, values=tuple(
            self._v("b%d" % i) for i in range(3)))
        acceptor.receive("proxy-leader-0", long_run)
        acceptor.receive("proxy-leader-0", short_run)
        self._info(acceptor, 2, 10)
        sent = [m for m in sim.transport.messages if m.dst == "leader-1"]
        phase1b = acceptor.serializer.from_bytes(sent[-1].data)
        info = {i.slot: (i.vote_round, i.vote_value) for i in phase1b.info}
        for i in range(3):
            assert info[10 + i] == (1, self._v("b%d" % i))
        for i in range(3, 8):
            assert info[10 + i] == (0, self._v("a%d" % i)), i

        # Leader C recovers from a quorum containing this acceptor: the
        # tail values must be re-proposed, not Noop'd.
        leader = sim.leaders[1]
        leader.chosen_watermark = 10
        phase1 = _Phase1(phase1bs=[{0: phase1b}], phase1b_acceptors=set(),
                         pending_batches=[], resend_phase1as=None)
        values = leader._recover_values(phase1, 17)
        assert values == [self._v("b%d" % i) for i in range(3)] \
            + [self._v("a%d" % i) for i in range(3, 8)]
        assert NOOP not in values

    def test_truncation_tail_collides_with_existing_run(self):
        """When the tail's start already holds a run record, the tail
        spills into the per-slot store instead of clobbering it; Phase1b
        still reports the max-round vote for every slot."""
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            Phase2aRun,
        )

        sim = make_multipaxos(f=1, coalesced=True)
        acceptor = sim.acceptors[0]
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=14, round=1,
            values=tuple(self._v("x%d" % i) for i in range(6))))
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=10, round=2,
            values=tuple(self._v("y%d" % i) for i in range(8))))
        # Shorter same-start replacement: tail [14, 18) collides with
        # the run record starting at 14.
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=10, round=3,
            values=tuple(self._v("z%d" % i) for i in range(4))))
        self._info(acceptor, 4, 10)
        sent = [m for m in sim.transport.messages if m.dst == "leader-1"]
        phase1b = acceptor.serializer.from_bytes(sent[-1].data)
        info = {i.slot: (i.vote_round, i.vote_value) for i in phase1b.info}
        for i in range(4):
            assert info[10 + i] == (3, self._v("z%d" % i))
        for i in range(4, 8):  # spilled tail beats the round-1 run
            assert info[10 + i] == (2, self._v("y%d" % i)), i
        for slot in (18, 19):  # the round-1 run's own tail survives
            assert info[slot] == (1, self._v("x%d" % (slot - 14)))


def test_simulation_with_tpu_backend():
    simulated = MultiPaxosSimulated(f=1, quorum_backend="tpu")
    systems = []
    new_system = simulated.new_system
    simulated.new_system = lambda seed: (
        systems.append(new_system(seed)) or systems[-1])
    # Long enough runs that votes reach the proxy leaders: at 60 steps
    # no tracker of either backend ever saw one.
    failure = Simulator(simulated, run_length=300, num_runs=3).run(seed=0)
    assert failure is None, str(failure)
    assert sum(sum(board_launches(sim)) for sim in systems) > 0


def test_quorum_tracker_dense_and_sparse_paths_match_dict():
    """TpuQuorumTracker (dense record_block runs + sparse scatter tail)
    reports exactly what DictQuorumTracker reports, over random mixes of
    contiguous-slot drains and scattered straggler drains."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    config = sim.config
    for seed in range(4):
        rng = random.Random(100 + seed)
        dict_tracker = DictQuorumTracker(config)
        tpu_tracker = TpuQuorumTracker(config, window=1 << 12)
        cursor = 0
        for _ in range(15):
            votes = []
            if rng.random() < 0.6 or cursor == 0:
                # Contiguous frontier run: the dense block shape.
                run_len = rng.randrange(1, 40)
                for slot in range(cursor, cursor + run_len):
                    for acc in rng.sample(range(3),
                                          rng.randrange(1, 4)):
                        votes.append((slot, acc))
                cursor += run_len
            else:
                # Scattered stragglers over already-seen slots.
                for _ in range(rng.randrange(1, 16)):
                    votes.append((rng.randrange(cursor),
                                  rng.randrange(3)))
            rng.shuffle(votes)
            for slot, acc in votes:
                dict_tracker.record(slot, 0, 0, acc)
                tpu_tracker.record(slot, 0, 0, acc)
            assert sorted(drain_and_collect(dict_tracker)) == \
                sorted(drain_and_collect(tpu_tracker)), (seed, cursor)
        assert tpu_tracker.device_launches >= 15


def test_quorum_tracker_ring_wrap_self_reclaims():
    """Advisor-found wedge: once slot numbers pass the vote-board
    window, the ring wraps onto columns still holding state from
    ``slot - window``. The board's owner mechanism must reclaim those
    columns in-kernel (no host GC plumbing), so quorums keep being
    reported for many windows' worth of slots."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    window = 256
    dict_tracker = DictQuorumTracker(sim.config)
    tpu_tracker = TpuQuorumTracker(sim.config, window=window)

    # Drive 8 windows of slots through in dense runs of 32.
    for base in range(0, 8 * window, 32):
        for slot in range(base, base + 32):
            for t in (dict_tracker, tpu_tracker):
                t.record(slot, 0, 0, 0)
                t.record(slot, 0, 0, 1)
        assert sorted(dict_tracker.drain()) \
            == sorted(drain_and_collect(tpu_tracker))
    # Sparse wrap: a straggler vote for a long-dead slot must be dropped
    # (its column has moved on), not clear the column's current state.
    half1 = window // 2
    tpu_tracker.record(half1, 0, 0, 0)  # ancient slot, wrapped 7 times
    assert drain_and_collect(tpu_tracker) == []
    live = 8 * window + 5
    for t in (dict_tracker, tpu_tracker):
        t.record(live, 0, 0, 0)
        t.record(live, 0, 0, 2)
    assert sorted(dict_tracker.drain()) \
        == sorted(drain_and_collect(tpu_tracker)) == [(live, 0)]
    assert tpu_tracker.device_launches > 0


def test_quorum_tracker_mixed_round_drain_reports_old_quorum():
    """Advisor-found ordering gap: when one drain carries BOTH the
    completing vote of an older round's quorum and a newer-round vote
    for the same slot, the dict oracle (arrival order) reports the old
    quorum; the device path must dispatch older-round sparse votes
    before the dense dominant-round block so it reports it too."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    for tracker_cls in (DictQuorumTracker,
                        lambda c: TpuQuorumTracker(c, window=1 << 10)):
        t = tracker_cls(sim.config)
        # Round 0: slot 5 has one of two votes.
        t.record(5, 0, 0, 0)
        assert drain_and_collect(t) == []
        # One drain: slot 5's completing round-0 vote arrives first,
        # then a wave of round-1 votes (the dominant round) including
        # slot 5. Arrival-order semantics: (5, 0) reached quorum.
        t.record(5, 0, 0, 1)
        for slot in range(4, 8):
            t.record(slot, 1, 0, 0)
        out = drain_and_collect(t)
        assert (5, 0) in out, (tracker_cls, out)
    # The last one built is the device tracker: the first drain is one
    # launch, the second one for each round.
    assert t.device_launches == 3


def test_quorum_tracker_duplicate_slot_two_rounds_one_drain():
    """Advisor-found: one drain completing ONE slot at TWO rounds must
    report the slot once in that drain -- the first = oldest round,
    arrival order, as the oracle reports -- and the dedup ring must
    hold exactly one (slot, round) pair for it: a later wide re-ack
    then re-reports no slot that was reported already. The dropped
    newer-round pair is simply never reported in that drain; a later
    re-ack completing it would be that pair's FIRST report, which the
    per-(slot, round) contract permits."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    t = TpuQuorumTracker(sim.config, window=1 << 10)
    # One mixed-round drain: slot 5 completes at round 0 AND round 1,
    # plus 10 more round-0 slots (the dominant round's dense block;
    # round 1 goes after it through the scatter).
    t.record(5, 0, 0, 0)
    t.record(5, 0, 0, 1)
    t.record(5, 1, 0, 0)
    t.record(5, 1, 0, 1)
    for slot in range(10, 20):
        t.record(slot, 0, 0, 0)
        t.record(slot, 0, 0, 1)
    out = drain_and_collect(t)
    assert [s for s, _ in out].count(5) == 1 and (5, 0) in out, out
    assert {s for s, _ in out} == {5, *range(10, 20)}, out
    assert t.device_launches == 2
    # A wide dense round-0 re-ack containing slot 5 must not re-report
    # any already-reported slot.
    for slot in range(0, 200):
        t.record(slot, 0, 0, 0)
        t.record(slot, 0, 0, 2)
    out2 = drain_and_collect(t)
    reported = {s for s, _ in out2}
    assert 5 not in reported, out2
    assert reported.isdisjoint(range(10, 20)), out2
    assert set(range(0, 5)).issubset(reported)
    assert t.device_launches == 3


def test_quorum_tracker_empty_range_ignored():
    """An empty Phase2bRange (slot_end <= slot_start) is dropped at the
    door like empty packed votes: it is no vote, and no drain."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    t = TpuQuorumTracker(sim.config, window=1 << 10)
    t.record_range(7, 7, 0, 0, 0)
    assert not t.has_votes()
    assert drain_and_collect(t) == [] and t.device_drains == 0
    t.record_range(7, 3, 5, 0, 0)  # inverted: also dropped
    t.record_range(3, 5, 0, 0, 0)
    t.record_range(3, 5, 0, 0, 1)
    assert sorted(drain_and_collect(t)) == [(3, 0), (4, 0)]
    assert (t.device_launches, t.device_votes) == (1, 4)


def test_quorum_tracker_ranged_votes_match_dict():
    """Phase2bRange votes (O(1) Python on the device tracker, per-slot
    expansion on the dict oracle) report identical quorums across mixed
    ranged/single/straggler drains."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    config = sim.config
    for seed in range(3):
        rng = random.Random(500 + seed)
        trackers = [DictQuorumTracker(config),
                    TpuQuorumTracker(config, window=1 << 12)]
        cursor = 0
        for _ in range(12):
            kind = rng.random()
            if kind < 0.6 or cursor == 0:
                width = rng.randrange(2, 64)
                for acc in range(3):
                    if rng.random() < 0.9:
                        for t in trackers:
                            t.record_range(cursor, cursor + width, 0,
                                           0, acc)
                cursor += width
            elif kind < 0.8:
                for t in trackers:
                    t.record(cursor, 0, 0, rng.randrange(3))
                cursor += 1
            else:
                for _ in range(rng.randrange(1, 8)):
                    slot, acc = rng.randrange(cursor), rng.randrange(3)
                    for t in trackers:
                        t.record(slot, 0, 0, acc)
            got = [sorted(drain_and_collect(t)) for t in trackers]
            assert got[0] == got[1], (seed, cursor)
        assert trackers[1].device_launches >= 12


def test_acceptor_emits_phase2b_ranges_per_drain():
    """Acceptors ack a drain's contiguous Phase2as as ONE Phase2bRange
    per proxy leader; lone votes stay plain Phase2bs."""
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        NOOP,
        Phase2a,
        Phase2b,
        Phase2bRange,
    )

    sim = make_multipaxos(f=1)
    acceptor = sim.acceptors[0]
    transport = sim.transport
    transport.messages.clear()
    for slot in (10, 11, 12, 20):
        acceptor.receive("proxy-leader-0",
                         Phase2a(slot=slot, round=0, value=NOOP))
    acceptor.on_drain()
    out = [acceptor.serializer.from_bytes(m.data)
           for m in transport.messages if m.src == acceptor.address]
    ranges = [m for m in out if isinstance(m, Phase2bRange)]
    singles = [m for m in out if isinstance(m, Phase2b)]
    assert len(ranges) == 1 and len(singles) == 1
    assert ranges[0].slot_start_inclusive == 10
    assert ranges[0].slot_end_exclusive == 13
    assert singles[0].slot == 20


def test_sim_transport_coalesced_waves_match_serial():
    """deliver_all_coalesced (event-loop drain granularity) commits the
    same commands as per-message deliver_all."""
    sim = make_multipaxos(f=1, quorum_backend="tpu")
    got = []
    for batch in range(3):
        for p in range(8):
            sim.clients[0].write(p, b"b%d.%d" % (batch, p), got.append)
        deliver_and_flush(sim, coalesced=True)
    assert len(got) == 24
    assert sum(board_launches(sim)) > 0
    logs = [executed_prefix(r) for r in sim.replicas]
    assert logs[0] == logs[1]
    assert len(logs[0]) >= 24


def test_quorum_tracker_gap_slot_keeps_old_round_votes():
    """Reviewer-found regression: the dense record_block path must not
    bump the round of gap slots inside the run (they received no vote
    this drain) -- an older-round slot mid-run keeps its votes and can
    still commit in its own round, exactly as the dict oracle does."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    trackers = [DictQuorumTracker(sim.config),
                TpuQuorumTracker(sim.config, window=1 << 12)]
    # Drain 1: slot 10 gets 1 of 2 round-0 votes.
    for t in trackers:
        t.record(10, 0, 0, 0)
    assert [drain_and_collect(t) for t in trackers] == [[], []]
    # Drain 2: round-1 votes for slots 8 and 12 only (slot 10 is a gap
    # inside the dense run and must be untouched).
    for t in trackers:
        t.record(8, 1, 0, 0)
        t.record(12, 1, 0, 1)
    assert [drain_and_collect(t) for t in trackers] == [[], []]
    # Drain 3: slot 10's second round-0 vote completes its quorum.
    for t in trackers:
        t.record(10, 0, 0, 1)
    dict_out, tpu_out = [drain_and_collect(t) for t in trackers]
    assert dict_out == tpu_out == [(10, 0)]
    assert trackers[1].device_launches == 3


def test_pipelined_tpu_backend_matches():
    """A drain only dispatches: with every message delivered a write's
    quorum is still in flight on the device, the flush timer collects
    it (quiescence), and then every write commits with replica logs
    identical to the reference semantics."""
    sim = make_multipaxos(f=1, quorum_backend="tpu")
    got = []
    for i in range(5):
        sim.clients[0].write(0, b"cmd%d" % i, got.append)
        sim.transport.deliver_all()
        assert len(got) == i
        assert any(p.tracker.has_pending() for p in sim.proxy_leaders)
        deliver_and_flush(sim)
        assert got[-1] == b"%d" % i, (i, got)
    logs = [executed_prefix(r) for r in sim.replicas]
    assert logs[0] == logs[1] and len(logs[0]) == 5
    assert sum(board_launches(sim)) >= 5


def test_tpu_backend_alone_commits_on_the_board():
    """``quorum_backend="tpu"`` and no other option is the board: every
    write commits, and every proxy leader's tracker launched kernels
    for the votes it was sent (nothing falls back to a host tally)."""
    sim = make_multipaxos(f=1, quorum_backend="tpu", coalesced=True)
    got = []
    # The leader moves on to its next proxy leader every 256 slots
    # (LeaderOptions.proxy_leader_chunk): 320 reach both.
    for wave in range(10):
        for p in range(32):
            sim.clients[0].write(p, b"w%d.%d" % (wave, p), got.append)
        sim.clients[0].flush_writes()
        deliver_and_flush(sim, coalesced=True)
    assert len(got) == 320
    logs = [executed_prefix(r) for r in sim.replicas]
    assert logs[0] == logs[1] and len(logs[0]) >= 320
    for proxy_leader in sim.proxy_leaders:
        t = proxy_leader.tracker
        assert t.device_launches > 0 and t.device_votes > 0
        assert (t.host_drains, t.host_votes, t.spilled_votes) == (0, 0, 0)
        assert not t.has_pending()


def test_pipelined_tracker_matches_dict_across_drains():
    """The tracker reports exactly the dict oracle's choices, however
    many dispatches are in flight before the first is collected."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    for seed in range(3):
        rng = random.Random(200 + seed)
        dict_tracker = DictQuorumTracker(sim.config)
        tpu_tracker = TpuQuorumTracker(sim.config, window=1 << 12)
        dict_out = []
        cursor = 0
        for _ in range(12):
            votes = []
            run_len = rng.randrange(1, 16)
            for slot in range(cursor, cursor + run_len):
                for acc in rng.sample(range(3), rng.randrange(1, 4)):
                    votes.append((slot, acc))
            cursor += run_len
            for slot, acc in votes:
                dict_tracker.record(slot, 0, 0, acc)
                tpu_tracker.record(slot, 0, 0, acc)
            dict_out += dict_tracker.drain()
            assert tpu_tracker.drain() == []  # dispatch only
        # Collect every in-flight dispatch (what the proxy leader's
        # collector thread / flush timer does).
        assert tpu_tracker.has_pending()
        tpu_out = drain_and_collect(tpu_tracker)
        assert sorted(dict_out) == sorted(tpu_out), seed
        assert tpu_tracker.device_launches >= 12


def test_quorum_tracker_straddling_board_split_uses_prewarmed_widths():
    """Review r4: a dense run straddling the ring end must
    decompose into prewarmed bucket widths (+ scatter remainder), not
    compile odd widths mid-run -- and still report the right slots."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    window = 256
    dict_tracker = DictQuorumTracker(sim.config)
    tpu_tracker = TpuQuorumTracker(sim.config, window=window)
    # A 100-wide run ending past the ring end (starts at window-30).
    start = window - 30
    for t in (dict_tracker, tpu_tracker):
        for slot in range(start, start + 100):
            t.record(slot, 0, 0, 0)
            t.record(slot, 0, 0, 1)
    got = drain_and_collect(tpu_tracker)
    assert sorted(got) == sorted(dict_tracker.drain())
    assert tpu_tracker.device_launches > 1  # split at the ring end


def test_acceptor_packs_fragmented_drains():
    """A fragmented drain (>4 runs, >=16 acks) ships as ONE packed
    Phase2bVotes; contiguous drains keep the Phase2bRange shape."""
    from frankenpaxos_tpu import native
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        Phase2bRange,
        Phase2bVotes,
    )

    sim = make_multipaxos(f=1)
    acceptor = sim.acceptors[0]
    # Fragmented: every other slot over a 40-slot span.
    acceptor._pending_phase2bs = {"proxy": [(s, 0)
                                            for s in range(0, 40, 2)]}
    sent = []
    acceptor.send = lambda dst, m: sent.append(m)
    acceptor.on_drain()
    assert len(sent) == 1 and isinstance(sent[0], Phase2bVotes)
    slots, rounds = native.unpack_votes2(sent[0].packed)
    assert list(slots) == list(range(0, 40, 2))
    assert set(rounds.tolist()) == {0}

    # Contiguous: one range.
    acceptor._pending_phase2bs = {"proxy": [(s, 0) for s in range(20)]}
    sent.clear()
    acceptor.on_drain()
    assert len(sent) == 1 and isinstance(sent[0], Phase2bRange)


def test_quorum_tracker_record_votes_matches_dict():
    """Packed array votes (record_votes) agree with the oracle: the
    board's vectorized expansion beside the dict's default per-slot
    one."""
    import numpy as np

    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    for seed in (7, 8):
        rng = random.Random(seed)
        dict_tracker = DictQuorumTracker(sim.config)
        tpu_tracker = TpuQuorumTracker(sim.config, window=1 << 12)
        cursor = 0
        for _ in range(10):
            run_len = rng.randrange(8, 60)
            # Each acceptor votes a random fragmented subset, delivered
            # as packed arrays.
            for acc in range(3):
                picked = sorted(s for s in range(cursor,
                                                 cursor + run_len)
                                if rng.random() < 0.7)
                slots = np.asarray(picked, dtype=np.int32)
                rounds = np.zeros(len(picked), dtype=np.int32)
                dict_tracker.record_votes(slots, rounds, 0, acc)
                tpu_tracker.record_votes(slots, rounds, 0, acc)
            cursor += run_len
            assert sorted(drain_and_collect(dict_tracker)) == \
                sorted(drain_and_collect(tpu_tracker)), (seed, cursor)
        assert tpu_tracker.device_launches >= 10


def test_quorum_tracker_trickle_drains_match_dict_on_the_board():
    """Drains of one and two votes -- a serial client's, where nothing
    amortises a launch -- go to the board like any other and report
    what the oracle reports, over a few hundred drains and more than
    one turn of the ring, quorums straddling drains included."""
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )

    sim = make_multipaxos(f=1)
    window = 64
    rng = random.Random(33)
    trackers = [DictQuorumTracker(sim.config),
                TpuQuorumTracker(sim.config, window=window)]
    reported = 0
    drains = 0
    for slot in range(3 * window):
        # The slot's two votes in one drain, or one a drain; now and
        # then a third vote for an older slot rides along (a re-ack:
        # it reports nothing).
        first, second = rng.sample(range(3), 2)
        together = rng.random() < 0.4
        for acceptors in ([first, second] if together
                          else [first], [second]):
            for t in trackers:
                for acceptor in acceptors:
                    t.record(slot, 0, 0, acceptor)
            if not together and slot and rng.random() < 0.2:
                for t in trackers:
                    t.record(slot - 1, 0, 0, first)
            got = [sorted(drain_and_collect(t)) for t in trackers]
            assert got[0] == got[1], (slot, got)
            reported += len(got[1])
            drains += 1
            if together:
                break
    assert reported == 3 * window and drains > 250
    board = trackers[1]
    assert board.device_drains == drains
    assert board.device_launches >= drains
    assert board.checker.window_violations == 0

"""MultiPaxos + paxlog: crash-restart recovery over SimTransport.

The scenario class the repo could not previously express: a role dies
(`kill -9` semantics -- volatile state wiped, synced WAL state
survives) and rejoins. Deterministic integration tests pin the
group-commit contract; the chaos SimulatedSystem interleaves
crash_restart of acceptors/replicas with drops, partitions, and leader
changes (tier-1 runs a regression-smoke scale; tests/soak.py runs the
full 500x250 -- bench_results/wal_chaos_soak.json).
"""

import random
from typing import Optional

import pytest

from frankenpaxos_tpu.sim import SimulatedSystem, Simulator
from tests.protocols.multipaxos_harness import (
    crash_restart_acceptor,
    crash_restart_replica,
    executed_prefix,
    make_multipaxos,
)
from tests.protocols.test_multipaxos import FlushCmd, TransportCmd, WriteCmd


def drive(sim, lo, hi, got):
    for p in range(lo, hi):
        sim.clients[0].write(p % 4, b"v%d" % p, got.append)
        sim.transport.deliver_all()


class TestCrashRestartIntegration:
    def test_wal_pipeline_matches_no_wal(self):
        """WAL on vs off: same writes, same replica logs and replies
        (durability must not change agreement)."""
        logs = {}
        for wal in (False, True):
            sim = make_multipaxos(f=1, wal=wal)
            got = []
            drive(sim, 0, 20, got)
            assert got == [b"%d" % i for i in range(20)]
            logs[wal] = executed_prefix(sim.replicas[0])
            assert executed_prefix(sim.replicas[1]) == logs[wal]
        assert logs[False] == logs[True]

    def test_acceptor_crash_restart_preserves_votes_across_failover(self):
        """Votes synced before the crash must survive restart: a
        post-restart leader change recovers every chosen value from
        the restarted acceptors' WALs."""
        sim = make_multipaxos(f=1, wal=True, coalesced=True)
        got = []
        for p in range(16):
            sim.clients[0].write(p, b"w%d" % p, got.append)
        sim.clients[0].flush_writes()
        sim.transport.deliver_all_coalesced()
        assert len(got) == 16
        before = executed_prefix(sim.replicas[0])

        for i in range(3):  # kill -9 EVERY acceptor, then restart
            crash_restart_acceptor(sim, i)
        for i, acceptor in enumerate(sim.acceptors):
            assert acceptor.max_voted_slot >= 0, i  # recovered votes
        sim.leaders[1].leader_change(is_new_leader=True)
        sim.leaders[0].leader_change(is_new_leader=False)
        sim.transport.deliver_all_coalesced()
        after = executed_prefix(sim.replicas[0])
        assert after[:len(before)] == before

        # The cluster keeps serving after recovery + failover.
        for p in range(16, 24):
            sim.clients[0].write(p, b"w%d" % p, got.append)
        sim.clients[0].flush_writes()
        sim.transport.deliver_all_coalesced()
        for t in list(sim.transport.running_timers()):
            if t.name.startswith("resendWrite"):
                t.run()
        sim.transport.deliver_all_coalesced()
        assert len(got) == 24

    def test_unsynced_vote_is_never_acked_and_never_recovered(self):
        """THE group-commit rule: a vote staged but not yet synced
        (crash before on_drain) produced no ack and is absent after
        restart -- so no peer can have depended on it."""
        from frankenpaxos_tpu.protocols.multipaxos.messages import (
            NOOP,
            Phase2aRun,
        )

        sim = make_multipaxos(f=1, wal=True)
        acceptor = sim.acceptors[0]
        sim.transport.messages.clear()
        # Deliver a run to receive() WITHOUT the drain that would
        # group-commit it (the crash window).
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=0, round=0, values=(NOOP, NOOP)))
        assert acceptor.max_voted_slot == 1  # voted in memory...
        assert sim.transport.messages == []  # ...but nothing acked
        crash_restart_acceptor(sim, 0)
        assert sim.acceptors[0].max_voted_slot == -1  # vote died

        # The same sequence WITH the drain: ack released after sync,
        # vote survives the crash.
        acceptor = sim.acceptors[0]
        acceptor.receive("proxy-leader-0", Phase2aRun(
            start_slot=0, round=0, values=(NOOP, NOOP)))
        acceptor.on_drain()
        assert len(sim.transport.messages) == 1  # the Phase2bRange
        crash_restart_acceptor(sim, 0)
        assert sim.acceptors[0].max_voted_slot == 1

    def test_replica_crash_restart_recovers_sm_and_client_table(self):
        """The restarted replica rebuilds its SM from the WAL and the
        client table keeps re-sent commands exactly-once."""
        sim = make_multipaxos(f=1, wal=True)
        got = []
        drive(sim, 0, 12, got)
        sm_before = sim.replicas[0].state_machine.get()
        assert len(sm_before) == 12

        crash_restart_replica(sim, 0)
        replica = sim.replicas[0]
        assert replica.state_machine.get() == sm_before
        assert replica.executed_watermark == \
            sim.replicas[1].executed_watermark
        # Exactly-once through the recovered client table: a duplicate
        # Chosen for an executed slot is ignored.
        drive(sim, 12, 16, got)
        assert len(got) == 16
        executed = sim.replicas[0].state_machine.get()
        assert executed == sim.replicas[1].state_machine.get()
        for p in range(16):
            assert executed.count(b"v%d" % p) == 1

    def test_replica_compaction_snapshot_then_crash(self):
        """Enough traffic to trigger segment rotation + compaction:
        recovery comes from the snapshot, and the reclaimed log stays
        O(live state)."""
        sim = make_multipaxos(f=1, wal=True)
        got = []
        for p in range(80):
            sim.clients[0].write(p % 4, b"big-%03d-" % p + b"x" * 120,
                                 got.append)
            sim.transport.deliver_all()
        assert len(got) == 80
        replica = sim.replicas[0]
        assert replica.wal.metrics.compactions >= 1
        assert replica.log.watermark > 0  # watermark GC reached disk
        assert not list(replica.log.items())  # nothing executed stays

        sm_before = replica.state_machine.get()
        crash_restart_replica(sim, 0)
        assert sim.replicas[0].state_machine.get() == sm_before
        assert sim.replicas[0].wal.metrics.recovered_records >= 1

        # Acceptors compacted too (their stores were re-logged).
        assert any(a.wal.metrics.compactions >= 1 for a in sim.acceptors)
        crash_restart_acceptor(sim, 0)
        assert sim.acceptors[0].max_voted_slot >= 0

    def test_crash_during_leader_change_phase1(self):
        """An acceptor that promised in Phase1 and crashed must come
        back with the promise (a forgotten promise would let the OLD
        leader keep committing in a round the NEW leader believes it
        owns)."""
        sim = make_multipaxos(f=1, wal=True)
        got = []
        drive(sim, 0, 4, got)
        sim.leaders[1].leader_change(is_new_leader=True)
        sim.transport.deliver_all()  # Phase1a/1b exchange completes
        rounds = [a.round for a in sim.acceptors]
        crash_restart_acceptor(sim, 0)
        assert sim.acceptors[0].round == rounds[0]  # promise survived


# --- a drain that compacts: what leaves first, and a crash at each call ------


class _ObservedStorage:
    """A MemStorage whose every call that changes the disk is noted,
    with the disk as a crash right after the call would leave it
    (MemStorage's crash model: what was appended survives, whole)."""

    def __init__(self, inner, events):
        self.inner = inner
        self.events = events

    def _note(self, call, name):
        self.events.append((call, name, {
            n: bytes(data) for n, data in self.inner.files.items()}))

    def append(self, name, data):
        self.inner.append(name, data)
        self._note("append", name)

    def sync(self, name):
        self.inner.sync(name)
        self._note("sync", name)

    def delete(self, name):
        self.inner.delete(name)
        self._note("delete", name)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _observe(role):
    """Note, in one list and in order, what ``role`` sends and what it
    does to its storage."""
    events = []
    role.wal.storage = _ObservedStorage(role.wal.storage, events)
    send = role.send

    def observed_send(dst, message, serializer=None):
        events.append(("send", message))
        send(dst, message, serializer)

    role.send = observed_send
    return events


def _restart_from(sim, kind, disk):
    """kill -9 role 0 of ``kind`` and restart it over ``disk``."""
    from frankenpaxos_tpu.wal import MemStorage

    role = (sim.acceptors if kind == "acceptor" else sim.replicas)[0]
    storage = MemStorage()
    storage.files = {n: bytearray(data) for n, data in disk.items()}
    sim.wal_storages[role.address] = storage
    if kind == "acceptor":
        crash_restart_acceptor(sim, 0)
        return sim.acceptors[0]
    crash_restart_replica(sim, 0)
    return sim.replicas[0]


def _drain_until_compaction(kind):
    """Drive role 0 of ``kind`` alone, a run a drain, until a drain
    compacts. Returns the sim, that drain's events, and what had been
    acknowledged when each of them happened: an acceptor's acked slots,
    a replica's answered commands (their indices in its AppendLog)."""
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        ChosenRun,
        ClientReply,
        ClientReplyArray,
        Command,
        CommandBatch,
        CommandId,
        NOOP,
        Phase2aRun,
        Phase2bRange,
    )

    sim = make_multipaxos(f=1, wal=True)
    role = (sim.acceptors if kind == "acceptor" else sim.replicas)[0]
    events = _observe(role)
    acked: set = set()
    width = 8
    for drain in range(400):
        del events[:]
        start = drain * width
        if kind == "acceptor":
            role.receive("proxy-leader-0", Phase2aRun(
                start_slot=start, round=0, values=(NOOP,) * width))
        else:
            role.receive("proxy-leader-0", ChosenRun(
                start_slot=start, values=tuple(
                    CommandBatch((Command(
                        CommandId("client-0", slot % 4, slot),
                        b"payload-%05d" % slot),))
                    for slot in range(start, start + width))))
        role.on_drain()
        acked_at = []
        for event in events:
            if event[0] == "send":
                message = event[1]
                if isinstance(message, Phase2bRange):
                    acked |= set(range(message.slot_start_inclusive,
                                       message.slot_end_exclusive))
                elif isinstance(message, ClientReplyArray):
                    acked |= {int(e[3]) for e in message.entries}
                elif isinstance(message, ClientReply):
                    acked.add(int(message.result))
            acked_at.append(frozenset(acked))
        if role.wal.metrics.compactions:
            return sim, events, acked_at
    raise AssertionError(f"{kind} never compacted")


def _compaction_calls(events):
    """Indices into ``events`` of a compacting drain's storage calls:
    the group commit's append and sync, then the compaction's append
    of the new segment, its sync, and a delete an old segment."""
    calls = [i for i, e in enumerate(events) if e[0] != "send"]
    kinds = [events[i][0] for i in calls]
    assert kinds[:4] == ["append", "sync", "append", "sync"], kinds
    assert kinds[4:] and set(kinds[4:]) == {"delete"}, kinds
    assert events[calls[2]][1] > events[calls[0]][1]  # a NEW segment
    return calls


ACKS = {"acceptor": "Phase2bRange", "replica": "ClientReplyArray"}


@pytest.mark.parametrize("kind", sorted(ACKS))
def test_a_compacting_drain_releases_its_acks_before_the_rewrite(kind):
    """sync -> release -> compact (wal/role.py): the drain's acks,
    which its group commit has made durable, are sent before the
    compaction's first write, not after its last delete."""
    _, events, _ = _drain_until_compaction(kind)
    calls = _compaction_calls(events)
    sends = [i for i, e in enumerate(events) if e[0] == "send"]
    acks = [i for i in sends
            if type(events[i][1]).__name__ == ACKS[kind]]
    assert acks, events
    assert all(calls[1] < i < calls[2] for i in sends), \
        [e[:2] for e in events]


@pytest.mark.parametrize("crash_after", [
    "new-segment-append", "new-segment-sync", "first-delete",
    "middle-delete", "last-delete"])
@pytest.mark.parametrize("kind", sorted(ACKS))
def test_crash_inside_a_compaction_loses_no_acked_state(kind, crash_after):
    """The acks left BEFORE the compaction, so a crash after any of
    its storage calls must recover all they depended on: the old
    segments are whole until the new one is synced, and recovery
    resets at the new one's WalSnapshot."""
    sim, events, acked_at = _drain_until_compaction(kind)
    calls = _compaction_calls(events)
    deletes = calls[4:]
    assert len(deletes) >= 3  # rotation made several old segments
    at = {"new-segment-append": calls[2],
          "new-segment-sync": calls[3],
          "first-delete": deletes[0],
          "middle-delete": deletes[len(deletes) // 2],
          "last-delete": deletes[-1]}[crash_after]
    acked = acked_at[at]
    assert acked and acked == acked_at[-1]  # this drain's acks too
    before = (sim.replicas[0].state_machine.get()
              if kind == "replica" else None)
    role = _restart_from(sim, kind, events[at][2])
    if kind == "acceptor":
        voted = {info.slot for info in role._voted_info(0)}
        assert acked <= voted, sorted(acked - voted)
        assert role.round == 0
    else:
        executed = role.state_machine.get()
        assert max(acked) < len(executed)
        assert executed == before[:len(executed)]
        assert role.executed_watermark == len(executed)


# --- the chaos simulated system --------------------------------------------


class CrashCmd:
    def __init__(self, kind, index):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Crash({self.kind}, {self.index})"


class PartitionCmd:
    def __init__(self, address, heal):
        self.address = address
        self.heal = heal

    def __repr__(self):
        return f"{'Heal' if self.heal else 'Partition'}({self.address})"


class LeaderChangeCmd:
    def __init__(self, index):
        self.index = index

    def __repr__(self):
        return f"LeaderChange({self.index})"


class SettleCmd:
    """Drain the network in coalesced waves (bounded). The pure
    single-delivery exploration rarely completes an execution before
    election churn restarts Phase1; an occasional settle guarantees
    every run commits real entries BETWEEN chaos events, so crashes
    hit executed state (SM snapshots, client tables), not just
    in-flight votes. Deterministic, hence minimizer-replayable."""

    def __repr__(self):
        return "Settle()"


class MultiPaxosWalSimulated(SimulatedSystem):
    """The WAL chaos soak: random writes/flushes/deliveries/timers
    INTERLEAVED with crash_restart of acceptors and replicas,
    partitions, and forced leader changes. The oracle is the host SM:
    executed command sequences must stay mutually prefix-compatible,
    only grow (except across that replica's own crash, where regression
    to the durable prefix is the correct semantics), and execute every
    payload at most once."""

    def __init__(self, **harness_kwargs):
        self.harness_kwargs = harness_kwargs

    def new_system(self, seed):
        sim = make_multipaxos(seed=seed, num_clients=2, wal=True,
                              **self.harness_kwargs)
        sim._counter = 0
        sim._crash_epochs = {"acceptor": [0] * len(sim.acceptors),
                             "replica": [0] * len(sim.replicas)}
        return sim

    def generate_command(self, sim, rng: random.Random):
        choices = []
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
            choices.extend(["transport"] * 6)
        # Rare chaos: frequent enough that every run crashes a few
        # roles, rare enough that commits still happen between events
        # (an exploration that never commits checks nothing).
        if rng.random() < 0.25:
            choices.append("crash")
        if rng.random() < 0.2:
            choices.append("partition")
        if rng.random() < 0.1:
            choices.append("leader_change")
        if rng.random() < 0.08:
            choices.append("settle")
        kind = rng.choice(choices)
        if kind == "write":
            client, pseudonym = rng.choice(idle)
            sim._counter += 1
            return WriteCmd(client, pseudonym, b"w%d" % sim._counter)
        if kind == "flush":
            return FlushCmd(rng.choice(staged))
        if kind == "crash":
            role = rng.choice(["acceptor", "replica"])
            n = len(sim.acceptors if role == "acceptor"
                    else sim.replicas)
            return CrashCmd(role, rng.randrange(n))
        if kind == "partition":
            candidates = ([a.address for a in sim.acceptors]
                          + [r.address for r in sim.replicas]
                          + list(sim.config.proxy_leader_addresses))
            partitioned = [a for a in candidates
                           if a in sim.transport.partitioned]
            if partitioned and rng.random() < 0.6:
                return PartitionCmd(rng.choice(partitioned), heal=True)
            return PartitionCmd(rng.choice(candidates), heal=False)
        if kind == "leader_change":
            return LeaderChangeCmd(rng.randrange(len(sim.leaders)))
        if kind == "settle":
            return SettleCmd()
        return TransportCmd(transport_cmd)

    def run_command(self, sim, command):
        if isinstance(command, WriteCmd):
            client = sim.clients[command.client]
            if command.pseudonym not in client.states:
                client.write(command.pseudonym, command.payload)
        elif isinstance(command, FlushCmd):
            sim.clients[command.client].flush_writes()
        elif isinstance(command, CrashCmd):
            if command.kind == "acceptor":
                crash_restart_acceptor(sim, command.index)
            else:
                crash_restart_replica(sim, command.index)
            sim._crash_epochs[command.kind][command.index] += 1
        elif isinstance(command, PartitionCmd):
            if command.heal:
                sim.transport.heal(command.address)
            else:
                sim.transport.partition(command.address)
        elif isinstance(command, LeaderChangeCmd):
            for i, leader in enumerate(sim.leaders):
                leader.leader_change(is_new_leader=(i == command.index))
        elif isinstance(command, SettleCmd):
            sim.transport.deliver_all_coalesced(max_steps=400)
        else:
            sim.transport.run_command(command.command)
        return sim

    def get_state(self, sim):
        return tuple(
            (sim._crash_epochs["replica"][i],
             tuple(r.state_machine.get()))
            for i, r in enumerate(sim.replicas))

    def state_invariant(self, sim) -> Optional[str]:
        seqs = [r.state_machine.get() for r in sim.replicas]
        for i in range(len(seqs)):
            for j in range(i + 1, len(seqs)):
                n = min(len(seqs[i]), len(seqs[j]))
                if seqs[i][:n] != seqs[j][:n]:
                    return (f"replica SM sequences diverge: {seqs[i]!r} "
                            f"vs {seqs[j]!r}")
        for i, seq in enumerate(seqs):
            if len(set(seq)) != len(seq):
                return f"replica {i} executed a payload twice: {seq!r}"
        # Chosen-value uniqueness per SLOT -- the sharp oracle for
        # durability loss: if a crashed acceptor forgets a synced vote,
        # a later leader can choose Noop (or another value) for a slot
        # some replica already holds, and this catches it the moment
        # the second replica learns the conflicting value, without
        # waiting for executions to diverge.
        logs: dict = {}
        for i, r in enumerate(sim.replicas):
            # Every value the address was ever handed, across its
            # restarts (the harness's record: a replica's own log
            # holds only what it has not executed yet).
            record = r.execution_record
            if record.conflicts:
                return (f"replica {i} was handed two values for a "
                        f"slot: {record.conflicts!r}")
            for slot, value in record.chosen.items():
                prev = logs.get(slot)
                if prev is not None and prev[1] != value:
                    return (f"slot {slot} chosen twice: replica "
                            f"{prev[0]} has {prev[1]!r}, replica {i} "
                            f"has {value!r}")
                logs[slot] = (i, value)
        return None

    def step_invariant(self, old_state, new_state) -> Optional[str]:
        for (old_epoch, old_seq), (new_epoch, new_seq) in zip(old_state,
                                                              new_state):
            if new_epoch != old_epoch:
                # This replica crashed this step: regression to its
                # durable prefix is the CORRECT crash semantics (the
                # unsynced suffix was never acked); compatibility with
                # the other replicas is still enforced by
                # state_invariant.
                continue
            if list(new_seq[:len(old_seq)]) != list(old_seq):
                return (f"replica SM sequence shrank/rewrote without a "
                        f"crash: {old_seq} -> {new_seq}")
        return None


@pytest.mark.parametrize("kwargs", [
    dict(f=1),
    dict(f=1, coalesced=True),
    dict(f=2, coalesced="mixed"),
], ids=["f1", "f1-coalesced", "f2-mixed"])
def test_simulation_crash_restart_no_divergence(kwargs):
    """Regression-smoke scale; tests/soak.py runs 500x250."""
    simulated = MultiPaxosWalSimulated(**kwargs)
    failure = Simulator(simulated, run_length=150, num_runs=10).run(seed=0)
    assert failure is None, str(failure)

"""Wire a whole MultiPaxos deployment over one SimTransport.

The analog of the reference's test harness
(shared/src/test/scala/multipaxos/MultiPaxos.scala:17-171): every role
in one process, driven by explicit message deliveries / timer firings.
"""

from __future__ import annotations

import dataclasses

from frankenpaxos_tpu.protocols.multipaxos import (
    Acceptor,
    Batcher,
    BatcherOptions,
    Client,
    ClientOptions,
    DistributionScheme,
    Leader,
    LeaderOptions,
    MultiPaxosConfig,
    ProxyLeader,
    ProxyLeaderOptions,
    ProxyReplica,
    ReadBatcher,
    ReadBatchingScheme,
    Replica,
    ReplicaOptions,
)
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    Chosen,
    ChosenRun,
    CommandBatch,
)
from frankenpaxos_tpu.protocols.multipaxos.wire import LazyValueArray
from frankenpaxos_tpu.runtime import FakeLogger, LogLevel, SimTransport
from frankenpaxos_tpu.statemachine import AppendLog, StateMachine


@dataclasses.dataclass
class MultiPaxosSim:
    transport: SimTransport
    config: MultiPaxosConfig
    batchers: list
    leaders: list
    proxy_leaders: list
    acceptors: list
    replicas: list
    proxy_replicas: list
    clients: list
    # paxingest disseminators (ingest/): WAL-free, rebuilt empty on
    # crash_restart.
    ingest_batchers: list = dataclasses.field(default_factory=list)
    # wal=True extras: address -> MemStorage (survives crash_restart),
    # plus what a restart needs to rebuild the actor.
    wal_storages: dict = dataclasses.field(default_factory=dict)
    state_machine_factory: object = None
    seed: int = 0


#: Small segment/compaction thresholds so sim runs exercise rotation
#: and snapshot GC, not just appends.
_SIM_WAL_SEGMENT_BYTES = 2048
_SIM_WAL_COMPACT_BYTES = 8192


def _sim_wal(sim_or_storages, address, root=None):
    """A Wal over the (surviving) MemStorage for ``address`` -- or,
    with ``root`` set (the wal_lt bench's real-fsync arm), over
    FileStorage at <root>/<address>."""
    from frankenpaxos_tpu.wal import FileStorage, MemStorage, Wal

    storages = getattr(sim_or_storages, "wal_storages", sim_or_storages)
    if root is not None:
        import os

        storage = storages.setdefault(
            address, FileStorage(os.path.join(root, str(address))))
        return Wal(storage)
    storage = storages.setdefault(address, MemStorage())
    return Wal(storage, segment_bytes=_SIM_WAL_SEGMENT_BYTES,
               compact_every_bytes=_SIM_WAL_COMPACT_BYTES)


def crash_restart_acceptor(sim: "MultiPaxosSim", i: int) -> None:
    """kill -9 acceptor ``i`` and restart it from its WAL: volatile
    state (staged acks, the unsynced group-commit buffer) dies; synced
    promises/votes/runs recover. Replacement acceptors (reconfig)
    relaunch with THEIR recorded config, like the deployed relaunch
    reuses the replacement's own config file."""
    old = sim.acceptors[i]
    config = getattr(sim, "acceptor_configs", {}).get(old.address,
                                                      sim.config)
    sim.transport.crash(old.address)
    sim.acceptors[i] = Acceptor(
        old.address, sim.transport, sim.transport.logger, config,
        old.options, wal=_sim_wal(sim, old.address))


def add_replacement_acceptor(sim: "MultiPaxosSim", members: tuple,
                             new_address) -> None:
    """Construct a reconfiguration replacement: a NEW acceptor at
    ``new_address`` whose config lists exactly ``members`` as the
    acceptor group (the deployed driver's rewritten-config shape).
    The caller then sends ``Reconfigure(members)`` to the leader."""
    import dataclasses as _dc

    assert new_address in members
    config = _dc.replace(sim.config,
                         acceptor_addresses=[list(members)])
    if not hasattr(sim, "acceptor_configs"):
        sim.acceptor_configs = {}
    sim.acceptor_configs[new_address] = config
    sim.acceptors.append(Acceptor(
        new_address, sim.transport, sim.transport.logger, config,
        wal=_sim_wal(sim, new_address)))


def crash_restart_ingest_batcher(sim: "MultiPaxosSim", i: int) -> None:
    """kill -9 ingest batcher ``i`` and restart it EMPTY: batchers are
    WAL-free by design -- staged-but-unshipped commands die with the
    process and the owning clients' resend timers cover them (retries,
    never acked-write loss; the replica client table keeps resends
    exactly-once)."""
    from frankenpaxos_tpu.ingest import (
        IngestBatcher,
        MultiPaxosIngestRouter,
    )

    old = sim.ingest_batchers[i]
    sim.transport.crash(old.address)
    sim.ingest_batchers[i] = IngestBatcher(
        old.address, sim.transport, sim.transport.logger,
        MultiPaxosIngestRouter(sim.config), index=i, options=old.options,
        seed=sim.seed + 50 + i)


def crash_restart_replica(sim: "MultiPaxosSim", i: int) -> None:
    """kill -9 replica ``i`` and restart it: the SM rebuilds from the
    WAL snapshot + chosen-record replay; unsynced executions (never
    acked, by the group-commit rule) are re-learned or re-requested."""
    old = sim.replicas[i]
    sim.transport.crash(old.address)
    sim.replicas[i] = Replica(
        old.address, sim.transport, sim.transport.logger,
        sim.state_machine_factory(), sim.config, old.options,
        seed=sim.seed + 20 + i, wal=_sim_wal(sim, old.address))
    # What the address was handed outlives the crash; what its state
    # machine ran starts again with the recovery, which is not seen.
    record_execution(sim.replicas[i], ExecutionRecord(
        chosen=old.execution_record.chosen, complete=False))


def make_multipaxos(
    f: int = 1,
    num_clients: int = 1,
    num_acceptor_groups: int = 1,
    num_batchers: int = 0,
    num_ingest_batchers: int = 0,
    num_read_batchers: int = 0,
    read_batching_scheme: ReadBatchingScheme = ReadBatchingScheme(
        kind="size", batch_size=1),
    num_proxy_replicas: int = 0,
    flexible: bool = False,
    grid_shape: tuple[int, int] | None = None,
    batch_size: int = 1,
    quorum_backend: str = "dict",
    coalesced: "bool | str" = False,
    phase1_backend: str = "host",
    state_machine_factory=AppendLog,
    seed: int = 0,
    log_level: LogLevel = LogLevel.FATAL,
    wal: "bool | str" = False,
    epoch_tag_runs: bool = False,
    epoch_quorums: bool = False,
    leader_admission: dict | None = None,
    client_retry_budget: int = 0,
    client_backoff=None,
    ingest_pipeline_window: int | None = None,
) -> MultiPaxosSim:
    """``wal``: False (reference in-memory behavior), True (MemStorage
    WALs, the crash-restart sims), or a directory path (FileStorage
    WALs with REAL fsyncs -- the wal_lt bench's measured arm)."""
    logger = FakeLogger(log_level)
    transport = SimTransport(logger)
    wal_storages: dict = {}
    if wal is False:
        wal_for = lambda a: None  # noqa: E731
    elif wal is True:
        wal_for = lambda a: _sim_wal(wal_storages, a)  # noqa: E731
    else:
        wal_for = lambda a: _sim_wal(wal_storages, a,  # noqa: E731
                                     root=wal)

    if flexible:
        rows, cols = grid_shape or (f + 1, f + 1)
        acceptor_addresses = [[f"acceptor-{g}-{i}" for i in range(cols)]
                              for g in range(rows)]
    else:
        acceptor_addresses = [
            [f"acceptor-{g}-{i}" for i in range(2 * f + 1)]
            for g in range(num_acceptor_groups)]

    config = MultiPaxosConfig(
        f=f,
        batcher_addresses=[f"batcher-{i}" for i in range(num_batchers)],
        ingest_batcher_addresses=[f"ingest-batcher-{i}"
                                  for i in range(num_ingest_batchers)],
        read_batcher_addresses=[f"read-batcher-{i}"
                                for i in range(num_read_batchers)],
        leader_addresses=[f"leader-{i}" for i in range(f + 1)],
        leader_election_addresses=[f"election-{i}" for i in range(f + 1)],
        proxy_leader_addresses=[f"proxy-leader-{i}" for i in range(f + 1)],
        acceptor_addresses=acceptor_addresses,
        replica_addresses=[f"replica-{i}" for i in range(f + 1)],
        proxy_replica_addresses=[f"proxy-replica-{i}"
                                 for i in range(num_proxy_replicas)],
        flexible=flexible,
        distribution_scheme=DistributionScheme.HASH,
    )
    config.check_valid()

    batchers = [
        Batcher(a, transport, logger, config,
                BatcherOptions(batch_size=batch_size))
        for a in config.batcher_addresses]
    from frankenpaxos_tpu.ingest import (
        IngestBatcher,
        IngestBatcherOptions,
        MultiPaxosIngestRouter,
    )

    ingest_options = IngestBatcherOptions()
    if ingest_pipeline_window is not None:
        # Chaos rows pin tight descriptor windows so IngestCredit
        # watermarks are load-bearing under kill/partition, not slack.
        ingest_options = IngestBatcherOptions(
            pipeline_window=ingest_pipeline_window)
    ingest_batchers = [
        IngestBatcher(a, transport, logger,
                      MultiPaxosIngestRouter(config), index=i,
                      options=ingest_options, seed=seed + 50 + i)
        for i, a in enumerate(config.ingest_batcher_addresses)]
    read_batchers = [
        ReadBatcher(a, transport, logger, config, read_batching_scheme,
                    seed=seed + 40 + i)
        for i, a in enumerate(config.read_batcher_addresses)]
    leaders = [
        Leader(a, transport, logger, config,
               LeaderOptions(resend_phase1as_period_s=5.0,
                             phase1_backend=phase1_backend,
                             epoch_tag_runs=epoch_tag_runs,
                             **(leader_admission or {})),
               seed=seed + i)
        for i, a in enumerate(config.leader_addresses)]
    proxy_leaders = [
        ProxyLeader(a, transport, logger, config,
                    ProxyLeaderOptions(
                        quorum_backend=quorum_backend,
                        tpu_window=1 << 12,
                        epoch_quorums=epoch_quorums),
                    seed=seed + 10 + i)
        for i, a in enumerate(config.proxy_leader_addresses)]
    acceptors = [
        Acceptor(a, transport, logger, config, wal=wal_for(a))
        for group in config.acceptor_addresses for a in group]
    replicas = [
        Replica(a, transport, logger, state_machine_factory(), config,
                ReplicaOptions(send_chosen_watermark_every_n_entries=10),
                seed=seed + 20 + i, wal=wal_for(a))
        for i, a in enumerate(config.replica_addresses)]
    for replica in replicas:
        record_execution(replica, ExecutionRecord())
    proxy_replicas = [
        ProxyReplica(a, transport, logger, config)
        for a in config.proxy_replica_addresses]
    # coalesced=True: every client stages writes into request arrays;
    # "mixed": even-indexed clients coalesce while odd ones send
    # per-message ClientRequests, so the run pipeline and the per-slot
    # path interleave in one cluster (the adversarial shape for the
    # proxy leader's dual pending stores). Reject anything else: a
    # typo'd mode would silently run fully per-message and a config
    # labeled "coalesced" would cover nothing.
    assert coalesced in (False, True, "mixed"), coalesced
    client_opt_extra: dict = {}
    if client_retry_budget:
        client_opt_extra["retry_budget"] = client_retry_budget
    if client_backoff is not None:
        client_opt_extra["backoff"] = client_backoff
    clients = [
        Client(f"client-{i}", transport, logger, config,
               ClientOptions(coalesce_writes=(
                   coalesced is True
                   or (coalesced == "mixed" and i % 2 == 0)),
                   **client_opt_extra),
               seed=seed + 30 + i)
        for i in range(num_clients)]

    return MultiPaxosSim(transport, config, batchers, leaders, proxy_leaders,
                         acceptors, replicas, proxy_replicas, clients,
                         ingest_batchers=ingest_batchers,
                         wal_storages=wal_storages,
                         state_machine_factory=state_machine_factory,
                         seed=seed)


@dataclasses.dataclass
class ExecutionRecord:
    """What one replica was handed and what its state machine ran,
    kept outside the replica: its log holds only what it cannot
    execute yet, so the tests' view of an executed slot is theirs."""

    # slot -> the value the address was first handed for it.
    chosen: dict = dataclasses.field(default_factory=dict)
    # (slot, first value, later value) of a slot handed two values.
    conflicts: list = dataclasses.field(default_factory=list)
    # (slot, payload) a write the state machine ran, in order.
    ran: list = dataclasses.field(default_factory=list)
    # False once ``ran`` misses a part (a recovery from the WAL).
    complete: bool = True
    reading: bool = False

    def hand(self, start_slot: int, values) -> None:
        for slot, value in enumerate(values, start_slot):
            first = self.chosen.setdefault(slot, value)
            if first != value:
                self.conflicts.append((slot, first, value))


def record_execution(replica: Replica, record: ExecutionRecord) -> None:
    """Put ``record`` round ``replica``: every Chosen / ChosenRun it
    is delivered (decoded by the recorder's own copy of the array, so
    the replica's stays as it came), and every write its state machine
    runs with the slot it ran in (``executed_watermark`` is the slot
    under execution). Reads run the state machine too and are left
    out."""
    receive = replica.receive
    execute_read = replica._execute_read
    run = replica.state_machine.run

    def receiving(src, message):
        if isinstance(message, Chosen):
            record.hand(message.slot, (message.value,))
        elif isinstance(message, ChosenRun):
            values = message.values
            if isinstance(values, LazyValueArray):
                values = LazyValueArray(values.raw, values.n)
            record.hand(message.start_slot, values)
        receive(src, message)

    def reading(command):
        record.reading = True
        try:
            return execute_read(command)
        finally:
            record.reading = False

    def running(input):
        if not record.reading:
            record.ran.append((replica.executed_watermark, input))
        return run(input)

    replica.receive = receiving
    replica._execute_read = reading
    replica.state_machine.run = running
    replica.execution_record = record


def exactly_once(values, use_client_table: bool = True) -> list:
    """The (slot, payload)s a replica has to run for ``values``, a
    value a slot from 0: every command in slot order, but one whose
    client has had a command with its id or a later one executed."""
    largest: dict = {}
    ran = []
    for slot, value in enumerate(values):
        if not isinstance(value, CommandBatch):
            continue
        for command in value.commands:
            cid = command.command_id
            key = (cid.client_address, cid.client_pseudonym)
            if key in largest and cid.client_id <= largest[key]:
                continue
            if use_client_table:
                largest[key] = cid.client_id
            ran.append((slot, command.command))
    return ran


def executed_prefix(replica: Replica) -> list:
    """The values of the slots the replica has executed, as it was
    handed them; checked on the way: no slot was handed two values,
    and the state machine ran exactly what these values hold, in slot
    order, each command once."""
    record = replica.execution_record
    assert not record.conflicts, record.conflicts
    values = [record.chosen.get(slot)
              for slot in range(replica.executed_watermark)]
    if record.complete:
        assert record.ran == exactly_once(
            values, not replica.options.unsafe_dont_use_client_table)
    return values


def state_machine_of(sim: MultiPaxosSim, i: int) -> StateMachine:
    return sim.replicas[i].state_machine


def drain_and_collect(tracker) -> list:
    """One drain of ``tracker`` with everything it decided: the device
    tracker's drain only dispatches, so its dispatches are taken and
    collected until none is left; a DictQuorumTracker just drains."""
    out = list(tracker.drain())
    take = getattr(tracker, "take_dispatch", None)
    while take is not None and (dispatch := take()) is not None:
        out.extend(tracker.collect(dispatch))
    return out


def deliver_and_flush(sim: MultiPaxosSim, coalesced: bool = False) -> None:
    """Deliver until quiet, firing the proxy leaders' ``tpuDrainFlush``
    timers (what collects a ``quorum_backend="tpu"`` tracker's
    dispatches over a SimTransport) until none is armed."""
    deliver = (sim.transport.deliver_all_coalesced if coalesced
               else sim.transport.deliver_all)
    deliver()
    while flushes := [t for t in sim.transport.running_timers()
                      if t.name == "tpuDrainFlush"]:
        for timer in flushes:
            sim.transport.trigger_timer(timer.id)
        deliver()

"""Wire a whole Mencius deployment over one SimTransport.

The analog of tests/protocols/multipaxos_harness.py for the
partitioned-log protocol: every role in one process, driven by explicit
message deliveries / timer firings. Shared by the Mencius tests and the
mencius_lt bench suite (per-message vs coalesced A/B), so the driving
harness cannot drift between them.
"""

from __future__ import annotations

import dataclasses

from frankenpaxos_tpu.protocols.mencius import (
    MenciusAcceptor,
    MenciusBatcher,
    MenciusClient,
    MenciusConfig,
    MenciusLeader,
    MenciusProxyLeader,
    MenciusProxyReplica,
    MenciusReplica,
)
from frankenpaxos_tpu.runtime import FakeLogger, LogLevel, SimTransport
from frankenpaxos_tpu.statemachine import AppendLog


@dataclasses.dataclass
class MenciusSim:
    transport: SimTransport
    config: MenciusConfig
    batchers: list
    leaders: list
    proxy_leaders: list
    acceptors: list
    replicas: list
    proxy_replicas: list
    clients: list
    # paxingest disseminators (ingest/): WAL-free.
    ingest_batchers: list = dataclasses.field(default_factory=list)
    # wal=True extras (see multipaxos_harness).
    wal_storages: dict = dataclasses.field(default_factory=dict)
    state_machine_factory: object = None
    seed: int = 0


def _sim_wal(storages: dict, address):
    from tests.protocols.multipaxos_harness import (
        _SIM_WAL_COMPACT_BYTES,
        _SIM_WAL_SEGMENT_BYTES,
    )

    from frankenpaxos_tpu.wal import MemStorage, Wal

    storage = storages.setdefault(address, MemStorage())
    return Wal(storage, segment_bytes=_SIM_WAL_SEGMENT_BYTES,
               compact_every_bytes=_SIM_WAL_COMPACT_BYTES)


def crash_restart_acceptor(sim: "MenciusSim", i: int) -> None:
    old = sim.acceptors[i]
    sim.transport.crash(old.address)
    sim.acceptors[i] = MenciusAcceptor(
        old.address, sim.transport, sim.transport.logger, sim.config,
        wal=_sim_wal(sim.wal_storages, old.address))


def crash_restart_replica(sim: "MenciusSim", i: int) -> None:
    old = sim.replicas[i]
    sim.transport.crash(old.address)
    sim.replicas[i] = MenciusReplica(
        old.address, sim.transport, sim.transport.logger,
        sim.state_machine_factory(), sim.config,
        send_chosen_watermark_every_n=old.send_chosen_watermark_every_n,
        seed=sim.seed + 70 + i,
        wal=_sim_wal(sim.wal_storages, old.address))


def make_mencius(f=1, num_leader_groups=2, num_acceptor_groups=1,
                 num_batchers=0, num_ingest_batchers=0,
                 num_proxy_replicas=0, num_clients=1,
                 batch_size=1, lag_threshold=100, coalesced=False,
                 state_machine_factory=AppendLog, seed=0,
                 wal=False, leader_admission: dict | None = None,
                 client_retry_budget: int = 0,
                 client_backoff=None, quorum_backend: str = "dict",
                 tpu_window: int = 1 << 12) -> MenciusSim:
    logger = FakeLogger(LogLevel.FATAL)
    transport = SimTransport(logger)
    wal_storages: dict = {}
    wal_for = (lambda a: _sim_wal(wal_storages, a)) if wal \
        else (lambda a: None)
    config = MenciusConfig(
        f=f,
        batcher_addresses=tuple(f"batcher-{i}" for i in range(num_batchers)),
        ingest_batcher_addresses=tuple(
            f"ingest-batcher-{i}" for i in range(num_ingest_batchers)),
        leader_addresses=tuple(
            tuple(f"leader-{g}-{i}" for i in range(f + 1))
            for g in range(num_leader_groups)),
        leader_election_addresses=tuple(
            tuple(f"election-{g}-{i}" for i in range(f + 1))
            for g in range(num_leader_groups)),
        proxy_leader_addresses=tuple(
            f"proxy-leader-{i}" for i in range(f + 1)),
        acceptor_addresses=tuple(
            tuple(tuple(f"acceptor-{g}-{ag}-{i}" for i in range(2 * f + 1))
                  for ag in range(num_acceptor_groups))
            for g in range(num_leader_groups)),
        replica_addresses=tuple(f"replica-{i}" for i in range(f + 1)),
        proxy_replica_addresses=tuple(
            f"proxy-replica-{i}" for i in range(num_proxy_replicas)),
    )
    config.check_valid()
    batchers = [MenciusBatcher(a, transport, logger, config,
                               batch_size=batch_size, seed=seed + i)
                for i, a in enumerate(config.batcher_addresses)]
    from frankenpaxos_tpu.ingest import IngestBatcher, MenciusIngestRouter

    ingest_batchers = [
        IngestBatcher(a, transport, logger, MenciusIngestRouter(config),
                      index=i, seed=seed + 40 + i)
        for i, a in enumerate(config.ingest_batcher_addresses)]
    leaders = [MenciusLeader(a, transport, logger, config,
                             send_high_watermark_every_n=3,
                             send_noop_range_if_lagging_by=lag_threshold,
                             seed=seed + 10 + g * 10 + i,
                             **(leader_admission or {}))
               for g, group in enumerate(config.leader_addresses)
               for i, a in enumerate(group)]
    proxy_leaders = [MenciusProxyLeader(a, transport, logger, config,
                                        seed=seed + 50 + i,
                                        quorum_backend=quorum_backend,
                                        tpu_window=tpu_window)
                     for i, a in enumerate(config.proxy_leader_addresses)]
    acceptors = [MenciusAcceptor(a, transport, logger, config,
                                 wal=wal_for(a))
                 for groups in config.acceptor_addresses
                 for group in groups for a in group]
    replicas = [MenciusReplica(a, transport, logger,
                               state_machine_factory(), config,
                               send_chosen_watermark_every_n=5,
                               seed=seed + 70 + i, wal=wal_for(a))
                for i, a in enumerate(config.replica_addresses)]
    proxy_replicas = [MenciusProxyReplica(a, transport, logger, config)
                      for a in config.proxy_replica_addresses]
    # coalesced=True: every client stages writes into request arrays
    # (the drain-granular run pipeline); "mixed": even-indexed clients
    # coalesce while odd ones send per-message ClientRequests, so
    # strided runs and per-slot proposals interleave in one cluster.
    assert coalesced in (False, True, "mixed"), coalesced
    client_extra: dict = {}
    if client_retry_budget:
        client_extra["retry_budget"] = client_retry_budget
    if client_backoff is not None:
        client_extra["backoff"] = client_backoff
    clients = [MenciusClient(f"client-{i}", transport, logger, config,
                             coalesce_writes=(
                                 coalesced is True
                                 or (coalesced == "mixed" and i % 2 == 0)),
                             seed=seed + 90 + i, **client_extra)
               for i in range(num_clients)]
    return MenciusSim(transport, config, batchers, leaders, proxy_leaders,
                      acceptors, replicas, proxy_replicas, clients,
                      ingest_batchers=ingest_batchers,
                      wal_storages=wal_storages,
                      state_machine_factory=state_machine_factory,
                      seed=seed)


def executed_prefix(replica) -> list:
    return [replica.log.get(s) for s in range(replica.executed_watermark)]

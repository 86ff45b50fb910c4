"""Table-driven deployment registry: every protocol over TcpTransport.

The analog of the reference's 105 ``<Role>Main`` objects
(jvm/src/main/scala/frankenpaxos/<proto>/<Role>Main.scala) collapsed
into one registry. For each protocol it knows how to

  * parse a cluster-config JSON into the protocol's Config dataclass
    (the prototext analog; ConfigUtil.scala:7-43),
  * construct every role actor from ``(role, index)`` plus per-role
    ``--options.*`` overrides (LeaderMain.scala:52-80),
  * construct a client and drive one smoke command through it
    (scripts/benchmark_smoke.sh semantics),
  * generate a localhost cluster placement for tests/benchmarks.

Role option overrides are uniform: ``--options.<name>=<value>`` matches
either a keyword parameter of the role constructor or a field of its
options dataclass, coerced to the type of the declared default.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable


def _addr(x) -> tuple:
    return (x[0], int(x[1]))


def _addrs(xs) -> list:
    return [_addr(x) for x in xs]


def coerce(text: str, default: Any) -> Any:
    """Parse ``text`` to the type of ``default`` (bool/int/float/str)."""
    if isinstance(default, bool):
        return text.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(text)
    if isinstance(default, float):
        return float(text)
    return text


def ctor_kwargs(fn: Callable, overrides: dict) -> dict:
    """Overrides matching ``fn``'s defaulted keyword params, coerced."""
    out = {}
    params = inspect.signature(fn).parameters
    for name, value in overrides.items():
        p = params.get(name)
        if p is None or p.default is inspect.Parameter.empty \
                or p.default is None or dataclasses.is_dataclass(p.default):
            continue
        out[name] = coerce(value, p.default)
    return out


def options_obj(cls, overrides: dict, **fixed):
    """An options dataclass from defaults + matching overrides."""
    base = cls(**fixed)
    repl = {}
    for f in dataclasses.fields(cls):
        if f.name in fixed or f.name not in overrides:
            continue
        default = getattr(base, f.name)
        if dataclasses.is_dataclass(default) or default is None:
            continue
        repl[f.name] = coerce(overrides[f.name], default)
    return dataclasses.replace(base, **repl) if repl else base


@dataclasses.dataclass
class DeployCtx:
    """Everything a role constructor might need."""

    config: Any
    transport: Any
    logger: Any
    overrides: dict
    seed: int = 0
    state_machine: str = "AppendLog"
    collectors: Any = None  # monitoring.Collectors; None -> fakes
    # Durability root (--wal_dir): when set, WAL-capable roles get a
    # Wal over FileStorage at <wal_dir>/<label> and recover from it on
    # construction -- a SIGKILL'd role relaunched with the same
    # wal_dir rejoins with its promises/votes/SM state intact.
    wal_dir: Any = None
    # paxchaos (--fault_fsync "every:stall_s:seed"): wrap this role's
    # WAL storage in a BLOCKING FsyncStallStorage -- the deployed twin
    # of the scenario matrix's storage-fault arm (faults/,
    # wal/faults.py). None (the default) leaves the WAL path
    # completely untouched.
    wal_fault: Any = None
    consumed: set = dataclasses.field(default_factory=set)

    def sm(self):
        from frankenpaxos_tpu.statemachine import state_machine_by_name

        return state_machine_by_name(self.state_machine)

    def wal(self, label: str):
        """A per-role Wal (or None when durability is off)."""
        if not self.wal_dir:
            return None
        import os

        from frankenpaxos_tpu.wal import FileStorage, Wal

        storage = FileStorage(os.path.join(self.wal_dir, label))
        if self.wal_fault:
            from frankenpaxos_tpu.wal import FsyncStallStorage

            parts = self.wal_fault.split(":")
            if parts[0] == "P" and len(parts) == 3:
                # Periodic windows on the host wall clock -- aligned
                # across every role process on the machine.
                storage = FsyncStallStorage(
                    storage, label=label,
                    stall_period_s=float(parts[1]),
                    stall_window_s=float(parts[2]), blocking=True)
            elif parts[0] == "C" and len(parts) == 4:
                storage = FsyncStallStorage(
                    storage, seed=int(parts[3]), label=label,
                    stall_every=int(parts[1]),
                    stall_s=float(parts[2]), blocking=True)
            else:
                raise ValueError(
                    "--fault_fsync spec must be P:<period_s>:"
                    "<window_s> or C:<every>:<stall_s>:<seed>; "
                    f"got {self.wal_fault!r}")
        return Wal(storage)

    def kw(self, fn) -> dict:
        out = ctor_kwargs(fn, self.overrides)
        self.consumed.update(out)
        return out

    def opts(self, cls, **fixed):
        obj = options_obj(cls, self.overrides, **fixed)
        names = {f.name for f in dataclasses.fields(cls)}
        self.consumed.update(names & set(self.overrides))
        return obj

    def opt(self, name: str, default: str) -> str:
        if name in self.overrides:
            self.consumed.add(name)
            return self.overrides[name]
        return default

    def unmatched_overrides(self) -> list:
        return sorted(set(self.overrides) - self.consumed)


@dataclasses.dataclass(frozen=True)
class Role:
    """One deployable role: its addresses in the config + constructor."""

    addresses: Callable[[Any], list]
    make: Callable[[DeployCtx, Any, int], Any]
    # The ``--options.*`` names that, set to "tpu", make this role build
    # a device backend. One chip serves one process, so the CLI and the
    # launcher decide from this, per process, who owns the chip and who
    # is pinned to the CPU (device.py).
    device_options: tuple = ()

    def hosts_device(self, overrides: dict) -> bool:
        return any(overrides.get(name) == "tpu"
                   for name in self.device_options)


def process_label(role_name: str, index_arg: str) -> str:
    """What the process started with ``--role role_name --index
    index_arg`` is called: in its log and trace file names, in the
    launcher's books, and in the ready handshake between them."""
    return f"{role_name}_{index_arg.replace(',', '_')}"


@dataclasses.dataclass(frozen=True)
class Protocol:
    name: str
    load_config: Callable[[dict], Any]
    roles: "dict[str, Role]"
    make_client: Callable[[DeployCtx, Any], Any]
    # drive(client, tag, callback): issue one command; callback fires on
    # completion (with whatever reply type the protocol uses).
    drive: Callable[[Any, int, Callable[..., None]], None]
    cluster: Callable[[int, Callable[[], list]], dict]


# --------------------------------------------------------------------------
# Per-protocol definitions (lazy imports keep CLI startup light).
# --------------------------------------------------------------------------


def _echo() -> Protocol:
    from frankenpaxos_tpu.protocols import echo as m

    class Cfg:
        def __init__(self, raw):
            self.server = _addr(raw["server"])

    return Protocol(
        name="echo",
        load_config=Cfg,
        roles={"server": Role(
            lambda c: [c.server],
            lambda ctx, a, i: m.EchoServer(a, ctx.transport, ctx.logger))},
        make_client=lambda ctx, a: m.EchoClient(
            a, ctx.transport, ctx.logger, ctx.config.server,
            **ctx.kw(m.EchoClient)),
        drive=lambda client, tag, cb: client.echo(f"hello-{tag}", cb),
        cluster=lambda f, port: {"server": port()},
    )


def _unreplicated() -> Protocol:
    from frankenpaxos_tpu.protocols import unreplicated as m

    class Cfg:
        def __init__(self, raw):
            self.server = _addr(raw["server"])

    return Protocol(
        name="unreplicated",
        load_config=Cfg,
        roles={"server": Role(
            lambda c: [c.server],
            lambda ctx, a, i: m.UnreplicatedServer(
                a, ctx.transport, ctx.logger, ctx.sm(),
                **ctx.kw(m.UnreplicatedServer)))},
        make_client=lambda ctx, a: m.UnreplicatedClient(
            a, ctx.transport, ctx.logger, ctx.config.server,
            **ctx.kw(m.UnreplicatedClient)),
        drive=lambda client, tag, cb: client.propose(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {"server": port()},
    )


def _batchedunreplicated() -> Protocol:
    from frankenpaxos_tpu.protocols import batchedunreplicated as m

    def load(raw):
        cfg = m.BatchedUnreplicatedConfig(
            batcher_addresses=tuple(_addrs(raw["batchers"])),
            server_address=_addr(raw["server"]),
            proxy_server_addresses=tuple(_addrs(raw["proxy_servers"])))
        return cfg

    return Protocol(
        name="batchedunreplicated",
        load_config=load,
        roles={
            "batcher": Role(
                lambda c: list(c.batcher_addresses),
                lambda ctx, a, i: m.BatchedUnreplicatedBatcher(
                    a, ctx.transport, ctx.logger, ctx.config,
                    **ctx.kw(m.BatchedUnreplicatedBatcher))),
            "server": Role(
                lambda c: [c.server_address],
                lambda ctx, a, i: m.BatchedUnreplicatedServer(
                    a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                    seed=ctx.seed)),
            "proxy_server": Role(
                lambda c: list(c.proxy_server_addresses),
                lambda ctx, a, i: m.BatchedUnreplicatedProxyServer(
                    a, ctx.transport, ctx.logger, ctx.config,
                    **ctx.kw(m.BatchedUnreplicatedProxyServer))),
        },
        make_client=lambda ctx, a: m.BatchedUnreplicatedClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.BatchedUnreplicatedClient)),
        drive=lambda client, tag, cb: client.propose(b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "batchers": [port() for _ in range(2)],
            "server": port(),
            "proxy_servers": [port() for _ in range(2)],
        },
    )


def _single_decree(name, mod_name, cfg_name, leader_name, acceptor_name,
                   client_name, payload) -> Protocol:
    """paxos / fastpaxos / caspaxos / matchmakerpaxos share this shape."""
    import importlib

    m = importlib.import_module(f"frankenpaxos_tpu.protocols.{mod_name}")
    cfg_cls = getattr(m, cfg_name)
    leader_cls = getattr(m, leader_name)
    acceptor_cls = getattr(m, acceptor_name)
    client_cls = getattr(m, client_name)
    has_matchmakers = name == "matchmakerpaxos"

    def load(raw):
        kwargs = dict(
            f=raw["f"],
            leader_addresses=tuple(_addrs(raw["leaders"])),
            acceptor_addresses=tuple(_addrs(raw["acceptors"])))
        if has_matchmakers:
            kwargs["matchmaker_addresses"] = tuple(
                _addrs(raw["matchmakers"]))
        return cfg_cls(**kwargs)

    roles = {
        "leader": Role(
            lambda c: list(c.leader_addresses),
            lambda ctx, a, i: leader_cls(
                a, ctx.transport, ctx.logger, ctx.config,
                **ctx.kw(leader_cls)),
            device_options=(("quorum_backend",) if name == "fastpaxos"
                            else ())),
        "acceptor": Role(
            lambda c: list(c.acceptor_addresses),
            lambda ctx, a, i: acceptor_cls(
                a, ctx.transport, ctx.logger, ctx.config)),
    }
    if has_matchmakers:
        roles["matchmaker"] = Role(
            lambda c: list(c.matchmaker_addresses),
            lambda ctx, a, i: m.Matchmaker(
                a, ctx.transport, ctx.logger, ctx.config))

    def cluster(f, port):
        raw = {
            "f": f,
            "leaders": [port() for _ in range(f + 1)],
            "acceptors": [port() for _ in range(2 * f + 1)],
        }
        if has_matchmakers:
            raw["matchmakers"] = [port() for _ in range(2 * f + 1)]
        return raw

    return Protocol(
        name=name,
        load_config=load,
        roles=roles,
        make_client=lambda ctx, a: client_cls(
            a, ctx.transport, ctx.logger, ctx.config,
            **ctx.kw(client_cls)),
        drive=payload,
        cluster=cluster,
    )


def _paxos() -> Protocol:
    return _single_decree(
        "paxos", "paxos", "PaxosConfig", "PaxosLeader", "PaxosAcceptor",
        "PaxosClient",
        lambda client, tag, cb: client.propose(f"v{tag}", cb))


def _fastpaxos() -> Protocol:
    return _single_decree(
        "fastpaxos", "fastpaxos", "FastPaxosConfig", "FastPaxosLeader",
        "FastPaxosAcceptor", "FastPaxosClient",
        lambda client, tag, cb: client.propose(f"v{tag}", cb))


def _caspaxos() -> Protocol:
    return _single_decree(
        "caspaxos", "caspaxos", "CasPaxosConfig", "CasPaxosLeader",
        "CasPaxosAcceptor", "CasPaxosClient",
        lambda client, tag, cb: client.propose({tag}, cb))


def _matchmakerpaxos() -> Protocol:
    return _single_decree(
        "matchmakerpaxos", "matchmakerpaxos", "MatchmakerPaxosConfig",
        "MatchmakerPaxosLeader", "MatchmakerPaxosAcceptor",
        "MatchmakerPaxosClient",
        lambda client, tag, cb: client.propose(f"v{tag}", cb))


def _make_ingest_batcher(ctx: "DeployCtx", address, index: int,
                         protocol: str):
    """Construct a paxingest disseminator (ingest/) for either run-
    pipeline protocol -- WAL-free by design, so no ctx.wal plumbing."""
    from frankenpaxos_tpu import ingest

    router = (ingest.MultiPaxosIngestRouter(ctx.config)
              if protocol == "multipaxos"
              else ingest.MenciusIngestRouter(ctx.config))
    return ingest.IngestBatcher(
        address, ctx.transport, ctx.logger, router, index=index,
        options=ctx.opts(ingest.IngestBatcherOptions), seed=ctx.seed)


def _multipaxos() -> Protocol:
    from frankenpaxos_tpu.protocols import multipaxos as mp

    def load(raw):
        config = mp.MultiPaxosConfig(
            f=raw["f"],
            batcher_addresses=_addrs(raw.get("batchers", [])),
            ingest_batcher_addresses=_addrs(
                raw.get("ingest_batchers", [])),
            read_batcher_addresses=_addrs(raw.get("read_batchers", [])),
            leader_addresses=_addrs(raw["leaders"]),
            leader_election_addresses=_addrs(raw["leader_elections"]),
            proxy_leader_addresses=_addrs(raw["proxy_leaders"]),
            acceptor_addresses=[_addrs(g) for g in raw["acceptors"]],
            replica_addresses=_addrs(raw["replicas"]),
            proxy_replica_addresses=_addrs(raw.get("proxy_replicas", [])),
            flexible=raw.get("flexible", False),
            distribution_scheme=mp.DistributionScheme(
                raw.get("distribution_scheme", "hash")),
        )
        config.check_valid()
        return config

    def flat_acceptors(c):
        return [a for group in c.acceptor_addresses for a in group]

    return Protocol(
        name="multipaxos",
        load_config=load,
        roles={
            "batcher": Role(
                lambda c: list(c.batcher_addresses),
                lambda ctx, a, i: mp.Batcher(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(mp.BatcherOptions),
                    collectors=ctx.collectors)),
            "read_batcher": Role(
                lambda c: list(c.read_batcher_addresses),
                lambda ctx, a, i: mp.ReadBatcher(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(mp.ReadBatchingScheme), seed=ctx.seed)),
            "ingest_batcher": Role(
                lambda c: list(c.ingest_batcher_addresses),
                lambda ctx, a, i: _make_ingest_batcher(
                    ctx, a, i, "multipaxos")),
            "leader": Role(
                lambda c: list(c.leader_addresses),
                lambda ctx, a, i: mp.Leader(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(mp.LeaderOptions), seed=ctx.seed,
                    collectors=ctx.collectors),
                device_options=("phase1_backend",)),
            "proxy_leader": Role(
                lambda c: list(c.proxy_leader_addresses),
                lambda ctx, a, i: mp.ProxyLeader(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(mp.ProxyLeaderOptions), seed=ctx.seed,
                    collectors=ctx.collectors),
                device_options=("quorum_backend", "epoch_backend")),
            "acceptor": Role(
                flat_acceptors,
                lambda ctx, a, i: mp.Acceptor(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(mp.AcceptorOptions),
                    collectors=ctx.collectors,
                    wal=ctx.wal(f"acceptor_{i}"))),
            "replica": Role(
                lambda c: list(c.replica_addresses),
                lambda ctx, a, i: mp.Replica(
                    a, ctx.transport, ctx.logger, ctx.sm(), ctx.config,
                    ctx.opts(mp.ReplicaOptions), seed=ctx.seed,
                    collectors=ctx.collectors,
                    wal=ctx.wal(f"replica_{i}"))),
            "proxy_replica": Role(
                lambda c: list(c.proxy_replica_addresses),
                lambda ctx, a, i: mp.ProxyReplica(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(mp.ProxyReplicaOptions),
                    collectors=ctx.collectors)),
        },
        make_client=lambda ctx, a: mp.Client(
            a, ctx.transport, ctx.logger, ctx.config,
            ctx.opts(mp.ClientOptions), seed=ctx.seed),
        drive=_multipaxos_drive,
        cluster=lambda f, port: {
            "f": f,
            "batchers": [],
            "ingest_batchers": [],
            "read_batchers": [],
            "leaders": [port() for _ in range(f + 1)],
            "leader_elections": [port() for _ in range(f + 1)],
            "proxy_leaders": [port() for _ in range(f + 1)],
            "acceptors": [[port() for _ in range(2 * f + 1)]],
            "replicas": [port() for _ in range(f + 1)],
            "proxy_replicas": [],
        },
    )


def _multipaxos_drive(client, tag, cb):
    from frankenpaxos_tpu.runtime.serializer import PickleSerializer
    from frankenpaxos_tpu.statemachine import SetRequest

    client.write(0, PickleSerializer().to_bytes(
        SetRequest(((f"k{tag}", str(tag)),))), cb)


def _mencius() -> Protocol:
    from frankenpaxos_tpu.protocols import mencius as m

    def load(raw):
        config = m.MenciusConfig(
            f=raw["f"],
            batcher_addresses=_addrs(raw.get("batchers", [])),
            ingest_batcher_addresses=_addrs(
                raw.get("ingest_batchers", [])),
            leader_addresses=[_addrs(g) for g in raw["leaders"]],
            leader_election_addresses=[_addrs(g)
                                       for g in raw["leader_elections"]],
            proxy_leader_addresses=_addrs(raw["proxy_leaders"]),
            acceptor_addresses=[[_addrs(g) for g in grp]
                                for grp in raw["acceptors"]],
            replica_addresses=_addrs(raw["replicas"]),
            proxy_replica_addresses=_addrs(raw.get("proxy_replicas", [])),
            distribution_scheme=m.DistributionScheme(
                raw.get("distribution_scheme", "hash")),
        )
        config.check_valid()
        return config

    def flat_leaders(c):
        return [a for group in c.leader_addresses for a in group]

    def flat_acceptors(c):
        return [a for grp in c.acceptor_addresses for g in grp for a in g]

    return Protocol(
        name="mencius",
        load_config=load,
        roles={
            "batcher": Role(
                lambda c: list(c.batcher_addresses),
                lambda ctx, a, i: m.MenciusBatcher(
                    a, ctx.transport, ctx.logger, ctx.config,
                    seed=ctx.seed, **ctx.kw(m.MenciusBatcher))),
            "ingest_batcher": Role(
                lambda c: list(c.ingest_batcher_addresses),
                lambda ctx, a, i: _make_ingest_batcher(
                    ctx, a, i, "mencius")),
            "leader": Role(
                flat_leaders,
                lambda ctx, a, i: m.MenciusLeader(
                    a, ctx.transport, ctx.logger, ctx.config,
                    seed=ctx.seed, **ctx.kw(m.MenciusLeader))),
            "proxy_leader": Role(
                lambda c: list(c.proxy_leader_addresses),
                lambda ctx, a, i: m.MenciusProxyLeader(
                    a, ctx.transport, ctx.logger, ctx.config,
                    seed=ctx.seed)),
            "acceptor": Role(
                flat_acceptors,
                lambda ctx, a, i: m.MenciusAcceptor(
                    a, ctx.transport, ctx.logger, ctx.config,
                    wal=ctx.wal(f"acceptor_{i}"))),
            "replica": Role(
                lambda c: list(c.replica_addresses),
                lambda ctx, a, i: m.MenciusReplica(
                    a, ctx.transport, ctx.logger, ctx.sm(), ctx.config,
                    seed=ctx.seed, wal=ctx.wal(f"replica_{i}"),
                    **ctx.kw(m.MenciusReplica))),
            "proxy_replica": Role(
                lambda c: list(c.proxy_replica_addresses),
                lambda ctx, a, i: m.MenciusProxyReplica(
                    a, ctx.transport, ctx.logger, ctx.config)),
        },
        make_client=lambda ctx, a: m.MenciusClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.MenciusClient)),
        drive=lambda client, tag, cb: client.write(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "batchers": [],
            "ingest_batchers": [],
            "leaders": [[port() for _ in range(f + 1)]
                        for _ in range(2)],
            "leader_elections": [[port() for _ in range(f + 1)]
                                 for _ in range(2)],
            "proxy_leaders": [port() for _ in range(f + 1)],
            "acceptors": [[[port() for _ in range(2 * f + 1)]]
                          for _ in range(2)],
            "replicas": [port() for _ in range(f + 1)],
            "proxy_replicas": [],
        },
    )


def _vanillamencius() -> Protocol:
    from frankenpaxos_tpu.protocols import vanillamencius as m

    def load(raw):
        return m.VanillaMenciusConfig(
            f=raw["f"],
            server_addresses=tuple(_addrs(raw["servers"])),
            heartbeat_addresses=tuple(_addrs(raw["heartbeats"])))

    return Protocol(
        name="vanillamencius",
        load_config=load,
        roles={"server": Role(
            lambda c: list(c.server_addresses),
            lambda ctx, a, i: m.VanillaMenciusServer(
                a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                seed=ctx.seed, **ctx.kw(m.VanillaMenciusServer)))},
        make_client=lambda ctx, a: m.VanillaMenciusClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.VanillaMenciusClient)),
        drive=lambda client, tag, cb: client.write(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "servers": [port() for _ in range(2 * f + 1)],
            "heartbeats": [port() for _ in range(2 * f + 1)],
        },
    )


def _fastmultipaxos() -> Protocol:
    from frankenpaxos_tpu import roundsystem as rs
    from frankenpaxos_tpu.protocols import fastmultipaxos as m

    def load(raw):
        f = raw["f"]
        name = raw.get("round_system", "round_zero_fast")
        systems = {
            "round_zero_fast": lambda: rs.RoundZeroFast(f + 1),
            "classic_round_robin": lambda: rs.ClassicRoundRobin(f + 1),
            "mixed_round_robin": lambda: rs.MixedRoundRobin(f + 1),
        }
        return m.FastMultiPaxosConfig(
            f=f,
            leader_addresses=tuple(_addrs(raw["leaders"])),
            leader_election_addresses=tuple(
                _addrs(raw["leader_elections"])),
            leader_heartbeat_addresses=tuple(
                _addrs(raw["leader_heartbeats"])),
            acceptor_addresses=tuple(_addrs(raw["acceptors"])),
            acceptor_heartbeat_addresses=tuple(
                _addrs(raw["acceptor_heartbeats"])),
            round_system=systems[name]())

    return Protocol(
        name="fastmultipaxos",
        load_config=load,
        roles={
            "leader": Role(
                lambda c: list(c.leader_addresses),
                lambda ctx, a, i: m.FastMultiPaxosLeader(
                    a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                    options=ctx.opts(m.FastMultiPaxosLeaderOptions),
                    seed=ctx.seed),
                device_options=("quorum_backend",)),
            "acceptor": Role(
                lambda c: list(c.acceptor_addresses),
                lambda ctx, a, i: m.FastMultiPaxosAcceptor(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(m.FastMultiPaxosAcceptorOptions))),
        },
        make_client=lambda ctx, a: m.FastMultiPaxosClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.FastMultiPaxosClient)),
        drive=lambda client, tag, cb: client.propose(b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            # The reference's own committed benchmarks deploy
            # FastMultiPaxos with the classic round-robin round system
            # (benchmarks/fastmultipaxos/smoke.py:17,
            # nsdi_fig1_lt.py:17): concurrent clients proposing
            # directly to acceptors in a fast round vote at offset
            # next_slots and wedge until recovery. Tests exercising the
            # fast path build round_zero_fast configs directly.
            "round_system": "classic_round_robin",
            "leaders": [port() for _ in range(f + 1)],
            "leader_elections": [port() for _ in range(f + 1)],
            "leader_heartbeats": [port() for _ in range(f + 1)],
            "acceptors": [port() for _ in range(2 * f + 1)],
            "acceptor_heartbeats": [port() for _ in range(2 * f + 1)],
        },
    )


def _epaxos() -> Protocol:
    from frankenpaxos_tpu.protocols import epaxos as m

    def load(raw):
        return m.EPaxosConfig(
            f=raw["f"],
            replica_addresses=tuple(_addrs(raw["replicas"])))

    return Protocol(
        name="epaxos",
        load_config=load,
        roles={"replica": Role(
            lambda c: list(c.replica_addresses),
            lambda ctx, a, i: m.EPaxosReplica(
                a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                ctx.opts(m.EPaxosReplicaOptions), seed=ctx.seed),
            device_options=("dep_backend",))},
        make_client=lambda ctx, a: m.EPaxosClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.EPaxosClient)),
        drive=lambda client, tag, cb: client.propose(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "replicas": [port() for _ in range(2 * f + 1)],
        },
    )


def _simplebpaxos(gc: bool = False) -> Protocol:
    if gc:
        from frankenpaxos_tpu.protocols import simplegcbpaxos as m

        leader_cls, proposer_cls = m.GcBPaxosLeader, m.GcBPaxosProposer
        dep_cls, acceptor_cls = m.GcBPaxosDepServiceNode, m.GcBPaxosAcceptor
        replica_cls = m.GcBPaxosReplica
    else:
        from frankenpaxos_tpu.protocols import simplebpaxos as m

        leader_cls, proposer_cls = m.BPaxosLeader, m.BPaxosProposer
        dep_cls, acceptor_cls = m.BPaxosDepServiceNode, m.BPaxosAcceptor
        replica_cls = m.BPaxosReplica
    from frankenpaxos_tpu.protocols.simplebpaxos import BPaxosClient

    def load(raw):
        kwargs = dict(
            f=raw["f"],
            leader_addresses=tuple(_addrs(raw["leaders"])),
            proposer_addresses=tuple(_addrs(raw["proposers"])),
            dep_service_node_addresses=tuple(_addrs(raw["dep_nodes"])),
            acceptor_addresses=tuple(_addrs(raw["acceptors"])),
            replica_addresses=tuple(_addrs(raw["replicas"])))
        if gc:
            from frankenpaxos_tpu.protocols.simplegcbpaxos import (
                GcBPaxosConfig,
            )

            return GcBPaxosConfig(
                garbage_collector_addresses=tuple(
                    _addrs(raw["garbage_collectors"])), **kwargs)
        from frankenpaxos_tpu.protocols.simplebpaxos import (
            SimpleBPaxosConfig,
        )

        return SimpleBPaxosConfig(**kwargs)

    gc_options = ("gc_backend",) if gc else ()
    roles = {
        "leader": Role(
            lambda c: list(c.leader_addresses),
            lambda ctx, a, i: leader_cls(
                a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
                **ctx.kw(leader_cls)),
            device_options=("dep_backend",)),
        "proposer": Role(
            lambda c: list(c.proposer_addresses),
            lambda ctx, a, i: proposer_cls(
                a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
                **ctx.kw(proposer_cls)),
            device_options=gc_options),
        "dep_node": Role(
            lambda c: list(c.dep_service_node_addresses),
            lambda ctx, a, i: dep_cls(
                a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                **ctx.kw(dep_cls)),
            device_options=gc_options),
        "acceptor": Role(
            lambda c: list(c.acceptor_addresses),
            lambda ctx, a, i: acceptor_cls(
                a, ctx.transport, ctx.logger, ctx.config,
                **ctx.kw(acceptor_cls)),
            device_options=gc_options),
        "replica": Role(
            lambda c: list(c.replica_addresses),
            lambda ctx, a, i: replica_cls(
                a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                seed=ctx.seed, **ctx.kw(replica_cls))),
    }
    if gc:
        roles["garbage_collector"] = Role(
            lambda c: list(c.garbage_collector_addresses),
            lambda ctx, a, i: m.GarbageCollector(
                a, ctx.transport, ctx.logger, ctx.config))

    def cluster(f, port):
        raw = {
            "f": f,
            "leaders": [port() for _ in range(f + 1)],
            "proposers": [port() for _ in range(f + 1)],
            "dep_nodes": [port() for _ in range(2 * f + 1)],
            "acceptors": [port() for _ in range(2 * f + 1)],
            "replicas": [port() for _ in range(f + 1)],
        }
        if gc:
            raw["garbage_collectors"] = [port() for _ in range(f + 1)]
        return raw

    return Protocol(
        name="simplegcbpaxos" if gc else "simplebpaxos",
        load_config=load,
        roles=roles,
        make_client=lambda ctx, a: BPaxosClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(BPaxosClient)),
        drive=lambda client, tag, cb: client.propose(0, b"w%d" % tag, cb),
        cluster=cluster,
    )


def _unanimousbpaxos() -> Protocol:
    from frankenpaxos_tpu.protocols import unanimousbpaxos as m

    def load(raw):
        return m.UnanimousBPaxosConfig(
            f=raw["f"],
            leader_addresses=tuple(_addrs(raw["leaders"])),
            dep_service_node_addresses=tuple(_addrs(raw["dep_nodes"])),
            acceptor_addresses=tuple(_addrs(raw["acceptors"])))

    return Protocol(
        name="unanimousbpaxos",
        load_config=load,
        roles={
            "leader": Role(
                lambda c: list(c.leader_addresses),
                lambda ctx, a, i: m.UnanimousBPaxosLeader(
                    a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                    seed=ctx.seed, **ctx.kw(m.UnanimousBPaxosLeader))),
            "dep_node": Role(
                lambda c: list(c.dep_service_node_addresses),
                lambda ctx, a, i: m.UnanimousBPaxosDepServiceNode(
                    a, ctx.transport, ctx.logger, ctx.config, ctx.sm())),
            "acceptor": Role(
                lambda c: list(c.acceptor_addresses),
                lambda ctx, a, i: m.UnanimousBPaxosAcceptor(
                    a, ctx.transport, ctx.logger, ctx.config)),
        },
        make_client=lambda ctx, a: m.UnanimousBPaxosClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.UnanimousBPaxosClient)),
        drive=lambda client, tag, cb: client.propose(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "leaders": [port() for _ in range(f + 1)],
            "dep_nodes": [port() for _ in range(2 * f + 1)],
            "acceptors": [port() for _ in range(2 * f + 1)],
        },
    )


def _matchmakermultipaxos() -> Protocol:
    from frankenpaxos_tpu.protocols import matchmakermultipaxos as m

    def load(raw):
        return m.MatchmakerMultiPaxosConfig(
            f=raw["f"],
            leader_addresses=tuple(_addrs(raw["leaders"])),
            matchmaker_addresses=tuple(_addrs(raw["matchmakers"])),
            reconfigurer_addresses=tuple(_addrs(raw["reconfigurers"])),
            acceptor_addresses=tuple(_addrs(raw["acceptors"])),
            replica_addresses=tuple(_addrs(raw["replicas"])))

    return Protocol(
        name="matchmakermultipaxos",
        load_config=load,
        roles={
            "leader": Role(
                lambda c: list(c.leader_addresses),
                lambda ctx, a, i: m.MMPLeader(
                    a, ctx.transport, ctx.logger, ctx.config,
                    seed=ctx.seed,
                    quorum_backend=ctx.opt("quorum_backend", "dict")),
                device_options=("quorum_backend",)),
            "matchmaker": Role(
                lambda c: list(c.matchmaker_addresses),
                lambda ctx, a, i: m.MMPMatchmaker(
                    a, ctx.transport, ctx.logger, ctx.config)),
            "reconfigurer": Role(
                lambda c: list(c.reconfigurer_addresses),
                lambda ctx, a, i: m.MMPReconfigurer(
                    a, ctx.transport, ctx.logger, ctx.config,
                    seed=ctx.seed, **ctx.kw(m.MMPReconfigurer))),
            "acceptor": Role(
                lambda c: list(c.acceptor_addresses),
                lambda ctx, a, i: m.MMPAcceptor(
                    a, ctx.transport, ctx.logger, ctx.config)),
            "replica": Role(
                lambda c: list(c.replica_addresses),
                lambda ctx, a, i: m.MMPReplica(
                    a, ctx.transport, ctx.logger, ctx.config, ctx.sm())),
        },
        make_client=lambda ctx, a: m.MMPClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.MMPClient)),
        drive=lambda client, tag, cb: client.write(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "leaders": [port() for _ in range(f + 1)],
            "matchmakers": [port() for _ in range(2 * f + 1)],
            "reconfigurers": [port()],
            "acceptors": [port() for _ in range(2 * f + 1)],
            "replicas": [port() for _ in range(f + 1)],
        },
    )


def _horizontal() -> Protocol:
    from frankenpaxos_tpu.protocols import horizontal as m

    def load(raw):
        return m.HorizontalConfig(
            f=raw["f"],
            leader_addresses=tuple(_addrs(raw["leaders"])),
            leader_election_addresses=tuple(
                _addrs(raw["leader_elections"])),
            acceptor_addresses=tuple(_addrs(raw["acceptors"])),
            replica_addresses=tuple(_addrs(raw["replicas"])),
            alpha=raw.get("alpha", 10))

    return Protocol(
        name="horizontal",
        load_config=load,
        roles={
            "leader": Role(
                lambda c: list(c.leader_addresses),
                lambda ctx, a, i: m.HorizontalLeader(
                    a, ctx.transport, ctx.logger, ctx.config,
                    seed=ctx.seed)),
            "acceptor": Role(
                lambda c: list(c.acceptor_addresses),
                lambda ctx, a, i: m.HorizontalAcceptor(
                    a, ctx.transport, ctx.logger, ctx.config)),
            "replica": Role(
                lambda c: list(c.replica_addresses),
                lambda ctx, a, i: m.HorizontalReplica(
                    a, ctx.transport, ctx.logger, ctx.config, ctx.sm())),
        },
        make_client=lambda ctx, a: m.HorizontalClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.HorizontalClient)),
        drive=lambda client, tag, cb: client.write(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "leaders": [port() for _ in range(f + 1)],
            "leader_elections": [port() for _ in range(f + 1)],
            "acceptors": [port() for _ in range(2 * f + 1)],
            "replicas": [port() for _ in range(f + 1)],
            "alpha": 10,
        },
    )


def _fasterpaxos() -> Protocol:
    from frankenpaxos_tpu.protocols import fasterpaxos as m

    def load(raw):
        return m.FasterPaxosConfig(
            f=raw["f"],
            server_addresses=tuple(_addrs(raw["servers"])))

    return Protocol(
        name="fasterpaxos",
        load_config=load,
        roles={"server": Role(
            lambda c: list(c.server_addresses),
            lambda ctx, a, i: m.FasterPaxosServer(
                a, ctx.transport, ctx.logger, ctx.config, ctx.sm(),
                options=ctx.opts(m.FasterPaxosOptions), seed=ctx.seed))},
        make_client=lambda ctx, a: m.FasterPaxosClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.FasterPaxosClient)),
        drive=lambda client, tag, cb: client.write(0, b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "servers": [port() for _ in range(2 * f + 1)],
        },
    )


def _craq() -> Protocol:
    from frankenpaxos_tpu.protocols import craq as m

    def load(raw):
        return m.CraqConfig(
            chain_node_addresses=tuple(_addrs(raw["chain_nodes"])))

    return Protocol(
        name="craq",
        load_config=load,
        roles={"chain_node": Role(
            lambda c: list(c.chain_node_addresses),
            lambda ctx, a, i: m.ChainNode(
                a, ctx.transport, ctx.logger, ctx.config))},
        make_client=lambda ctx, a: m.CraqClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.CraqClient)),
        drive=lambda client, tag, cb: client.write(
            0, f"k{tag}", f"v{tag}", lambda *a: cb(*(a or (None,)))),
        cluster=lambda f, port: {
            "chain_nodes": [port() for _ in range(3)],
        },
    )


def _scalog() -> Protocol:
    from frankenpaxos_tpu.protocols import scalog as m

    def load(raw):
        return m.ScalogConfig(
            f=raw["f"],
            server_addresses=tuple(tuple(_addrs(shard))
                                   for shard in raw["servers"]),
            aggregator_address=_addr(raw["aggregator"]),
            leader_addresses=tuple(_addrs(raw["leaders"])),
            acceptor_addresses=tuple(_addrs(raw["acceptors"])),
            replica_addresses=tuple(_addrs(raw["replicas"])),
            proxy_replica_addresses=tuple(
                _addrs(raw.get("proxy_replicas", []))))

    def flat_servers(c):
        return [a for shard in c.server_addresses for a in shard]

    return Protocol(
        name="scalog",
        load_config=load,
        roles={
            "server": Role(
                flat_servers,
                lambda ctx, a, i: m.ScalogServer(
                    a, ctx.transport, ctx.logger, ctx.config,
                    **ctx.kw(m.ScalogServer))),
            "aggregator": Role(
                lambda c: [c.aggregator_address],
                lambda ctx, a, i: m.ScalogAggregator(
                    a, ctx.transport, ctx.logger, ctx.config,
                    **ctx.kw(m.ScalogAggregator))),
            "leader": Role(
                lambda c: list(c.leader_addresses),
                lambda ctx, a, i: m.ScalogLeader(
                    a, ctx.transport, ctx.logger, ctx.config)),
            "acceptor": Role(
                lambda c: list(c.acceptor_addresses),
                lambda ctx, a, i: m.ScalogAcceptor(
                    a, ctx.transport, ctx.logger, ctx.config)),
            "replica": Role(
                lambda c: list(c.replica_addresses),
                lambda ctx, a, i: m.ScalogReplica(
                    a, ctx.transport, ctx.logger, ctx.config, ctx.sm())),
            "proxy_replica": Role(
                lambda c: list(c.proxy_replica_addresses),
                lambda ctx, a, i: m.ScalogProxyReplica(
                    a, ctx.transport, ctx.logger, ctx.config,
                    **ctx.kw(m.ScalogProxyReplica))),
        },
        make_client=lambda ctx, a: m.ScalogClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            **ctx.kw(m.ScalogClient)),
        drive=lambda client, tag, cb: client.propose(b"w%d" % tag, cb),
        cluster=lambda f, port: {
            "f": f,
            "servers": [[port() for _ in range(f + 1)]
                        for _ in range(2)],
            "aggregator": port(),
            "leaders": [port() for _ in range(f + 1)],
            "acceptors": [port() for _ in range(2 * f + 1)],
            "replicas": [port() for _ in range(f + 1)],
            "proxy_replicas": [port() for _ in range(f + 1)],
        },
    )


def _wpaxos() -> Protocol:
    from frankenpaxos_tpu.protocols import wpaxos as m

    def load(raw):
        config = m.WPaxosConfig(
            zones=tuple(raw["zones"]),
            leader_addresses=_addrs(raw["leaders"]),
            acceptor_addresses=tuple(
                tuple(_addrs(row)) for row in raw["acceptors"]),
            replica_addresses=_addrs(raw["replicas"]),
            num_groups=raw.get("num_groups", 4))
        config.check_valid()
        return config

    return Protocol(
        name="wpaxos",
        load_config=load,
        roles={
            "leader": Role(
                lambda c: list(c.leader_addresses),
                lambda ctx, a, i: m.WPaxosLeader(
                    a, ctx.transport, ctx.logger, ctx.config,
                    ctx.opts(m.WPaxosLeaderOptions)),
                device_options=("quorum_backend",)),
            "acceptor": Role(
                lambda c: [a for row in c.acceptor_addresses
                           for a in row],
                lambda ctx, a, i: m.WPaxosAcceptor(
                    a, ctx.transport, ctx.logger, ctx.config,
                    wal=ctx.wal(f"acceptor_{i}"))),
            "replica": Role(
                lambda c: list(c.replica_addresses),
                lambda ctx, a, i: m.WPaxosReplica(
                    a, ctx.transport, ctx.logger, ctx.config,
                    **ctx.kw(m.WPaxosReplica))),
        },
        make_client=lambda ctx, a: m.WPaxosClient(
            a, ctx.transport, ctx.logger, ctx.config, seed=ctx.seed,
            options=ctx.opts(m.WPaxosClientOptions)),
        # Pseudonyms rotate so closed-loop drivers can keep several
        # commands in flight; keys spread the load across groups.
        drive=lambda client, tag, cb: client.write(
            tag % 16, b"w%d" % tag, cb, key=b"obj-%d" % (tag % 8)),
        cluster=lambda f, port: {
            "zones": [f"zone-{z}" for z in range(3)],
            "leaders": [port() for _ in range(3)],
            "acceptors": [[port() for _ in range(2 * f + 1)]
                          for _ in range(3)],
            "replicas": [port() for _ in range(3)],
            "num_groups": 4,
        },
    )


REGISTRY: "dict[str, Callable[[], Protocol]]" = {
    "echo": _echo,
    "unreplicated": _unreplicated,
    "batchedunreplicated": _batchedunreplicated,
    "paxos": _paxos,
    "fastpaxos": _fastpaxos,
    "caspaxos": _caspaxos,
    "multipaxos": _multipaxos,
    "mencius": _mencius,
    "vanillamencius": _vanillamencius,
    "fastmultipaxos": _fastmultipaxos,
    "epaxos": _epaxos,
    "simplebpaxos": lambda: _simplebpaxos(gc=False),
    "simplegcbpaxos": lambda: _simplebpaxos(gc=True),
    "unanimousbpaxos": _unanimousbpaxos,
    "matchmakerpaxos": _matchmakerpaxos,
    "matchmakermultipaxos": _matchmakermultipaxos,
    "horizontal": _horizontal,
    "fasterpaxos": _fasterpaxos,
    "craq": _craq,
    "scalog": _scalog,
    "wpaxos": _wpaxos,
}

PROTOCOL_NAMES = sorted(REGISTRY)


def get_protocol(name: str) -> Protocol:
    try:
        factory = REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown protocol {name!r}; known: {PROTOCOL_NAMES}") from None
    return factory()

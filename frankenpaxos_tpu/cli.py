"""Deployment CLI: one process per role over TcpTransport, any protocol.

The analog of the reference's 105 ``<Role>Main`` objects
(jvm/src/main/scala/frankenpaxos/<proto>/<Role>Main.scala): parse flags
(``--protocol``, ``--role``, ``--index``, ``--config``, ``--log_level``,
``--prometheus_port``, ``--state_machine``; LeaderMain.scala:19-103),
read a cluster config file (the prototext analog is JSON here;
ConfigUtil.scala:7-43), construct the role actor over TcpTransport via
the deployment registry (frankenpaxos_tpu/deploy.py), and optionally
expose Prometheus metrics (PrometheusUtil.scala:6-15).

Per-role tunables use ``--options.<name> <value>`` (or ``=``-joined),
matching the reference's scopt ``--options.*`` flags
(LeaderMain.scala:52-80); they apply to both constructor keyword
parameters and options-dataclass fields, coerced by declared type.

One chip serves one process (device.py). This process takes the chip
only if a role it hosts builds a device backend (``Role.device_options``
in deploy.py); otherwise it pins JAX to the CPU. ``--index 0,1``
colocates several instances of one role, which is how a deployment's
device-backed instances share the one chip-owning process.

Usage::

    python -m frankenpaxos_tpu.cli --protocol multipaxos --role acceptor \
        --index 2 --config cluster.json --options.flush_every_n 10
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from frankenpaxos_tpu.deploy import (
    DeployCtx,
    get_protocol,
    process_label,
    PROTOCOL_NAMES,
)
from frankenpaxos_tpu.runtime import LogLevel, PrintLogger
from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport


def parse_option_overrides(extra: list) -> dict:
    """``--options.name value`` / ``--options.name=value`` pairs."""
    overrides: dict = {}
    i = 0
    while i < len(extra):
        arg = extra[i]
        if not arg.startswith("--options."):
            raise SystemExit(f"unrecognized argument: {arg}")
        key = arg[len("--options."):]
        if "=" in key:
            key, _, value = key.partition("=")
        else:
            i += 1
            if i >= len(extra) or extra[i].startswith("--options."):
                raise SystemExit(f"missing value for {arg}")
            value = extra[i]
        overrides[key] = value
        i += 1
    return overrides


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(prog="frankenpaxos_tpu")
    parser.add_argument("--protocol", required=True,
                        choices=PROTOCOL_NAMES)
    parser.add_argument("--role", required=True)
    parser.add_argument("--index", default="0",
                        help="role index, or a comma-separated list to "
                             "colocate several instances of the role in "
                             "this process")
    parser.add_argument("--config", required=True,
                        help="cluster config JSON")
    parser.add_argument("--log_level", default="info",
                        choices=["debug", "info", "warn", "error", "fatal"])
    parser.add_argument("--state_machine", default="AppendLog")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--prometheus_port", type=int, default=0,
                        help="0 disables the metrics endpoint")
    parser.add_argument("--wal_dir", default=None,
                        help="durability root (wal/): WAL-capable roles "
                             "write a per-role write-ahead log under "
                             "<wal_dir>/<role>_<index> and recover from "
                             "it on startup, so a SIGKILL'd role "
                             "relaunched with the same wal_dir rejoins "
                             "with its state intact")
    parser.add_argument("--fault_fsync", default=None,
                        metavar="P:PERIOD:WINDOW|C:EVERY:STALL_S:SEED",
                        help="paxchaos storage-fault arm (faults/): "
                             "wrap this role's WAL storage in a "
                             "BLOCKING FsyncStallStorage -- "
                             "P:<period_s>:<window_s> sleeps through "
                             "the first <window_s> of every "
                             "<period_s> on the host wall clock "
                             "(aligned across role processes); "
                             "C:<every>:<stall_s>:<seed> stalls after "
                             "every EVERY-th group commit. The "
                             "deployed twin of the scenario matrix's "
                             "fsync-stall schedule")
    parser.add_argument("--fault_link", default=None,
                        metavar="zone:H:P=Z;drop:ZA-ZB;lat:ZA-ZB=S",
                        help="paxchaos link-fault arm (faults/): inject "
                             "partitions/latency at THIS role's "
                             "TcpTransport send path, mirroring "
                             "--fault_fsync's launch-time arming -- the "
                             "deployed twin of the scenario matrix's "
                             "partition rows (before this flag only the "
                             "in-process client transport armed "
                             "LinkFaults; role->role links ran clean). "
                             "Clauses: zone:HOST:PORT=NAME endpoint "
                             "mapping, drop:ZA-ZB partition (both "
                             "ways), lat:ZA-ZB=SECONDS extra latency")
    parser.add_argument("--ready_addr", default=None,
                        help="host:port the launcher listens on for the "
                             "wait-for-listen handshake: once this role "
                             "is fully constructed and listening, it "
                             "connects there and reports its label")
    parser.add_argument("--trace", default=None, metavar="DIR",
                        help="paxtrace root (obs/): emit receive/timer/"
                             "drain spans with drain-stage sub-spans to "
                             "DIR/<role>_<index>.trace.jsonl, keep the "
                             "crash flight recorder ring in "
                             "DIR/<role>_<index>.flight (mmap'd: "
                             "survives kill -9), and propagate trace "
                             "contexts on outbound frames")
    parser.add_argument("--trace_sample", type=float, default=1.0,
                        help="trace sampling rate at trace roots "
                             "(1.0 = every command, 0.01 = 1 in 100); "
                             "propagated contexts keep the root's "
                             "decision")
    # Back-compat shorthands (now spelled --options.*):
    parser.add_argument("--quorum_backend", default=None,
                        choices=[None, "dict", "tpu"])
    parser.add_argument("--batch_size", type=int, default=None)
    args, extra = parser.parse_known_args(argv)

    overrides = parse_option_overrides(extra)
    if args.quorum_backend is not None:
        overrides.setdefault("quorum_backend", args.quorum_backend)
    if args.batch_size is not None:
        overrides.setdefault("batch_size", str(args.batch_size))

    logger = PrintLogger(LogLevel[args.log_level.upper()])
    protocol = get_protocol(args.protocol)

    with open(args.config) as f:
        config = protocol.load_config(json.load(f))

    # Every (role name, role, index) this process hosts: one, several
    # instances of one role, or with --role supernode all of them.
    if args.role == "supernode":
        hosted = [(name, role, index)
                  for name, role in protocol.roles.items()
                  for index in range(len(role.addresses(config)))]
    else:
        try:
            role = protocol.roles[args.role]
        except KeyError:
            raise SystemExit(
                f"unknown role {args.role!r} for {args.protocol}; "
                f"known: {sorted(protocol.roles)} or 'supernode'")
        try:
            indices = [int(x) for x in args.index.split(",")]
        except ValueError:
            raise SystemExit(f"--index {args.index!r} is not an integer "
                             f"or a comma-separated list of them")
        count = len(role.addresses(config))
        for index in indices:
            if not 0 <= index < count:
                raise SystemExit(
                    f"--index {index} out of range for {args.protocol} "
                    f"{args.role}: valid range 0..{count - 1}")
        hosted = [(args.role, role, index) for index in indices]

    owns_chip = any(role.hosts_device(overrides) for _, role, _ in hosted)
    if owns_chip:
        from frankenpaxos_tpu import device, native

        try:
            found = device.claim_tpu()
            native.require()
        except RuntimeError as e:
            raise SystemExit(f"{args.role} {args.index} needs the TPU and "
                             f"the native codec: {e}")
        logger.info(f"device: {json.dumps(found)}")
        if found["platform"] != "tpu":
            logger.warn("JAX_PLATFORMS=cpu is set: the tpu backends of "
                        "this process run on CPU XLA, not on a chip")
    else:
        from frankenpaxos_tpu import device

        device.pin_cpu()

    collectors = None
    if args.prometheus_port > 0:
        from frankenpaxos_tpu.runtime.monitoring import PrometheusCollectors

        collectors = PrometheusCollectors()

    listen_address = None
    if len(hosted) == 1:
        _, role, index = hosted[0]
        listen_address = role.addresses(config)[index]

    transport = TcpTransport(listen_address, logger)
    if args.fault_link:
        from frankenpaxos_tpu.faults.deployed_backend import (
            parse_link_fault_spec,
        )

        transport.link_faults = parse_link_fault_spec(
            args.fault_link).check
    label = process_label(args.role, args.index)
    if collectors is not None:
        from frankenpaxos_tpu.obs import RuntimeMetrics

        # The chip owner's stages are also annotations on the device
        # trace's clock while one runs (obs/trace.py); no other process
        # imports JAX's profiler for it.
        transport.runtime_metrics = RuntimeMetrics(
            collectors, label, device_clock=owns_chip)
        transport.runtime_metrics.watch_gc()
    if args.trace:
        import atexit
        import os

        from frankenpaxos_tpu.obs import FlightRecorder, Tracer

        os.makedirs(args.trace, exist_ok=True)
        tracer = Tracer(
            role=label, sample_rate=args.trace_sample,
            flight=FlightRecorder(
                os.path.join(args.trace, f"{label}.flight")),
            runtime_metrics=transport.runtime_metrics,
            sink_path=os.path.join(args.trace,
                                   f"{label}.trace.jsonl"),
            # Incarnation salt: a crash-relaunched role appends to the
            # same trace.jsonl and must not reuse the dead life's ids.
            instance=os.getpid())
        transport.tracer = tracer
        # SIGTERM exits via sys.exit (below), so a clean kill flushes
        # the span sink; a SIGKILL leaves the mmap'd flight ring.
        atexit.register(tracer.flush)
    transport.start()
    ctx = DeployCtx(config=config, transport=transport, logger=logger,
                    overrides=overrides, seed=args.seed,
                    state_machine=args.state_machine,
                    collectors=collectors, wal_dir=args.wal_dir,
                    wal_fault=args.fault_fsync)

    def make_instrumented(role, role_name, role_address, index):
        """Construct the role actor and, when metrics are on, wrap its
        receive with the uniform per-role request metrics."""
        actor = role.make(ctx, role_address, index)
        if collectors is not None and actor is not None:
            from frankenpaxos_tpu.runtime.monitoring import (
                instrument_actor,
            )

            instrument_actor(actor, collectors, args.protocol, role_name)

    if listen_address is None:
        # Colocated roles share one event loop (all of them is the
        # reference's SuperNode main, jvm/.../multipaxos/
        # SuperNode.scala:22+). Bind every address FIRST so
        # construction-time sends (a leader's Phase1a) always find
        # their targets listening.
        for _, role, index in hosted:
            transport.listen_on(role.addresses(config)[index])
    # A distinct seed per colocated actor, matching the per-process
    # --seed diversity of one role per process: identical seeds would
    # sync the elections' randomized timeouts.
    for count, (role_name, role, index) in enumerate(hosted):
        ctx.seed = args.seed + count
        make_instrumented(role, role_name,
                          role.addresses(config)[index], index)
    address = (listen_address if listen_address is not None
               else f"{len(hosted)} colocated roles")
    if not owns_chip:
        asked = sorted(k for k in ctx.consumed
                       if overrides.get(k) == "tpu")
        if asked:
            # deploy.py's Role.device_options missed an option: this
            # process was pinned to the CPU and would serve a "tpu"
            # backend from it.
            raise SystemExit(
                f"{args.role} built a tpu backend from {asked} in a "
                f"process pinned to the CPU; declare the option in "
                f"deploy.py's Role.device_options")
    unmatched = ctx.unmatched_overrides()
    if unmatched:
        # Overrides are shared across a deployment's roles, so an option
        # aimed at another role lands here too -- note, don't fail.
        logger.info(f"options not used by this role: {unmatched}")

    if args.prometheus_port > 0:
        import prometheus_client

        prometheus_client.start_http_server(args.prometheus_port)

    logger.info(f"{args.protocol} {args.role} {args.index} "
                f"listening on {address}")
    if args.ready_addr:
        # Explicit readiness handshake (deploy_suite.launch_roles): by
        # this point every listener is bound, every actor constructed,
        # and the metrics endpoint (if any) serving -- so connecting
        # back and reporting our label is a true end-to-end "ready",
        # unlike grepping logs (which races log flushing and says
        # nothing about whether the process can actually be reached).
        import socket

        ready_host, _, ready_port = args.ready_addr.rpartition(":")
        try:
            with socket.create_connection(
                    (ready_host, int(ready_port)), timeout=10) as sock:
                sock.sendall(f"{label}\n".encode())
        except OSError as e:
            # The launcher may have timed out and gone away; the role
            # itself is healthy, so keep serving.
            logger.warn(f"ready handshake to {args.ready_addr} "
                        f"failed: {e}")
    # Exit cleanly on SIGTERM so wrappers that dump state at interpreter
    # exit (cProfile's -m runner, the perf_util.py:37 analog) get to
    # write their output when the harness kills the role.
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(0))
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        transport.stop()


if __name__ == "__main__":
    main()

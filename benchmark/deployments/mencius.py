"""Bringing one compartmentalized Mencius deployment up: the cluster file,
the roles, the probe write, and the settling after the window.

The same three entry points as ``deployments/multipaxos.py``, whose shape
this follows: ``launch_with_retry(bench, config, record_dir, trace_s) ->
cluster file``, ``settle(bench)`` and ``GRACE_S``. Every role count is the
configuration's: ``leader_groups`` groups of ``leaders_per_group``
leaders, ``acceptor_groups`` groups of ``acceptors_per_group`` acceptors
spread evenly over the leader groups, ``proxy_leaders`` and ``replicas``.
The proxy leaders own the chip (``quorum_backend`` ``tpu``), so
``launch_roles`` puts both in one process, ``proxy_leader_0_1``.

A program whose Mencius proxy leader cannot own the chip, or whose client
cannot read through the log, cannot run this deployment: the launch says
so at once and starts nothing.
"""

from __future__ import annotations

import os
import shutil
import sys
import time

from harness.manifest import ROOT

#: The generators wait this long for a late answer, and then as long
#: again for the read-back; roles get as long to catch up and to dump.
GRACE_S = 60.0


def log(message: str) -> None:
    print(f"[bench] {message}", file=sys.stderr, flush=True)


def cluster_of(config: dict) -> dict:
    """A localhost placement for the configuration's role counts (the
    shape of ``deploy.py``'s Mencius cluster)."""
    from frankenpaxos_tpu.bench.harness import free_port

    def addresses(count: int) -> list:
        return [["127.0.0.1", free_port()] for _ in range(count)]

    groups = config["leader_groups"]
    per_group = config["acceptor_groups"] // groups
    return {
        "f": config["f"],
        "batchers": addresses(config["batchers"]),
        "ingest_batchers": [],
        "leaders": [addresses(config["leaders_per_group"])
                    for _ in range(groups)],
        "leader_elections": [addresses(config["leaders_per_group"])
                             for _ in range(groups)],
        "proxy_leaders": addresses(config["proxy_leaders"]),
        "acceptors": [[addresses(config["acceptors_per_group"])
                       for _ in range(per_group)] for _ in range(groups)],
        "replicas": addresses(config["replicas"]),
        "proxy_replicas": addresses(config["proxy_replicas"]),
    }


def refuse_a_program_that_cannot_run_it(protocol) -> None:
    """Leave at once, before any process starts, where the program's
    Mencius has no device path for its proxy leaders or no read."""
    from frankenpaxos_tpu.protocols.mencius import MenciusClient

    owns = protocol.roles["proxy_leader"].device_options
    if "quorum_backend" not in owns or not hasattr(MenciusClient, "read"):
        raise SystemExit("this program's Mencius cannot run the "
                         "deployment: its proxy leader declares no "
                         "quorum_backend device option, or its client has "
                         "no read through the log")


def launch(bench, config: dict, record_dir: str, trace_s: float) -> str:
    """Start the roles and commit one write through them. Returns the
    cluster file's path. Raises RuntimeError, with the roles stopped, if
    the deployment does not come up."""
    import threading

    from frankenpaxos_tpu.bench.deploy_suite import launch_roles
    from frankenpaxos_tpu.bench.harness import free_port
    from frankenpaxos_tpu.deploy import DeployCtx, get_protocol
    from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
    from frankenpaxos_tpu.runtime.serializer import PickleSerializer
    from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport
    from frankenpaxos_tpu.statemachine import SetRequest

    protocol = get_protocol(config["protocol"])
    refuse_a_program_that_cannot_run_it(protocol)
    cluster = cluster_of(config)
    cluster_path = bench.write_json("cluster.json", cluster)
    loaded = protocol.load_config(cluster)
    launch_roles(bench, config["protocol"], cluster_path, loaded,
                 state_machine=config["state_machine"],
                 overrides=config["options"], prometheus=True,
                 ready_timeout_s=300.0,
                 entry=(os.path.join(ROOT, config["role_entry"]),
                        record_dir, str(trace_s)))
    # A gentle resend: rapid duplicates during the tracker's first drains
    # would each be proposed to a fresh slot.
    logger = FakeLogger(LogLevel.FATAL)
    transport = TcpTransport(("127.0.0.1", free_port()), logger)
    transport.start()
    ctx = DeployCtx(config=loaded, transport=transport, logger=logger,
                    overrides={"resend_period_s": "2.0"}, seed=0xBEEF)
    probe = protocol.make_client(ctx, transport.listen_address)
    committed = threading.Event()
    transport.loop.call_soon_threadsafe(
        probe.write, 0,
        PickleSerializer().to_bytes(SetRequest((("probe", "0"),))),
        lambda _: committed.set())
    ok = committed.wait(timeout=60)
    transport.stop()
    if not ok:
        bench.cleanup()
        raise RuntimeError("the deployment never committed the probe write")
    return cluster_path


def launch_with_retry(bench, config: dict, record_dir: str,
                      trace_s: float) -> str:
    """One retry on fresh ports: a lost start-up race is the
    deployment's, not a result."""
    try:
        return launch(bench, config, record_dir, trace_s)
    except RuntimeError as e:
        log(f"start-up failed ({e}); once more on fresh ports")
        for name in os.listdir(bench.path):
            if name.endswith(".log"):
                os.replace(bench.abspath(name),
                           bench.abspath(name + ".attempt1"))
        shutil.rmtree(record_dir)
        os.makedirs(record_dir)
        return launch(bench, config, record_dir, trace_s)


def settle(bench) -> None:
    """Every replica is sent every chosen run and noop range; give a
    slower one time to hold what the faster has (late is late, not
    wrong)."""
    from frankenpaxos_tpu.bench.metrics import scrape

    name = "mencius_replica_chosen_slots_total"
    ports = [port for label, port in bench.prometheus_ports.items()
             if label.startswith("replica_")]

    def chosen(port: int) -> float:
        return sum(value for series, value in scrape(port).items()
                   if series.split("{", 1)[0] == name)

    deadline = time.monotonic() + GRACE_S
    while time.monotonic() < deadline:
        if len({chosen(port) for port in ports}) <= 1:
            return
        time.sleep(0.2)

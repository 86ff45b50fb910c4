"""Bringing one MultiPaxos deployment up: the cluster file, the roles,
the probe write, and the settling after the window.

A configuration names this file as its ``deployment``; another protocol
brings a file of its own beside it, with the same three entry points:
``launch_with_retry(bench, config, record_dir, trace_s) -> cluster file``,
``settle(bench)`` and ``GRACE_S``.

Copies of ``frankenpaxos_tpu/bench/multipaxos_suite.py``'s ``placement``,
``_launch_and_warm`` and ``launch_with_retry``, driven by a configuration
file instead of a ``MultiPaxosInput``, and starting every role through the
configuration's role entry. Every role count is the configuration's:
none is fixed here. A later change to the program's copies does not move
the yardstick.
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
    shape of ``multipaxos_suite.placement``)."""
    from frankenpaxos_tpu.bench.harness import free_port

    def addresses(count: int) -> list:
        return [["127.0.0.1", free_port()] for _ in range(count)]

    return {
        "f": config["f"],
        "flexible": config["flexible"],
        "batchers": addresses(config["batchers"]),
        "read_batchers": addresses(config["read_batchers"]),
        "leaders": addresses(config["leaders"]),
        "leader_elections": addresses(config["leaders"]),
        "proxy_leaders": addresses(config["proxy_leaders"]),
        "acceptors": [addresses(config["acceptors_per_group"])
                      for _ in range(config["acceptor_groups"])],
        "replicas": addresses(config["replicas"]),
        "proxy_replicas": addresses(config["proxy_replicas"]),
    }


def launch(bench, config: dict, record_dir: str, trace_s: float) -> tuple:
    """Start the roles and commit one write through them (a copy of
    ``multipaxos_suite._launch_and_warm``). Returns the cluster file's
    path. Raises RuntimeError, with the roles stopped, if the deployment
    does not come up."""
    import threading

    from frankenpaxos_tpu.bench.deploy_suite import launch_roles
    from frankenpaxos_tpu.bench.harness import free_port
    from frankenpaxos_tpu.deploy import DeployCtx, get_protocol
    from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
    from frankenpaxos_tpu.runtime.serializer import PickleSerializer
    from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport
    from frankenpaxos_tpu.statemachine import SetRequest

    protocol = get_protocol(config["protocol"])
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
                    overrides={"resend_client_request_period_s": "2.0"},
                    seed=0xBEEF)
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
    """One retry on fresh ports, as ``multipaxos_suite.launch_with_retry``
    makes: a lost start-up race is the deployment's, not a result."""
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
    """Every replica is sent every chosen run; give a slower one time to
    execute what the faster has (late is late, not wrong)."""
    from frankenpaxos_tpu.bench.metrics import scrape

    name = "multipaxos_replica_executed_commands_total"
    ports = [port for label, port in bench.prometheus_ports.items()
             if label.startswith("replica_")]
    deadline = time.monotonic() + GRACE_S
    while time.monotonic() < deadline:
        executed = [scrape(port).get(name) for port in ports]
        if len(set(executed)) <= 1:
            return
        time.sleep(0.2)



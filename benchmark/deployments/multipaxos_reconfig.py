#!/usr/bin/env python3
"""Bringing up one MultiPaxos deployment whose acceptor set is replaced
under load, and replacing it.

``multipaxos.py``'s deployment (its cluster file and its catch-up wait by
import, ``multipaxos_durable.py``'s probe and waits likewise; the retry is
that file's, around this one's launch) with a pool of acceptor processes
where that has a group, and a reconfigurer:

  the pool         ``acceptor_pool`` acceptor processes, all alive from
                   launch. The first ``members`` of them are the group of
                   the cluster file every role is started with (epoch 0).
                   The others are started beside them from a second
                   cluster file, ``cluster_pool.json``, that lists them as
                   a second group: an acceptor finds itself in it, votes
                   for whatever it is sent, and takes part in nothing
                   until an epoch names it. Membership lives in the
                   program's epoch store, which the reconfigurations
                   change; no role reads the second file's grouping.
  the reconfigurer a process of its own (this file, run as a script),
                   started beside the pool and told to go once the probe
                   write has committed. One ``period_s`` after that
                   instant, and then every ``period_s`` on the monotonic
                   clock until ``settle`` stops it (the launch returns,
                   and the generators start, once a second write has
                   committed through the first of them), it draws
                   ``members`` of the pool uniformly (``random.Random``
                   of the run's seed; a draw equal to the set it sent
                   last is drawn again) and sends the program's
                   ``Reconfigure(members)`` over TCP to every leader, of
                   which the active one acts. What it sent, and when, it
                   leaves in ``<records>/reconfigurer.json``.

The run's seed is not among what the launcher hands a deployment, so it
is read from the launcher's own command line (``--seed``).

Before anything is launched it checks that the program gives its epoch
tracker the window the configuration states
(``require_the_epoch_board_is_whole``): a program that cuts that board
shorter cannot run this configuration, and the launcher says so and
exits at once.

Names of the program this file holds on to: ``reconfig.Reconfigure``,
``proxy_leader.ProxyLeader`` / ``ProxyLeaderOptions`` (``tpu_window``,
``epoch_quorums``) and the module's ``EpochQuorumTracker``,
``runtime.SimTransport``, the chip owner's gauge
``multipaxos_proxy_leader_epoch_planes``; and what ``multipaxos.py``
holds on to.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import sys
import time

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

from deployments import multipaxos  # noqa: E402
from harness.manifest import ROOT  # noqa: E402

GRACE_S = multipaxos.GRACE_S
#: The pool's processes are started beside the roles; this long after
#: the roles are ready they have to be listening too.
POOL_READY_S = 60.0
#: After the first reconfiguration the chip owner may compile for this
#: long (a machine with no compile cache) before it counts votes again.
EPOCHS_READY_S = 180.0
RECONFIGURER = "reconfigurer"
#: The chip owner's gauge of the epochs its trackers know.
PLANES = "multipaxos_proxy_leader_epoch_planes"

log = multipaxos.log


def first_cluster(config: dict) -> dict:
    """The cluster every role is started with: ``multipaxos.py``'s, its one
    acceptor group the first ``members`` of the pool (epoch 0)."""
    return multipaxos.cluster_of(dict(
        config, acceptors_per_group=config["members"]))


def seed_of(argv: list) -> int:
    """``--seed`` of the launcher's command line (0 where it has none)."""
    parser = argparse.ArgumentParser(add_help=False)
    parser.add_argument("--seed", type=int, default=0)
    return parser.parse_known_args(argv)[0].seed


def require_the_epoch_board_is_whole(config: dict) -> None:
    """What this deployment needs of the program before anything is
    launched: a proxy leader that engages epoch counting builds its epoch
    tracker with the window its options give. Asked of a proxy leader
    built on a simulated transport with the host tally (no device, a few
    milliseconds), whose epoch tracker's class is replaced by one that
    only notes what it was built with."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from frankenpaxos_tpu.deploy import get_protocol
    from frankenpaxos_tpu.protocols.multipaxos import proxy_leader
    from frankenpaxos_tpu.runtime import FakeLogger, LogLevel, SimTransport

    wanted = int(config["options"]["tpu_window"])
    built: list = []

    class Noted:
        def __init__(self, store, backend="dict", window=None):
            built.append(window)
            self.planes = 1

    loaded = get_protocol(config["protocol"]).load_config(
        first_cluster(config))
    logger = FakeLogger(LogLevel.FATAL)
    real = proxy_leader.EpochQuorumTracker
    proxy_leader.EpochQuorumTracker = Noted
    try:
        proxy_leader.ProxyLeader(
            loaded.proxy_leader_addresses[0], SimTransport(logger), logger,
            loaded, proxy_leader.ProxyLeaderOptions(
                tpu_window=wanted, epoch_quorums=True))
    finally:
        proxy_leader.EpochQuorumTracker = real
    if built != [wanted]:
        raise SystemExit(
            f"this program builds its epoch tracker over {built} slots "
            f"where the configuration states a window of {wanted}: it "
            f"cannot hold this configuration's board, so the "
            f"configuration cannot run on it")


def start_pool(bench, config: dict, pool_path: str, record_dir: str,
               trace_s: float) -> dict:
    """Start the pool's acceptors that are not of the first group (the
    command ``launch_roles`` gives an acceptor, on the second cluster
    file). Returns ``{label: (command, environment, /metrics port)}``."""
    from frankenpaxos_tpu.bench.deploy_suite import role_process_env
    from frankenpaxos_tpu.bench.harness import free_port, LocalHost
    from frankenpaxos_tpu.deploy import process_label

    started = {}
    for index in range(config["members"], config["acceptor_pool"]):
        label = process_label("acceptor", str(index))
        port = free_port()
        cmd = [sys.executable,
               os.path.join(ROOT, config["role_entry"]), record_dir,
               str(trace_s),
               "--protocol", config["protocol"], "--role", "acceptor",
               "--index", str(index), "--config", pool_path,
               "--state_machine", config["state_machine"],
               "--seed", str(index), "--prometheus_port", str(port)]
        for key, value in config["options"].items():
            cmd.append(f"--options.{key}={value}")
        env = role_process_env()
        bench.popen(LocalHost(), label, cmd, env=env)
        started[label] = (cmd, env, port)
    return started


def launch(bench, config: dict, record_dir: str, trace_s: float,
           seed: int) -> str:
    """Start the pool and the roles, commit one write through them, start
    the reconfigurer. Returns the cluster file's path. Raises
    RuntimeError, with everything stopped, if the deployment does not
    come up."""
    from frankenpaxos_tpu.bench.deploy_suite import (
        launch_roles,
        role_process_env,
    )
    from frankenpaxos_tpu.bench.harness import free_port, LocalHost
    from frankenpaxos_tpu.bench.metrics import scrape
    from frankenpaxos_tpu.deploy import get_protocol

    from deployments import multipaxos_durable

    protocol = get_protocol(config["protocol"])
    cluster = first_cluster(config)
    spare = [["127.0.0.1", free_port()]
             for _ in range(config["members"], config["acceptor_pool"])]
    cluster_path = bench.write_json("cluster.json", cluster)
    pool_path = bench.write_json(
        "cluster_pool.json",
        dict(cluster, acceptors=cluster["acceptors"] + [spare]))
    loaded = protocol.load_config(cluster)
    pool = start_pool(bench, config, pool_path, record_dir, trace_s)
    # The reconfigurer starts with them, so that it is up when the probe
    # has committed, and waits for the word to go.
    period_s = float(config["reconfigure"]["period_s"])
    go = bench.abspath(RECONFIGURER + ".go")
    bench.reconfigurer = LocalHost().popen(
        [sys.executable, os.path.abspath(__file__),
         "--cluster", pool_path, "--members", str(config["members"]),
         "--seed", str(seed), "--period_s", repr(period_s), "--go", go,
         "--out", os.path.join(record_dir, RECONFIGURER + ".json")],
        bench.abspath(RECONFIGURER + ".log"), env=role_process_env())
    bench.procs.append(bench.reconfigurer)
    launch_roles(bench, config["protocol"], cluster_path, loaded,
                 state_machine=config["state_machine"],
                 overrides=config["options"], prometheus=True,
                 ready_timeout_s=300.0,
                 entry=(os.path.join(ROOT, config["role_entry"]),
                        record_dir, str(trace_s)))
    for label, (cmd, env, port) in pool.items():
        bench.role_commands[label] = (cmd, env)
        bench.prometheus_ports[label] = port
    if not multipaxos_durable.wait_for(
            lambda: all(multipaxos_durable.is_listening(bench, label)
                        for label in pool),
            time.monotonic() + POOL_READY_S):
        bench.cleanup()
        raise RuntimeError("the acceptor pool did not come up")
    probe = multipaxos_durable.Probe(protocol, loaded, seed=0xBEEF)
    try:
        if not probe.write("0", 60.0):
            raise RuntimeError("the deployment never committed the probe "
                               "write")
        # The first reconfiguration is due one period after this instant.
        with open(go + ".tmp", "w") as f:
            f.write(repr(time.monotonic() + period_s))
        os.replace(go + ".tmp", go)
        # The chip owner builds its epoch tracker, and compiles what that
        # runs, when the first reconfiguration reaches it. That is set-up,
        # as every other program's compilation is: the generators start
        # once it counts votes, shown by a second write committed through
        # it.
        owner = bench.prometheus_ports[bench.chip_owner]
        if not multipaxos_durable.wait_for(
                lambda: scrape(owner).get(PLANES, 0.0) >= 2,
                time.monotonic() + period_s + EPOCHS_READY_S, 0.1):
            raise RuntimeError("the chip owner never took up the first "
                               "reconfiguration")
        if not probe.write("1", 60.0):
            raise RuntimeError("no write committed through the first "
                               "reconfiguration")
    except RuntimeError:
        bench.cleanup()
        raise
    finally:
        probe.stop()
    return cluster_path


def launch_with_retry(bench, config: dict, record_dir: str,
                      trace_s: float) -> str:
    """One retry on fresh ports, as ``multipaxos.launch_with_retry``
    makes."""
    require_the_epoch_board_is_whole(config)
    seed = seed_of(sys.argv[1:])
    try:
        return launch(bench, config, record_dir, trace_s, seed)
    except RuntimeError as e:
        log(f"start-up failed ({e}); once more on fresh ports")
        for name in os.listdir(bench.path):
            if name.endswith(".log"):
                os.replace(bench.abspath(name),
                           bench.abspath(name + ".attempt1"))
        shutil.rmtree(record_dir)
        os.makedirs(record_dir)
        return launch(bench, config, record_dir, trace_s, seed)


def settle(bench) -> None:
    """Stop the reconfigurer (it writes what it sent), then give a slower
    replica time to execute what the faster has."""
    proc = bench.reconfigurer
    if proc.running():
        os.kill(proc.pid(), signal.SIGTERM)
        try:
            proc.wait(timeout=10.0)
        except Exception as e:
            log(f"the reconfigurer did not stop: {e!r}")
    multipaxos.settle(bench)


# --- the reconfigurer ---------------------------------------------------------

def draw(rng: random.Random, pool: int, members: int, current: tuple) -> tuple:
    """``members`` of ``range(pool)``, uniform over the subsets, never the
    set ``current``."""
    while True:
        drawn = tuple(sorted(rng.sample(range(pool), members)))
        if drawn != current:
            return drawn


def reconfigurer_main(argv: list) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cluster", required=True,
                        help="the pool's cluster file: every acceptor "
                             "group together is the pool")
    parser.add_argument("--members", type=int, required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--period_s", type=float, required=True)
    parser.add_argument("--go", required=True,
                        help="a file the launcher writes once the probe "
                             "has committed: the monotonic instant of the "
                             "first reconfiguration")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from frankenpaxos_tpu import device

    device.pin_cpu()
    from frankenpaxos_tpu.bench.harness import free_port
    from frankenpaxos_tpu.reconfig import Reconfigure
    from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
    from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
    from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

    with open(args.cluster) as f:
        cluster = json.load(f)
    pool = [tuple(address) for group in cluster["acceptors"]
            for address in group]
    leaders = [tuple(address) for address in cluster["leaders"]]
    rng = random.Random(args.seed)
    current = tuple(range(args.members))
    sent: list = []
    stopped = []
    signal.signal(signal.SIGTERM, lambda *_: stopped.append(True))
    transport = TcpTransport(("127.0.0.1", free_port()),
                             FakeLogger(LogLevel.FATAL))
    transport.start()
    try:
        while not stopped and not os.path.exists(args.go):
            time.sleep(0.01)
        if not stopped:
            with open(args.go) as f:
                at = float(f.read())
        while not stopped:
            delay = at - time.monotonic()
            if delay > 0:
                time.sleep(min(delay, 0.05))
                continue
            current = draw(rng, len(pool), args.members, current)
            data = DEFAULT_SERIALIZER.to_bytes(Reconfigure(
                members=tuple(pool[index] for index in current)))
            for leader in leaders:
                transport.send(transport.listen_address, leader, data)
            sent.append({"mono_s": time.monotonic(), "due_mono_s": at,
                         "pool_indices": list(current)})
            at += args.period_s
    finally:
        transport.stop()
        with open(args.out + ".tmp", "w") as f:
            json.dump({"seed": args.seed, "period_s": args.period_s,
                       "pool": [list(address) for address in pool],
                       "sent": sent}, f)
        os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(reconfigurer_main(sys.argv[1:]))

"""Bringing one durable MultiPaxos deployment up, and at the end of the
run killing its whole storage tier and bringing it back from its logs.

``multipaxos.py`` (of which the cluster file, the launch, the probe
write, the retry and the catch-up wait are copies) with every role given
``--wal_dir <run>/wal``, and a ``settle`` that, after the catch-up wait:

  (a) signals the acceptors and replicas to dump what they hold
      (``role_entry_durable``'s ``<label>.life1.*``) and waits for it,
      and the chip owner to mark where its trackers' records stand;
  (b) SIGKILLs all of them, one signal after another;
  (c) discards what was not fsynced: every segment is cut to the length
      its role last recorded as synced (``cut_unsynced``), and what was
      cut goes into ``<label>.cut.json``;
  (d) starts each again from its recorded command, on the same log;
  (e) commits one write and one linearizable read of it through the
      recovered cluster with a fresh client;
  (f) waits for the replicas to have executed the same again;
  (g) leaves ``recovery.json`` in the record directory: the monotonic
      instants of the kill (each role's), of each role ready, of the
      probe written and read, what was cut, and what failed.

Before anything is launched it checks that the program's transport
reaches a peer's next life (``require_a_next_life_is_reached``): a program
whose transport does not cannot run this configuration, and the launcher
says so and exits at once. All the rest is after the window. Every wait has a deadline inside ``GRACE_S``
of the moment the recovery starts, and a step that fails is a number in
``recovery.json`` (``recovery_probe_failed``, ``not_recovered``), never an
exception: the run still prints its result line. A storage role that
left no record of its own gets one from what its first life dumped, so
the launcher finds a record for every role.

The kill, the relaunch and the wait for "listening" are copies of
``frankenpaxos_tpu/bench/chaos.py``'s ``sigkill_role``, ``relaunch_role``
and ``wait_relaunched_ready``, with every deadline on
``time.monotonic()`` (the clock rule of ``benchmark/README.md``).
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import time

from harness import role_entry_durable
from harness.manifest import ROOT

#: The generators wait this long for a late answer, and then as long
#: again for the read-back; roles get as long to catch up and to dump,
#: and the kill and the recovery as long again, all told.
GRACE_S = 60.0
#: Of the recovery's ``GRACE_S``: the dump, and the reaping of the killed.
DUMP_S = 20.0
REAP_S = 5.0
#: Left for the replicas to agree again once the probe is through.
AGREE_S = 5.0

PROBE_KEY = "probe"
EXECUTED = "multipaxos_replica_executed_commands_total"


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


class Probe:
    """A fresh client of the deployment: one write of ``PROBE_KEY``, and
    one linearizable read of it."""

    def __init__(self, protocol, loaded, seed: int):
        from frankenpaxos_tpu.bench.harness import free_port
        from frankenpaxos_tpu.deploy import DeployCtx
        from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
        from frankenpaxos_tpu.runtime.serializer import PickleSerializer
        from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

        # A gentle resend: rapid duplicates during the tracker's first
        # drains would each be proposed to a fresh slot.
        logger = FakeLogger(LogLevel.FATAL)
        self.transport = TcpTransport(("127.0.0.1", free_port()), logger)
        self.transport.start()
        ctx = DeployCtx(config=loaded, transport=self.transport,
                        logger=logger,
                        overrides={"resend_client_request_period_s": "2.0"},
                        seed=seed)
        self.client = protocol.make_client(ctx,
                                           self.transport.listen_address)
        self.serializer = PickleSerializer()

    def write(self, value: str, timeout_s: float) -> bool:
        import threading

        from frankenpaxos_tpu.statemachine import SetRequest

        committed = threading.Event()
        self.transport.loop.call_soon_threadsafe(
            self.client.write, 0,
            self.serializer.to_bytes(SetRequest(((PROBE_KEY, value),))),
            lambda _: committed.set())
        return committed.wait(timeout=max(0.0, timeout_s))

    def read(self, timeout_s: float):
        """The value a linearizable read of the probe's key returns, or
        None if none came in time."""
        import threading

        from frankenpaxos_tpu.statemachine import GetRequest

        answered = threading.Event()
        got: list = []

        def on_read(raw) -> None:
            got.append(raw)
            answered.set()

        self.transport.loop.call_soon_threadsafe(
            self.client.read, 0,
            self.serializer.to_bytes(GetRequest((PROBE_KEY,))), on_read)
        if not answered.wait(timeout=max(0.0, timeout_s)) \
                or not isinstance(got[0], bytes):
            return None
        return dict(self.serializer.from_bytes(got[0]).key_values).get(
            PROBE_KEY)

    def stop(self) -> None:
        self.transport.stop()


def require_a_next_life_is_reached() -> None:
    """What this deployment needs of the program's transport before
    anything is launched: a message sent to an address whose process
    went away and came back arrives. A transport that finds a dead peer
    only by the write it loses cannot serve a storage tier that restarts
    all at once (no survivor covers the lost ``Phase2a``; the recovery
    probe never commits). Such a program cannot run this configuration:
    the launcher exits at once, with no result, instead of two minutes
    later with a run that says ``recovery_probe_failed``."""
    from frankenpaxos_tpu.bench.harness import free_port
    from frankenpaxos_tpu.runtime import Actor, FakeLogger, LogLevel
    from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

    logger = FakeLogger(LogLevel.FATAL)
    here = ("127.0.0.1", free_port())
    there = ("127.0.0.1", free_port())
    got: list = []

    class Listener(Actor):
        def receive(self, src, message) -> None:
            got.append(message)

    ours = TcpTransport(here, logger)
    ours.start()
    sender = Listener(here, ours, logger)

    def reached(word: str) -> bool:
        theirs = TcpTransport(there, logger)
        theirs.start()
        Listener(there, theirs, logger)
        ours.loop.call_soon_threadsafe(sender.send, there, word)
        ok = wait_for(lambda: word in got, time.monotonic() + 2.0, 0.01)
        theirs.stop()
        return ok

    try:
        first = reached("to the first life")
        time.sleep(0.2)  # the close reaches this side
        second = first and reached("to the next life")
    finally:
        ours.stop()
    if not second:
        raise SystemExit(
            "this program's transport loses the first message to a peer "
            "that restarted (it finds a dead connection only by a failed "
            "write): a storage tier killed and recovered all at once "
            "cannot be served, so this configuration cannot run on it")


def launch(bench, config: dict, record_dir: str, trace_s: float) -> str:
    """Start the roles, each with its log under ``<run>/wal``, and commit
    one write through them (a copy of
    ``multipaxos_suite._launch_and_warm``). Returns the cluster file's
    path. Raises RuntimeError, with the roles stopped, if the deployment
    does not come up."""
    from frankenpaxos_tpu.bench.deploy_suite import launch_roles
    from frankenpaxos_tpu.deploy import get_protocol

    protocol = get_protocol(config["protocol"])
    cluster = cluster_of(config)
    cluster_path = bench.write_json("cluster.json", cluster)
    loaded = protocol.load_config(cluster)
    wal_dir = bench.abspath("wal")
    launch_roles(bench, config["protocol"], cluster_path, loaded,
                 state_machine=config["state_machine"],
                 overrides=config["options"], prometheus=True,
                 ready_timeout_s=300.0, wal_dir=wal_dir,
                 entry=(os.path.join(ROOT, config["role_entry"]),
                        record_dir, str(trace_s)))
    # What ``settle`` needs, and is not given.
    bench.durable = {"record_dir": record_dir, "wal_dir": wal_dir,
                     "protocol": protocol, "loaded": loaded}
    probe = Probe(protocol, loaded, seed=0xBEEF)
    ok = probe.write("0", 60.0)
    probe.stop()
    if not ok:
        bench.cleanup()
        raise RuntimeError("the deployment never committed the probe write")
    return cluster_path


def launch_with_retry(bench, config: dict, record_dir: str,
                      trace_s: float) -> str:
    """One retry on fresh ports and fresh logs, as
    ``multipaxos_suite.launch_with_retry`` makes: a lost start-up race is
    the deployment's, not a result."""
    require_a_next_life_is_reached()
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
        shutil.rmtree(bench.abspath("wal"), ignore_errors=True)
        return launch(bench, config, record_dir, trace_s)


def catch_up(bench) -> None:
    """Every replica is sent every chosen run; give a slower one time to
    execute what the faster has (late is late, not wrong)."""
    from frankenpaxos_tpu.bench.metrics import scrape

    ports = [port for label, port in bench.prometheus_ports.items()
             if label.startswith("replica_")]
    deadline = time.monotonic() + GRACE_S
    while time.monotonic() < deadline:
        executed = [scrape(port).get(EXECUTED) for port in ports]
        if len(set(executed)) <= 1:
            return
        time.sleep(0.2)


def storage_labels(bench) -> list:
    return sorted(label for label in bench.role_commands
                  if label.split("_")[0] in role_entry_durable.STORAGE_KINDS)


def wait_for(done, deadline: float, every_s: float = 0.05) -> bool:
    """Poll ``done()`` until it holds or the monotonic ``deadline``."""
    while True:
        if done():
            return True
        if time.monotonic() >= deadline:
            return False
        time.sleep(every_s)


def kill_all(bench, labels: list) -> dict:
    """SIGKILL every ``label``, none first by more than it takes to send
    the signals, then reap each. Returns each one's monotonic instant."""
    killed = {}
    for label in labels:
        proc = bench.labeled_procs[label]
        if proc.running():
            os.kill(proc.pid(), signal.SIGKILL)
        killed[label] = time.monotonic()
    for label in labels:
        proc = bench.labeled_procs[label]
        try:
            proc.wait(timeout=REAP_S)
        except Exception as e:  # a process that outlives SIGKILL
            log(f"{label} was not reaped: {e!r}")
        proc.kill()  # reaped already: closes its log
    return killed


def relaunch(bench, label: str) -> None:
    """Start ``label`` again with its recorded command (same ports, same
    ``--wal_dir``); its first life's log moves aside."""
    from frankenpaxos_tpu.bench.harness import LocalHost

    cmd, env = bench.role_commands[label]
    role_log = bench.abspath(f"{label}.log")
    if os.path.exists(role_log):
        os.replace(role_log, role_log + ".life1")
    bench.popen(LocalHost(), label, cmd, env=env)


def is_listening(bench, label: str) -> bool:
    """The launch-time handshake's listener is gone; a relaunched role is
    ready when its fresh log says so."""
    try:
        with open(bench.abspath(f"{label}.log"), errors="replace") as f:
            return "listening" in f.read()
    except OSError:
        return False


def executed_by_replica(bench) -> "dict | None":
    from frankenpaxos_tpu.bench.metrics import scrape

    try:
        return {label: scrape(port).get(EXECUTED, 0.0)
                for label, port in bench.prometheus_ports.items()
                if label.startswith("replica_")}
    except Exception:
        return None


def recover(bench, recovery: dict) -> None:
    """Steps (a) to (f); ``recovery`` is filled as far as they get."""
    run = bench.durable
    record_dir, wal_dir = run["record_dir"], run["wal_dir"]
    labels = storage_labels(bench)
    deadline = time.monotonic() + GRACE_S
    recovery["started_mono_s"] = time.monotonic()

    # (a) the first life's records; the chip owner marks where its
    # trackers' records stand.
    for label in labels + [bench.chip_owner]:
        proc = bench.labeled_procs.get(label)
        if proc is not None and proc.running():
            os.kill(proc.pid(), role_entry_durable.DUMP_SIGNAL)

    def dumped() -> bool:
        return all(os.path.exists(os.path.join(record_dir,
                                               f"{label}.life1.json"))
                   for label in labels)

    if not wait_for(dumped, min(deadline, time.monotonic() + DUMP_S)):
        recovery["not_dumped"] = [
            label for label in labels if not os.path.exists(
                os.path.join(record_dir, f"{label}.life1.json"))]
        log(f"no first-life record from {recovery['not_dumped']}")
    recovery["dumped_mono_s"] = time.monotonic()

    # (b) the whole storage tier, at once.
    recovery["killed_mono_s"] = kill_all(bench, labels)
    recovery["kill_mono_s"] = min(recovery["killed_mono_s"].values())
    log(f"killed {len(labels)} storage processes within "
        f"{max(recovery['killed_mono_s'].values()) - recovery['kill_mono_s']:.4f}s")

    # (c) what no fsync covered is gone.
    for label in labels:
        synced = role_entry_durable.read_synced(os.path.join(wal_dir, label))
        cut = role_entry_durable.cut_unsynced(os.path.join(wal_dir, label))
        recovery["unsynced_bytes_discarded"][label] = cut
        recovery["synced"][label] = list(synced)
        with open(os.path.join(record_dir, f"{label}.cut.json"), "w") as f:
            json.dump(cut, f)

    # (d) each again, from its log.
    for label in labels:
        relaunch(bench, label)
    recovery["relaunched_mono_s"] = time.monotonic()
    pending = set(labels)

    def all_ready() -> bool:
        for label in sorted(pending):
            if is_listening(bench, label):
                recovery["ready_mono_s"][label] = time.monotonic()
                pending.discard(label)
            elif not bench.labeled_procs[label].running():
                return True  # it will not come
        return not pending

    wait_for(all_ready, deadline - AGREE_S)
    recovery["not_recovered"] = sorted(pending)
    if pending:
        log(f"never came back: {sorted(pending)}")
    executed_at_ready = executed_by_replica(bench)

    # (e) one write and one linearizable read through what came back.
    probe = Probe(run["protocol"], run["loaded"], seed=0xFEED)
    try:
        value = f"recovered-{os.getpid()}"
        if probe.write(value, deadline - AGREE_S - time.monotonic()):
            recovery["probe_committed_mono_s"] = time.monotonic()
            read = probe.read(deadline - AGREE_S - time.monotonic())
            if read == value:
                recovery["probe_read_mono_s"] = time.monotonic()
                recovery["recovery_probe_failed"] = 0
            else:
                log(f"the probe read back {read!r}, not {value!r}")
        else:
            log("the recovered deployment never committed the probe write")
    finally:
        probe.stop()

    # (f) both replicas have executed as much again since they came back.
    def agree() -> bool:
        now = executed_by_replica(bench)
        if now is None or executed_at_ready is None:
            return False
        grown = {now[label] - executed_at_ready.get(label, 0.0)
                 for label in now}
        return len(grown) == 1 and min(grown) >= 1

    recovery["replicas_agree"] = int(wait_for(agree, deadline, 0.1))


def provisional_records(bench, recovery: dict) -> None:
    """A record for every storage role that has none of its own yet (its
    second life writes one at exit, over this): what its first life
    dumped, or an empty one, with what this file knows."""
    record_dir = bench.durable["record_dir"]
    for label in storage_labels(bench):
        prefix = os.path.join(record_dir, label)
        record = {"label": label, "claimed": False, "cache": {},
                  "gc_pause_s": [0.0, 0.0, 0.0], "gc_collections": [],
                  "trackers": [], "stores": [], "lives": 0,
                  "kind": label.split("_")[0]}
        if os.path.exists(prefix + ".life1.json"):
            with open(prefix + ".life1.json") as f:
                record = json.load(f)
        record["unsynced_bytes_discarded"] = recovery[
            "unsynced_bytes_discarded"].get(label, 0)
        record["recovery"] = recovery
        with open(prefix + ".json.tmp", "w") as f:
            json.dump(record, f)
        os.replace(prefix + ".json.tmp", prefix + ".json")
        if os.path.exists(prefix + ".life1.replica.npz"):
            shutil.copyfile(prefix + ".life1.replica.npz",
                            prefix + ".replica.npz.tmp")
            os.replace(prefix + ".replica.npz.tmp", prefix + ".replica.npz")


def settle(bench) -> None:
    catch_up(bench)
    recovery = {"recovery_probe_failed": 1, "not_recovered": None,
                "killed_mono_s": {}, "ready_mono_s": {},
                "unsynced_bytes_discarded": {}, "synced": {},
                "probe_committed_mono_s": None, "probe_read_mono_s": None}
    try:
        recover(bench, recovery)
    except Exception as e:  # a failed step is a number, not a lost run
        log(f"the recovery stopped at {e!r}")
        recovery["error"] = repr(e)
    recovery["ended_mono_s"] = time.monotonic()
    if recovery["not_recovered"] is None:
        recovery["not_recovered"] = storage_labels(bench)
    if recovery["probe_committed_mono_s"] and recovery.get("kill_mono_s"):
        # From the kill to a write committed by what came back.
        recovery["recover_s"] = (recovery["probe_committed_mono_s"]
                                 - recovery["kill_mono_s"])
    try:
        path = os.path.join(bench.durable["record_dir"], "recovery.json")
        with open(path + ".tmp", "w") as f:
            json.dump(recovery, f)
        os.replace(path + ".tmp", path)
        provisional_records(bench, recovery)
    except Exception as e:
        log(f"the recovery's record was not written: {e!r}")
    log(f"recovery: {json.dumps(recovery)}")

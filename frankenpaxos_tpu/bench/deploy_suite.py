"""Generic deployment smoke: any protocol, every role its own process.

The analog of scripts/benchmark_smoke.sh (which runs
``benchmarks.<proto>.smoke`` for 18 protocols over SSH-to-localhost,
benchmark_smoke.sh:5-18): compute a localhost placement from the
deployment registry, launch every role via the CLI over real TCP, drive
a few commands from an in-process client, and assert replies arrive.
"""

from __future__ import annotations

import os
import socket
import sys
import threading
import time

from frankenpaxos_tpu import native
from frankenpaxos_tpu.bench.harness import (
    BenchmarkDirectory,
    free_port,
    LocalHost,
)
from frankenpaxos_tpu.deploy import DeployCtx, get_protocol, process_label
from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport


def role_process_env() -> dict:
    """Environment for every process that must stay off the chip
    (host-backend roles, load generators): JAX pinned to the CPU."""
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONUNBUFFERED"] = "1"
    return env


def chip_process_env() -> dict:
    """Environment for THE process that owns the chip: the launcher's
    own, so ``JAX_PLATFORMS`` is whatever the user left it (unset on a
    TPU host; an explicit ``cpu`` under the tests)."""
    env = os.environ.copy()
    env["PYTHONUNBUFFERED"] = "1"
    return env


#: How a role process starts, after the interpreter (and cProfile,
#: when profiled). ``chip_smoke.py`` substitutes a wrapper around the
#: same CLI that records what the device tracker was fed.
CLI_ENTRY = ("-m", "frankenpaxos_tpu.cli")


def launch_plan(protocol, config, overrides: dict,
                supernode: bool) -> list:
    """``[(role_name, index_arg, owns_chip)]``, one entry per process.

    One chip serves one process (device.py), so the processes are laid
    out around that: every instance of the role that builds a device
    backend under ``overrides`` shares ONE process (``--index 0,1``),
    which alone is left unpinned; every other role instance gets its own
    CPU-pinned process. A launch that needs two chip-owning processes is
    refused here, with a ValueError, rather than left to libtpu to hang
    or to JAX to fall back."""
    device_roles = [name for name, role in protocol.roles.items()
                    if role.hosts_device(overrides)
                    and role.addresses(config)]
    if supernode:
        return [("supernode", "0", bool(device_roles))]
    if len(device_roles) > 1:
        raise ValueError(
            f"roles {device_roles} would each initialise the TPU, and "
            f"one chip serves one process: give only one of them a tpu "
            f"backend, or launch with supernode=True so that one "
            f"process hosts them all")
    plan = []
    for name, role in protocol.roles.items():
        count = len(role.addresses(config))
        if name in device_roles:
            plan.append((name, ",".join(map(str, range(count))), True))
        else:
            plan += [(name, str(index), False) for index in range(count)]
    return plan


def launch_roles(bench: BenchmarkDirectory, protocol_name: str,
                 config_path: str, config, *, state_machine: str,
                 overrides: "dict[str, str] | None" = None,
                 prometheus: bool = False, supernode: bool = False,
                 profiled: bool = False,
                 ready_timeout_s: float = 120.0,
                 wal_dir: "str | None" = None,
                 trace_dir: "str | None" = None,
                 trace_sample: float = 1.0,
                 extra_role_args: "dict | None" = None,
                 entry=CLI_ENTRY, host=None) -> list:
    """Start every role of ``protocol_name`` as a subprocess and wait
    until each reports it is listening.

    With ``prometheus=True`` each role gets a ``/metrics`` endpoint on a
    fresh port; the ``{label: port}`` map lands in
    ``bench.prometheus_ports`` and a generated scrape config in
    ``prometheus.json`` (benchmarks/prometheus.py:10-60 semantics).

    With ``supernode=True`` all roles run colocated in ONE process (the
    coupled baseline, SuperNode.scala:22+). Otherwise the processes are
    laid out by :func:`launch_plan`: at most one of them owns the chip,
    the rest are pinned to the CPU, and ``bench.role_commands`` records
    which is which.

    With ``profiled=True`` every role runs under cProfile (the
    benchmarks/perf_util.py:37 perf-wrap analog for Python roles); the
    role's SIGTERM handler exits cleanly so ``{label}.prof`` dumps at
    kill time -- render it with ``write_profile_reports``.

    ``host`` (default a LocalHost) is the machine the roles launch on:
    pass a ``bench.remote.RemoteHost`` to deploy through its shell
    (ssh, or the loopback stand-in) -- the reference's SSH deployment
    seam (benchmarks/host.py:36-50). Config/log paths pass through
    unchanged on shared filesystems; a RemoteHost with
    ``staging_dir``/``local_root`` set ships them for disjoint
    filesystems (see bench/remote.py).

    ``wal_dir`` turns on per-role durability (``--wal_dir``, wal/):
    WAL-capable roles log to <wal_dir>/<label> and recover on
    relaunch -- the seam the chaos driver (bench/chaos.py) uses to
    SIGKILL and resurrect roles mid-benchmark.

    ``extra_role_args`` maps a role label to extra CLI args appended
    to THAT role's command only (paxchaos: per-acceptor
    ``--fault_fsync`` arming from ``faults.fsync_fault_args``); the
    args are recorded in the launch spec, so a chaos relaunch keeps
    the role's fault arming.

    ``trace_dir`` turns on paxtrace (``--trace``, obs/): every role
    emits spans to <trace_dir>/<label>.trace.jsonl and keeps its
    crash flight-recorder ring in <trace_dir>/<label>.flight --
    ``bench/chaos.py`` snapshots the ring of a SIGKILL'd role for the
    post-mortem. ``trace_sample`` is the root sampling rate.

    ``entry`` replaces the interpreter arguments that start a role
    (:data:`CLI_ENTRY`) with a wrapper that takes the same CLI flags.

    Every launched command is recorded in ``bench.role_commands`` so a
    role can be relaunched verbatim (same ports, same wal_dir) after a
    kill.
    """
    protocol = get_protocol(protocol_name)
    host = host or LocalHost()
    overrides = overrides or {}
    plan = launch_plan(protocol, config, overrides, supernode)
    # Build the codec once, before a dozen role processes race to.
    native.load()
    # Explicit wait-for-listen handshake (local deployments): the
    # launcher listens on an ephemeral port; each role connects back
    # and reports its label AFTER binding its listeners, constructing
    # its actors, and starting its metrics endpoint. This replaces the
    # old sleep-and-grep of role logs for "listening", which raced log
    # flushing under load (the deployment startup race behind the
    # flaky read/write-benchmark test). Remote hosts keep the log-grep
    # path through host.grep_ready: their roles can't necessarily dial
    # back to a listener on this machine's loopback.
    handshake = type(host) is LocalHost
    ready_server = None
    ready_args: list = []
    if handshake:
        ready_server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        ready_server.bind(("127.0.0.1", 0))
        ready_server.listen(128)
        ready_args = ["--ready_addr",
                      f"127.0.0.1:{ready_server.getsockname()[1]}"]
    labels = []
    prometheus_ports: dict[str, int] = {}
    bench.role_commands = {}
    bench.chip_owner = None
    for role_name, index, owns_chip in plan:
        label = process_label(role_name, index)
        labels.append(label)
        if owns_chip:
            bench.chip_owner = label
        cmd = [sys.executable]
        if profiled:
            cmd += ["-m", "cProfile", "-o", bench.abspath(f"{label}.prof")]
        cmd += [*entry,
                "--protocol", protocol_name, "--role", role_name,
                "--index", index, "--config", config_path,
                "--state_machine", state_machine,
                "--seed", index.partition(",")[0]] + ready_args
        if prometheus:
            prometheus_ports[label] = free_port()
            cmd += ["--prometheus_port",
                    str(prometheus_ports[label])]
        if wal_dir:
            cmd += ["--wal_dir", wal_dir]
        if trace_dir:
            cmd += ["--trace", trace_dir,
                    "--trace_sample", str(trace_sample)]
        for key, value in overrides.items():
            cmd.append(f"--options.{key}={value}")
        cmd += (extra_role_args or {}).get(label, [])
        env = chip_process_env() if owns_chip else role_process_env()
        bench.role_commands[label] = (cmd, env)
        bench.popen(host, label, cmd, env=env)
    bench.prometheus_ports = prometheus_ports
    bench.trace_dir = trace_dir
    if prometheus:
        from frankenpaxos_tpu.bench.metrics import scrape_config

        bench.write_json("prometheus.json",
                         scrape_config(prometheus_ports))

    try:
        pending = _wait_ready(bench, host, labels, ready_server,
                              ready_timeout_s)
        if pending and type(host) is LocalHost:
            # THE unified readiness retry (every deployment entry point
            # -- smoke, benchmarks, LT suites, sweeps -- comes through
            # here): a role that lost the startup scheduling lottery on
            # a loaded host gets killed and relaunched VERBATIM (same
            # ports, same wal_dir) once, with a fresh full deadline.
            # Callers that want fresh ports on top of this (a stolen
            # free_port) keep their own whole-placement retry.
            for label in sorted(pending):
                print(f"role {label} not ready after "
                      f"{ready_timeout_s:.0f}s; relaunching it")
                bench.labeled_procs[label].kill()
                log = bench.abspath(f"{label}.log")
                if os.path.exists(log):
                    os.replace(log, f"{log}.attempt1")
                cmd, cmd_env = bench.role_commands[label]
                bench.popen(host, label, cmd, env=cmd_env)
            pending = _wait_ready(bench, host, sorted(pending),
                                  ready_server, ready_timeout_s)
    finally:
        if ready_server is not None:
            ready_server.close()
    if pending:
        bench.cleanup()
        raise RuntimeError(
            f"{protocol_name} roles never became ready: {sorted(pending)}")
    return labels


def _wait_ready(bench: BenchmarkDirectory, host, labels: list,
                ready_server, ready_timeout_s: float) -> set:
    """Wait for every role to become ready; returns the labels that
    never did. With ``ready_server`` set, readiness is the role's own
    connect-back handshake (and a role process that EXITS before
    reporting fails immediately instead of burning the full timeout);
    otherwise fall back to polling role logs for "listening"."""
    deadline = time.time() + ready_timeout_s
    pending = set(labels)
    if ready_server is not None:
        ready_server.settimeout(0.25)
        while pending and time.time() < deadline:
            dead = [label for label in sorted(pending)
                    if not bench.labeled_procs[label].running()]
            if dead:
                bench.cleanup()
                raise RuntimeError(
                    f"role process(es) exited before becoming ready: "
                    f"{dead}; see {bench.path}/<label>.log")
            try:
                conn, _ = ready_server.accept()
            except socket.timeout:
                continue
            try:
                conn.settimeout(5)
                with conn, conn.makefile() as f:
                    pending.discard(f.readline().strip())
            except OSError:
                # A half-open/reset connection reads as "not ready yet";
                # the deadline still bounds the wait.
                pass
        return pending
    while pending and time.time() < deadline:
        # Through the host (one round-trip for ALL pending labels) so
        # remote logs -- possibly on a disjoint filesystem, see
        # bench/remote.py RemoteHost -- are readable.
        ready = host.grep_ready(
            [bench.abspath(f"{label}.log") for label in pending],
            "listening")
        pending -= {label for label in pending
                    if bench.abspath(f"{label}.log") in ready}
        time.sleep(0.1)
    return pending


def run_protocol_smoke(bench: BenchmarkDirectory, protocol_name: str, *,
                       f: int = 1, num_commands: int = 3,
                       state_machine: str = "AppendLog",
                       overrides: "dict[str, str] | None" = None,
                       command_timeout_s: float = 30.0,
                       host=None, prometheus: bool = False,
                       trace_dir: "str | None" = None) -> dict:
    """Deploy ``protocol_name`` over localhost TCP and commit
    ``num_commands`` commands through it. ``host`` launches the roles
    on another machine (see ``launch_roles``)."""
    protocol = get_protocol(protocol_name)
    raw = protocol.cluster(f, lambda: ["127.0.0.1", free_port()])
    config_path = bench.write_json("config.json", raw)
    config = protocol.load_config(raw)

    # Leaders' very first Phase1as can race slower-starting acceptor
    # processes; a fast resend rides that out without a long stall.
    overrides = {"resend_phase1as_period_s": "0.5", **(overrides or {})}

    t0 = time.time()
    labels = launch_roles(bench, protocol_name, config_path, config,
                          state_machine=state_machine,
                          overrides=overrides, host=host,
                          prometheus=prometheus, trace_dir=trace_dir)
    ready_s = time.time() - t0

    # In-process client over real TCP. A short resend period rides out
    # any leader still finishing Phase1/matchmaking/elections. The
    # try/finally starts HERE so a failed client-transport bind still
    # kills the role processes.
    transport = None
    try:
        logger = FakeLogger(LogLevel.FATAL)
        transport = TcpTransport(("127.0.0.1", free_port()), logger)
        transport.start()
        ctx = DeployCtx(config=config, transport=transport, logger=logger,
                        overrides={"resend_period_s": "0.5",
                                   "repropose_period_s": "0.5",
                                   "ping_period_s": "0.5"},
                        seed=0xC11E47, state_machine=state_machine)
        client = protocol.make_client(ctx, transport.listen_address)
        latencies = []
        for tag in range(num_commands):
            done = threading.Event()
            start = time.perf_counter()
            transport.loop.call_soon_threadsafe(
                protocol.drive, client, tag, lambda *_: done.set())
            if not done.wait(timeout=command_timeout_s):
                raise RuntimeError(
                    f"{protocol_name}: command {tag} never completed "
                    f"(roles: {labels})")
            latencies.append(time.perf_counter() - start)
    finally:
        if transport is not None:
            transport.stop()
        bench.cleanup()

    return {
        "protocol": protocol_name,
        "num_roles": len(labels),
        "num_commands": num_commands,
        "ready_s": round(ready_s, 3),
        "latency_ms": [round(x * 1000, 3) for x in latencies],
    }


def write_profile_reports(bench: BenchmarkDirectory,
                          top: int = 25) -> "dict[str, str]":
    """Render each role's cProfile dump (from ``profiled=True``) to a
    cumulative-time text report, the flamegraph-summary analog of
    benchmarks/perf_util.py. Returns {label: report_path}."""
    import glob
    import io
    import pstats

    reports = {}
    for prof in glob.glob(bench.abspath("*.prof")):
        label = os.path.basename(prof)[:-len(".prof")]
        out = io.StringIO()
        try:
            stats = pstats.Stats(prof, stream=out)
        except Exception as e:  # noqa: BLE001 - truncated dump (SIGKILL)
            print(f"skipping unreadable profile {prof}: {e!r}")
            continue
        stats.sort_stats("cumulative").print_stats(top)
        path = bench.abspath(f"{label}.profile.txt")
        with open(path, "w") as f:
            f.write(out.getvalue())
        reports[label] = path
    return reports

"""MultiPaxos deployment benchmark: every role its own OS process.

The analog of benchmarks/multipaxos/multipaxos.py: compute a placement
(ports on localhost; multipaxos.py:199-246), write the cluster config,
launch every role via the CLI over real TCP (multipaxos.py:311-577),
drive closed-loop clients, and report the reference-compatible stats.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time

from frankenpaxos_tpu.bench.harness import (
    BenchmarkDirectory,
    free_port,
    latency_throughput_stats,
)
from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
from frankenpaxos_tpu.runtime.serializer import PickleSerializer
from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport
from frankenpaxos_tpu.statemachine import SetRequest


@dataclasses.dataclass(frozen=True)
class MultiPaxosInput:
    """(multipaxos.py:33-96)."""

    f: int = 1
    num_acceptor_groups: int = 1
    num_replicas: int = 0  # 0 -> f + 1
    # Batchers between clients and leaders (Batcher.scala:60-90): the
    # whole batch shares ONE log slot -- the eurosys fig4 ~4x lever.
    # 0 disables (clients talk to leaders directly).
    num_batchers: int = 0
    batch_size: int = 1
    batch_flush_period_s: float = 0.05  # partial-batch flush
    num_clients: int = 2
    duration_s: float = 2.0
    quorum_backend: str = "dict"
    # The drain-granular run pipeline (ClientRequestArray -> Phase2aRun
    # -> Phase2bRange -> ChosenRun -> ClientReplyArray): clients
    # coalesce each event-loop pass's writes into one array and every
    # downstream hop works in contiguous slot runs.
    coalesced: bool = False
    state_machine: str = "KeyValueStore"
    # A ReadWriteWorkload (bench/workload.py); None -> the legacy
    # write-only SetRequest loop.
    workload: object = None
    # "linearizable" (quorum reads), "sequential", or "eventual"
    # (Client.scala:851-933, :697+, :739+).
    read_consistency: str = "linearizable"
    # > 0: drive load from this many separate client OS processes
    # (bench/client_main.py, the reference's ClientMain shape), each
    # running ``num_clients`` closed loops. 0: in-process threads.
    client_procs: int = 0
    # Expose per-role /metrics endpoints and record them in the results
    # (benchmarks/prometheus.py semantics).
    prometheus: bool = False
    # Coupled baseline: all roles colocated in one process
    # (SuperNode.scala:22+). Compartmentalized (False) vs coupled (True)
    # is the reference's headline 4-8x shape (BASELINE.md).
    supernode: bool = False
    # Run every role under cProfile (bench/role_cost.py consumes the
    # dumps; the perf_util.py flamegraph-wrap analog).
    profiled: bool = False
    # Durability root (wal/): acceptors/replicas log to
    # <wal_dir>/<label> with one group-commit fsync per drain and
    # recover on relaunch. None = the reference's in-memory behavior.
    wal_dir: "str | None" = None


def placement(input: MultiPaxosInput) -> dict:
    def addrs(n):
        return [["127.0.0.1", free_port()] for _ in range(n)]

    f = input.f
    return {
        "f": f,
        "batchers": addrs(input.num_batchers),
        "read_batchers": [],
        "leaders": addrs(f + 1),
        "leader_elections": addrs(f + 1),
        "proxy_leaders": addrs(f + 1),
        "acceptors": [addrs(2 * f + 1)
                      for _ in range(input.num_acceptor_groups)],
        "replicas": addrs(max(input.num_replicas, f + 1)),
        "proxy_replicas": [],
    }


def launch_with_retry(bench: BenchmarkDirectory, input: MultiPaxosInput,
                      **launch_kwargs) -> tuple:
    """Launch + leader warmup, with ONE retry on a fresh placement: a
    lost startup race (a port taken between allocation and bind, a role
    losing the scheduler lottery on a loaded host) is a deployment
    artifact, not a benchmark result, and a retry runs with entirely
    fresh ports. The per-role readiness itself is the launch_roles
    connect-back handshake. Returns ``(config_path, config)``."""
    for attempt in (1, 2):
        try:
            return _launch_and_warm(bench, input, **launch_kwargs)
        except RuntimeError as e:
            if attempt == 2:
                raise
            # Keep the failed attempt diagnosable: say what happened,
            # and move its role logs aside before the relaunch reopens
            # the same {label}.log paths with mode "w" (which would
            # destroy the attempt-1 evidence).
            print(f"deployment startup attempt {attempt} failed "
                  f"({e}); retrying with fresh ports")
            import glob

            for log in glob.glob(os.path.join(bench.path, "*.log")):
                os.replace(log, f"{log}.attempt{attempt}")


def run_benchmark(bench: BenchmarkDirectory,
                  input: MultiPaxosInput) -> dict:
    config_path, config = launch_with_retry(bench, input)

    if input.client_procs > 0:
        return _run_with_client_procs(bench, input, config_path)

    return _run_with_client_threads(bench, input, config)


def _launch_and_warm(bench: BenchmarkDirectory,
                     input: MultiPaxosInput,
                     extra_overrides: "dict | None" = None,
                     **launch_kwargs) -> tuple:
    """One deployment startup attempt: launch every role (handshake
    readiness) and commit a warmup write through leader 0. Raises
    RuntimeError -- with the roles already cleaned up -- on failure.
    ``extra_overrides`` are ``--options.*`` beyond what ``input``
    spells; ``launch_kwargs`` go to ``launch_roles``."""
    from frankenpaxos_tpu.bench.deploy_suite import launch_roles
    from frankenpaxos_tpu.deploy import get_protocol
    from frankenpaxos_tpu.protocols.multipaxos import Client, ClientOptions

    config_raw = placement(input)
    config_path = bench.write_json("config.json", config_raw)
    config = get_protocol("multipaxos").load_config(config_raw)
    overrides = {"quorum_backend": input.quorum_backend}
    if input.coalesced:
        overrides["coalesce_writes"] = "true"
    if input.num_batchers:
        overrides["batch_size"] = str(input.batch_size)
        overrides["flush_period_s"] = str(input.batch_flush_period_s)
    overrides.update(extra_overrides or {})
    launch_roles(bench, "multipaxos", config_path, config,
                 state_machine=input.state_machine,
                 overrides=overrides,
                 prometheus=input.prometheus, supernode=input.supernode,
                 profiled=input.profiled, wal_dir=input.wal_dir,
                 # The chip-owning role initialises the TPU and compiles
                 # its tracker's kernels before it reports ready; with a
                 # cold compile cache that is the bulk of set-up time.
                 ready_timeout_s=(120.0 if input.quorum_backend == "dict"
                                  else 300.0),
                 **launch_kwargs)

    # Explicit leader-ready probe: a warmup write with a short resend
    # period retries until leader 0 has completed Phase 1 and can commit
    # it. Only then does the measured run start.
    serializer = PickleSerializer()
    probe_logger = FakeLogger(LogLevel.FATAL)
    probe_transport = TcpTransport(("127.0.0.1", free_port()), probe_logger)
    probe_transport.start()
    # A gentle resend for the tpu backend: rapid duplicate requests
    # during its first (compile-paying) drains each get proposed to a
    # fresh slot, snowballing the very backlog the probe waits on.
    probe_resend_s = 0.25 if input.quorum_backend == "dict" else 2.0
    probe = Client(probe_transport.listen_address, probe_transport,
                   probe_logger, config,
                   ClientOptions(
                       resend_client_request_period_s=probe_resend_s),
                   seed=0xBEEF)
    ready = threading.Event()
    probe_transport.loop.call_soon_threadsafe(
        probe.write, 0, serializer.to_bytes(SetRequest((("warmup", "1"),))),
        lambda _: ready.set())
    ok = ready.wait(timeout=60)
    probe_transport.stop()
    if not ok:
        bench.cleanup()
        raise RuntimeError("leader never committed the warmup write")
    return config_path, config


def _run_with_client_threads(bench: BenchmarkDirectory,
                             input: MultiPaxosInput, config) -> dict:
    from frankenpaxos_tpu.protocols.multipaxos import Client, ClientOptions

    serializer = PickleSerializer()

    # Closed-loop clients (in-process, real TCP). Each op comes from the
    # workload: writes go through the Phase2 write path; reads through
    # the configured consistency path (linearizable quorum reads /
    # sequential / eventual, Client.scala:851-933, :697+, :739+).
    import random as _random

    from frankenpaxos_tpu.bench.workload import WRITE

    samples: dict[str, tuple[list, list]] = {
        "read": ([], []), "write": ([], [])}
    lock = threading.Lock()
    stop_at = time.time() + input.duration_s
    from frankenpaxos_tpu.bench.workload import READ_METHODS

    read_method = READ_METHODS[input.read_consistency]

    def run_client(i: int) -> None:
        logger = FakeLogger(LogLevel.FATAL)
        transport = TcpTransport(("127.0.0.1", free_port()), logger)
        transport.start()
        client = Client(transport.listen_address, transport, logger,
                        config,
                        ClientOptions(coalesce_writes=input.coalesced),
                        seed=i)
        rng = _random.Random(1000 + i)
        try:
            k = 0
            while time.time() < stop_at:
                if input.workload is not None:
                    kind, command = input.workload.get(rng)
                else:
                    kind = WRITE
                    command = serializer.to_bytes(
                        SetRequest(((f"k{i}", str(k)),)))
                op = (client.write if kind == WRITE
                      else getattr(client, read_method))
                done = threading.Event()
                t0 = time.perf_counter()
                wall0 = time.time()
                transport.loop.call_soon_threadsafe(
                    op, 0, command, lambda _: done.set())
                if not done.wait(timeout=10):
                    break
                with lock:
                    samples[kind][0].append(time.perf_counter() - t0)
                    samples[kind][1].append(wall0)
                k += 1
        finally:
            transport.stop()

    threads = [threading.Thread(target=run_client, args=(i,))
               for i in range(input.num_clients)]
    start = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.time() - start

    try:
        role_metrics = _scrape_role_metrics(bench, input)
        role_cpu = bench.role_cpu_seconds()
    finally:
        bench.cleanup()
    return _write_stats(bench, input, samples, elapsed, role_metrics,
                        input.workload, role_cpu)


def _run_with_client_procs(bench: BenchmarkDirectory,
                           input: MultiPaxosInput,
                           config_path: str) -> dict:
    """Drive load from separate client OS processes and aggregate their
    CSVs (the reference's ClientMain + parse-client-data shape,
    multipaxos.py:632-785)."""
    import json
    import sys

    from frankenpaxos_tpu.bench.deploy_suite import role_process_env
    from frankenpaxos_tpu.bench.harness import LocalHost
    from frankenpaxos_tpu.bench.workload import (
        StringWorkload,
        UniformReadWriteWorkload,
        WriteOnlyWorkload,
        workload_to_dict,
    )

    # Default workload must emit commands the deployed state machine can
    # parse: KV stores take pickled Get/SetRequests, the string family
    # (AppendLog/Noop/Register) takes raw bytes.
    workload = input.workload or (
        UniformReadWriteWorkload(num_keys=8, read_fraction=0.0)
        if input.state_machine == "KeyValueStore"
        else WriteOnlyWorkload(StringWorkload()))
    host = LocalHost()
    env = role_process_env()
    procs = []
    for i in range(input.client_procs):
        out_csv = bench.abspath(f"client_{i}_data.csv")
        procs.append((out_csv, bench.popen(host, f"client_{i}", [
            sys.executable, "-m", "frankenpaxos_tpu.bench.client_main",
            "--config", config_path,
            "--workload", json.dumps(workload_to_dict(workload)),
            "--num_clients", str(input.num_clients),
            "--duration", str(input.duration_s),
            "--read_consistency", input.read_consistency,
            "--seed", str(i), "--out", out_csv]
            + (["--client_options",
                json.dumps({"coalesce_writes": "true"})]
               if input.coalesced else []), env=env)))
    try:
        deadline = input.duration_s + 90
        for _, proc in procs:
            code = proc.wait(timeout=deadline)
            if code != 0:
                raise RuntimeError(
                    f"client process exited with code {code}; see "
                    f"{bench.path}")

        samples: dict[str, tuple[list, list]] = {
            "read": ([], []), "write": ([], [])}
        for out_csv, _ in procs:
            with open(out_csv) as f:
                next(f)  # header
                for line in f:
                    kind, start, latency = line.strip().split(",")
                    # Beyond read/write: "giveup" (RETRY_EXHAUSTED) and
                    # "thinned" rows are kept out of the ack stats.
                    lat, starts = samples.setdefault(kind, ([], []))
                    lat.append(float(latency))
                    starts.append(float(start))
        role_metrics = _scrape_role_metrics(bench, input)
        role_cpu = bench.role_cpu_seconds()
    finally:
        bench.cleanup()
    return _write_stats(bench, input, samples, input.duration_s,
                        role_metrics, workload, role_cpu)


def _scrape_role_metrics(bench: BenchmarkDirectory,
                         input: MultiPaxosInput) -> dict:
    """Scrape every role's /metrics endpoint (framework metrics only);
    must run before bench.cleanup() kills the roles. An endpoint that
    does not answer raises: a missing scrape is a failure, not a zero."""
    if not input.prometheus:
        return {}
    from frankenpaxos_tpu.bench.metrics import scrape

    return {label: {k: v for k, v in scrape(port).items()
                    if k.startswith("multipaxos_")}
            for label, port in bench.prometheus_ports.items()}


def _write_stats(bench: BenchmarkDirectory, input: MultiPaxosInput,
                 samples: dict, duration_s: float, role_metrics: dict,
                 workload, role_cpu: "dict | None" = None) -> dict:
    """Aggregate per-kind samples into the reference-shaped results
    (benchmark.py:308-341), tagged with the input and role metrics."""
    from frankenpaxos_tpu.bench.workload import workload_to_dict

    all_lat = samples["read"][0] + samples["write"][0]
    all_starts = samples["read"][1] + samples["write"][1]
    stats = latency_throughput_stats(all_lat, duration_s,
                                     starts_s=all_starts)
    for kind in ("read", "write"):
        lat, starts = samples[kind]
        if lat:
            sub = latency_throughput_stats(lat, duration_s,
                                           starts_s=starts)
            stats.update({f"{kind}.{k}": v for k, v in sub.items()})
    stats["input"] = dataclasses.asdict(input)
    if workload is not None:
        stats["input"]["workload"] = workload_to_dict(workload)
    if role_metrics:
        stats["role_metrics"] = role_metrics
    if role_cpu:
        stats["role_cpu_seconds"] = role_cpu
    bench.write_json("results.json", stats)
    return stats

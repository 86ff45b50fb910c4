"""Benchmark client process: closed-loop workload driver.

The analog of the reference's ClientMain + BenchmarkUtil
(jvm/.../multipaxos/ClientMain.scala, BenchmarkUtil.scala:9-160): run
``--num_clients`` closed loops (one per pseudonym) against a deployed
cluster for ``--duration`` seconds, drawing ops from a ReadWriteWorkload,
and write one CSV row per completed op:
``kind,start_unix_s,latency_s`` (benchmark.py:310-335's recorder shape).

Ops are chained on the transport's event loop -- each completion issues
the pseudonym's next op -- so one process drives many concurrent closed
loops without a thread per client.

Usage::

    python -m frankenpaxos_tpu.bench.client_main --config cluster.json \
        --workload '{"name": "uniform_read_write", "read_fraction": 0.9}' \
        --duration 5 --num_clients 20 --out client_data.csv
"""

from __future__ import annotations

import argparse
import json
import random
import threading
import time

from frankenpaxos_tpu.bench.harness import free_port
from frankenpaxos_tpu.bench.workload import (
    READ_METHODS,
    StringWorkload,
    workload_from_dict,
    WRITE,
    WriteOnlyWorkload,
)
from frankenpaxos_tpu.deploy import DeployCtx, get_protocol
from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport
from frankenpaxos_tpu.serve.backoff import RETRY_EXHAUSTED



def _closed_loops(transport, num_loops: int, duration_s: float,
                  warmup_s: float, issue_op, stop: bool = True) -> list:
    """Shared closed-loop machinery: run ``num_loops`` callback-chained
    loops on the transport's event loop for ``duration_s`` (after a
    ``warmup_s`` settling window), recording one row per completed op.

    ``issue_op(i, finished)`` issues loop ``i``'s next op and arranges
    for ``finished(kind)`` on completion. Reissues are rescheduled via
    call_soon rather than recursed: a protocol that answers
    synchronously (an already-chosen single-decree value) would
    otherwise blow the stack. ``stop=False`` leaves the transport
    running for a caller that has more to ask of the cluster.
    """
    rows: list = []
    done = threading.Event()
    stop_at = time.time() + warmup_s + duration_s
    measure_from = time.time() + warmup_s
    live = {"count": num_loops}

    def issue(i: int) -> None:
        now = time.time()
        if now >= stop_at:
            live["count"] -= 1
            if live["count"] == 0:
                done.set()
            return
        t0 = time.perf_counter()

        def finished(kind: str) -> None:
            if now >= measure_from:
                rows.append((kind, now, time.perf_counter() - t0))
            transport.loop.call_soon(issue, i)

        issue_op(i, finished)

    for i in range(num_loops):
        transport.loop.call_soon_threadsafe(issue, i)
    done.wait(timeout=warmup_s + duration_s + 30)
    if stop:
        transport.stop()
    return rows


def run(protocol_name: str, config_raw: dict, workload, *,
        num_clients: int, duration_s: float, read_consistency: str,
        seed: int = 0, warmup_s: float = 0.25,
        overrides: dict | None = None) -> list:
    """Drive the workload against multipaxos (pseudonym-keyed write/read
    client loops); returns [(kind, start_unix_s, latency_s)]."""
    protocol = get_protocol(protocol_name)
    config = protocol.load_config(config_raw)
    logger = FakeLogger(LogLevel.FATAL)
    transport = TcpTransport(("127.0.0.1", free_port()), logger)
    transport.start()
    ctx = DeployCtx(config=config, transport=transport, logger=logger,
                    overrides=overrides or {}, seed=seed)
    client = protocol.make_client(ctx, transport.listen_address)
    read_method = READ_METHODS[read_consistency]
    rngs = [random.Random((seed << 20) + p) for p in range(num_clients)]

    def issue_op(pseudonym: int, finished) -> None:
        kind, command = workload.get(rngs[pseudonym])
        op = (client.write if kind == WRITE
              else getattr(client, read_method))
        # Retry-budget give-ups are labeled, never counted as acks --
        # a backoff-dominated RETRY_EXHAUSTED sample would otherwise
        # inflate throughput and corrupt the latency percentiles.
        op(pseudonym, command,
           lambda reply: finished(
               "giveup" if reply is RETRY_EXHAUSTED else kind))

    return _closed_loops(transport, num_clients, duration_s, warmup_s,
                         issue_op)


def run_open_loop(protocol_name: str, config_raw: dict, workload, *,
                  num_sessions: int, duration_s: float,
                  read_consistency: str = "linearizable", seed: int = 0,
                  warmup_s: float = 0.5,
                  overrides: dict | None = None) -> list:
    """OPEN-loop driver (paxload): ops issue on the arrival process's
    schedule, independent of completions -- the load shape overload
    needs (a closed loop self-throttles and can never offer more than
    the cluster absorbs). ``workload`` is the SHARED
    :class:`~frankenpaxos_tpu.bench.workload.OpenLoopWorkload`, the
    same definition the sim tier draws from (serve/loadgen.py), so
    "10x offered load" means the same arrival process, key skew, and
    mix on both arms.

    Sessions are a pseudonym pool: an arrival binds a free pseudonym;
    when none is free the arrival is dropped-at-the-source and counted
    (``thinned`` rows are not latencies -- the row kind says what
    happened: write/read kinds, ``giveup`` for RETRY_EXHAUSTED
    conclusions). Returns [(kind, start_unix_s, latency_s)] plus one
    ``("thinned", t, count)`` tail row when any arrivals were thinned.
    """
    import numpy as np

    protocol = get_protocol(protocol_name)
    config = protocol.load_config(config_raw)
    logger = FakeLogger(LogLevel.FATAL)
    transport = TcpTransport(("127.0.0.1", free_port()), logger)
    transport.start()
    ctx = DeployCtx(config=config, transport=transport, logger=logger,
                    overrides=overrides or {}, seed=seed)
    client = protocol.make_client(ctx, transport.listen_address)
    read_method = READ_METHODS[read_consistency]
    np_rng = np.random.default_rng(seed)
    rng = random.Random(seed)
    rows: list = []
    done = threading.Event()
    idle = list(range(num_sessions))
    thinned = {"count": 0}
    dt = 0.02
    t_start = time.time()
    measure_from = t_start + warmup_s
    stop_at = t_start + warmup_s + duration_s

    # Absolute fire schedule: each window draws arrivals for exactly dt
    # of the arrival process, and a window that runs long is followed by
    # catch-up windows back-to-back, so offered load stays rate*duration
    # even when per-window work inflates the period (otherwise the
    # driver would self-throttle at exactly the loads it exists for).
    sched = {"t": t_start}

    def window() -> None:
        now = time.time()
        if now >= stop_at:
            done.set()
            return
        for _ in range(workload.arrival_count(np_rng, sched["t"] - t_start,
                                              dt)):
            if not idle:
                thinned["count"] += 1
                continue
            pseudonym = idle.pop()
            kind, command = workload.get(rng)
            t0 = time.perf_counter()

            def finished(result, pseudonym=pseudonym, kind=kind,
                         t0=t0, issued=now) -> None:
                idle.append(pseudonym)
                label = ("giveup" if result is RETRY_EXHAUSTED
                         else kind)
                if issued >= measure_from:
                    rows.append((label, issued,
                                 time.perf_counter() - t0))

            op = (client.write if kind == WRITE
                  else getattr(client, read_method))
            op(pseudonym, command, finished)
        flush = getattr(client, "flush_writes", None)
        if flush is not None:
            flush()
        sched["t"] += dt
        transport.loop.call_later(max(0.0, sched["t"] - time.time()), window)

    transport.loop.call_soon_threadsafe(window)
    done.wait(timeout=warmup_s + duration_s + 30)
    transport.stop()
    if thinned["count"]:
        rows.append(("thinned", time.time(), float(thinned["count"])))
    return rows


def run_skewed(protocol_name: str, config_raw: dict, *,
               point_fraction: float, num_clients: int,
               duration_s: float, seed: int = 0,
               warmup_s: float = 0.25, num_keys: int = 16) -> list:
    """Point-skewed KV write loops for the conflict-sensitivity sweep
    (vldb21_compartmentalized/compartmentalized_skew, craq_skew):
    ``point_fraction`` of writes hit ONE hot key, the rest uniform --
    the knob that changes EPaxos fast-path conflict rates and CRAQ
    chain contention. Commands are protocol-appropriate: CRAQ's native
    chain KV write; pickled SetRequests against a KeyValueStore for
    epaxos/multipaxos."""
    import pickle

    from frankenpaxos_tpu.statemachine import SetRequest

    protocol = get_protocol(protocol_name)
    config = protocol.load_config(config_raw)
    logger = FakeLogger(LogLevel.FATAL)
    transport = TcpTransport(("127.0.0.1", free_port()), logger)
    transport.start()
    ctx = DeployCtx(config=config, transport=transport, logger=logger,
                    overrides={}, seed=seed)
    client = protocol.make_client(ctx, transport.listen_address)
    rngs = [random.Random((seed << 20) + p) for p in range(num_clients)]
    tags = {"next": 0}

    def issue_op(i: int, finished) -> None:
        rng = rngs[i]
        key = ("point" if rng.random() < point_fraction
               else str(rng.randrange(num_keys)))
        tags["next"] += 1
        value = "v%d" % tags["next"]
        done = lambda *reply: finished(  # noqa: E731
            "giveup" if reply and reply[0] is RETRY_EXHAUSTED else "write")
        if protocol_name == "craq":
            client.write(i, key, value, done)
        elif protocol_name == "epaxos":
            client.propose(i, pickle.dumps(SetRequest(((key, value),))),
                           done)
        else:  # multipaxos
            client.write(i, pickle.dumps(SetRequest(((key, value),))),
                         done)

    return _closed_loops(transport, num_clients, duration_s, warmup_s,
                         issue_op)


def run_readback(config_raw: dict, *, num_clients: int, duration_s: float,
                 seed: int = 0, warmup_s: float = 0.25,
                 overrides: dict | None = None) -> dict:
    """Closed write loops against a multipaxos KeyValueStore whose
    outcome can be CHECKED: loop ``p`` owns key ``k<seed>.<p>`` and
    writes 0, 1, 2, ... to it, so every key's last acknowledged value is
    known, and once the loops stop every written key is read back with
    a linearizable read. Returns the counts and every discrepancy:
    ``unacked`` (a write that got no reply, or a give-up) and
    ``mismatched`` (a read that did not return the last acked value)."""
    from frankenpaxos_tpu.runtime.serializer import PickleSerializer
    from frankenpaxos_tpu.statemachine import GetRequest, SetRequest

    serializer = PickleSerializer()
    protocol = get_protocol("multipaxos")
    config = protocol.load_config(config_raw)
    logger = FakeLogger(LogLevel.FATAL)
    transport = TcpTransport(("127.0.0.1", free_port()), logger)
    transport.start()
    ctx = DeployCtx(config=config, transport=transport, logger=logger,
                    overrides=overrides or {}, seed=seed)
    client = protocol.make_client(ctx, transport.listen_address)
    keys = [f"k{seed}.{p}" for p in range(num_clients)]
    issued = [0] * num_clients
    acked = [-1] * num_clients   # last acknowledged value per loop
    num_acked = [0]

    def issue_op(p: int, finished) -> None:
        value = issued[p]
        issued[p] += 1

        def on_reply(reply) -> None:
            if reply is RETRY_EXHAUSTED:
                finished("giveup")
                return
            acked[p] = value
            num_acked[0] += 1
            finished(WRITE)

        client.write(p, serializer.to_bytes(
            SetRequest(((keys[p], str(value)),))), on_reply)

    rows = _closed_loops(transport, num_clients, duration_s, warmup_s,
                         issue_op, stop=False)
    unacked = [keys[p] for p in range(num_clients)
               if acked[p] != issued[p] - 1]

    # A loop whose write is still outstanding is not idle, and reading
    # on it would trip the client's one-op-per-pseudonym check; with
    # any write unacknowledged the run has failed already.
    to_read = ([] if unacked
               else [p for p in range(num_clients) if acked[p] >= 0])
    read = {}
    all_read = threading.Event()

    def read_all() -> None:
        for p in to_read:
            def on_reply(raw, p=p) -> None:
                read[p] = dict(
                    serializer.from_bytes(raw).key_values).get(keys[p])
                if len(read) == len(to_read):
                    all_read.set()

            client.read(p, serializer.to_bytes(GetRequest((keys[p],))),
                        on_reply)

    if to_read:
        transport.loop.call_soon_threadsafe(read_all)
        all_read.wait(timeout=60)
    transport.stop()
    mismatched = [keys[p] for p in to_read
                  if read.get(p) != str(acked[p])]
    return {"keys": num_clients,
            "writes_issued": sum(issued), "writes_acked": num_acked[0],
            "keys_read_back": len(to_read) - len(mismatched),
            "unacked": unacked, "mismatched": mismatched,
            "rows": rows}


def run_drive(protocol_name: str, config_raw: dict, *,
              num_clients: int, duration_s: float, seed: int = 0,
              warmup_s: float = 0.25,
              client_overrides: dict | None = None) -> list:
    """Protocol-agnostic closed loops: one client actor per loop (each
    on its own port via the transport's multi-bind), driven through the
    registry's ``drive`` entry -- works for every protocol the smoke
    deploys. Returns [("write", start_unix_s, latency_s)].

    ``client_overrides`` adds ``--options.*``-style client constructor
    overrides (e.g. ``{"coalesce_writes": "true"}`` for run-pipeline
    clients)."""
    protocol = get_protocol(protocol_name)
    config = protocol.load_config(config_raw)
    logger = FakeLogger(LogLevel.FATAL)
    transport = TcpTransport(("127.0.0.1", free_port()), logger)
    transport.start()
    clients = []
    for i in range(num_clients):
        ctx = DeployCtx(config=config, transport=transport, logger=logger,
                        overrides={"resend_period_s": "1.0",
                                   "repropose_period_s": "1.0",
                                   **(client_overrides or {})},
                        seed=(seed << 8) + i)
        address = (transport.listen_address if i == 0
                   else ("127.0.0.1", free_port()))
        clients.append(protocol.make_client(ctx, address))

    tags = {"next": 0}

    def issue_op(i: int, finished) -> None:
        tag = tags["next"]
        tags["next"] += 1
        protocol.drive(clients[i], tag, lambda *reply: finished(
            "giveup" if reply and reply[0] is RETRY_EXHAUSTED else "write"))

    return _closed_loops(transport, num_clients, duration_s, warmup_s,
                         issue_op)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--protocol", default="multipaxos")
    parser.add_argument("--config", required=True)
    parser.add_argument("--workload", default=None,
                        help="JSON workload spec (bench/workload.py)")
    parser.add_argument("--num_clients", type=int, default=1)
    parser.add_argument("--duration", type=float, required=True)
    parser.add_argument("--read_consistency", default="linearizable")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--client_options", default=None,
                        help="JSON dict of ClientOptions overrides "
                             "(e.g. {\"coalesce_writes\": \"true\"})")
    parser.add_argument("--point_skew", type=float, default=None,
                        help="point-skewed KV write loops with this "
                             "hot-key fraction (conflict sweep)")
    parser.add_argument("--open_loop", action="store_true",
                        help="OPEN-loop arrivals from the shared "
                             "OpenLoopWorkload (paxload): the "
                             "--workload spec must be "
                             '{"name": "open_loop", "rate": ...}')
    parser.add_argument("--num_sessions", type=int, default=1024,
                        help="open-loop pseudonym pool size")
    parser.add_argument("--readback", default=None, metavar="JSON",
                        help="checked KV write loops (run_readback): "
                             "one key per loop, every key read back "
                             "linearizably at the end; the verdict goes "
                             "to this JSON file, the ops to --out")
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)

    with open(args.config) as f:
        config_raw = json.load(f)

    if args.readback:
        verdict = run_readback(
            config_raw, num_clients=args.num_clients,
            duration_s=args.duration, seed=args.seed,
            overrides=(json.loads(args.client_options)
                       if args.client_options else None))
        rows = verdict.pop("rows")
        with open(args.readback, "w") as f:
            json.dump(verdict, f)
    elif args.open_loop:
        from frankenpaxos_tpu.bench.workload import OpenLoopWorkload

        workload = (workload_from_dict(json.loads(args.workload))
                    if args.workload else OpenLoopWorkload())
        assert isinstance(workload, OpenLoopWorkload), \
            "--open_loop needs an open_loop workload spec"
        rows = run_open_loop(args.protocol, config_raw, workload,
                             num_sessions=args.num_sessions,
                             duration_s=args.duration,
                             read_consistency=args.read_consistency,
                             seed=args.seed,
                             overrides=(json.loads(args.client_options)
                                        if args.client_options
                                        else None))
    elif args.point_skew is not None:
        rows = run_skewed(args.protocol, config_raw,
                          point_fraction=args.point_skew,
                          num_clients=args.num_clients,
                          duration_s=args.duration, seed=args.seed)
    elif args.protocol != "multipaxos" and args.workload is None:
        # Generic closed loops via the registry's drive() -- any
        # protocol the smoke can deploy can be benchmarked.
        rows = run_drive(args.protocol, config_raw,
                         num_clients=args.num_clients,
                         duration_s=args.duration, seed=args.seed,
                         client_overrides=(json.loads(args.client_options)
                                           if args.client_options
                                           else None))
    else:
        workload = (workload_from_dict(json.loads(args.workload))
                    if args.workload
                    else WriteOnlyWorkload(StringWorkload(size_mean=8)))
        rows = run(args.protocol, config_raw, workload,
                   num_clients=args.num_clients,
                   duration_s=args.duration,
                   read_consistency=args.read_consistency,
                   seed=args.seed,
                   overrides=(json.loads(args.client_options)
                              if args.client_options else None))
    with open(args.out, "w") as f:
        f.write("kind,start_unix_s,latency_s\n")
        for kind, start, latency in rows:
            f.write(f"{kind},{start!r},{latency!r}\n")
    print(f"wrote {len(rows)} ops to {args.out}", flush=True)


if __name__ == "__main__":
    main()

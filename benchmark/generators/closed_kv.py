#!/usr/bin/env python3
"""One load-generator process: closed loops over the client library,
against a key-value store whose keys all loops share.

A copy of ``frankenpaxos_tpu/bench/client_main.py::_closed_loops`` and
``run_readback``, with a warm-up and a measured window that the parent
fixes for all generators at once, and with every operation kept, so that
the parent can compute the metrics and the reference can judge every
answer.

The process builds one ``TcpTransport`` and one MultiPaxos ``Client``
(the entry a user's request takes), prints ``ready`` and waits on its
standard input for ``go <start> <end>``, two instants of
``time.monotonic()``. From ``go`` each of its loops issues one operation
at a time, the next when the last is answered; operations issued in
``[start, end)`` are the window's. After ``end`` no loop issues, every
outstanding operation is waited for (up to ``GRACE_S``: late is late,
not lost), and every key this process wrote is read back with a
linearizable read.

One clock. Every instant this process records or compares (an
operation's issue and its answer, "is the window over") is a read of
``time.monotonic()``: ``CLOCK_MONOTONIC``, which all processes of a
Linux host share and which is never stepped. The launcher's ``go`` and
the reference's real-time checks stand on the same clock. The wall clock
is read twice, at ``go`` and at exit, only to report whether it moved
against the monotonic one meanwhile (``wall_minus_mono_s``).

The traffic file's parameters (upstream's ``UniformReadWriteWorkload``:
``num_keys``, ``read_fraction``, ``write_size_mean``):

  keys              how many keys there are, named "0", "1", ...; every
                    loop of every generator draws from all of them
  key_distribution  ``uniform``
  read_share        the share of operations that are linearizable reads
  value_bytes       the size of a written value, 16 or more

Keys are shared, so no loop knows what a key holds. Instead every write
carries a value of its own: 16 hexadecimal digits that name the
generator, the loop and the loop's count of writes (``write_id``),
padded to ``value_bytes``. The reference finds each write in the
replicas' executed logs by it, and holds every read to the writes that
were acknowledged before it was issued.

Written to ``<out>.npz``, one row per operation: ``issue_mono_s``,
``latency_s`` (the answer's instant less the issue's, both monotonic;
-1: never answered), ``kind`` (0 write, 1 read), ``key`` and ``value``
(a write's id; for a read the id it returned, ``ABSENT`` or
``UNREADABLE``). To ``<out>.json``: the counts, ``end_mono_s`` and the
two readings of ``wall_minus_mono_s``.
"""

from __future__ import annotations

import argparse
import array
import json
import os
import random
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GRACE_S = 60.0
WRITE, READ = 0, 1
ID_DIGITS = 16
ABSENT, UNREADABLE, UNANSWERED = -1, -2, -3


def write_id(generator: int, loop: int, count: int) -> int:
    """Generators below 128, loops below 65536, a loop's writes below
    2**40: one signed 64-bit number."""
    return generator << 56 | loop << 40 | count


def read_id(value) -> int:
    if value is None:
        return ABSENT
    try:
        return int(value[:ID_DIGITS], 16)
    except ValueError:
        return UNREADABLE


def wall_minus_mono() -> float:
    """The wall clock against the monotonic one. A step of the wall clock
    (NTP, a VM put right after a pause) shows as a change between two
    readings; nothing is compared with it."""
    return time.time() - time.monotonic()


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--cluster", required=True)
    parser.add_argument("--protocol", required=True)
    parser.add_argument("--traffic", required=True)
    parser.add_argument("--client_options", default="{}")
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--index", type=int, required=True)
    parser.add_argument("--out", required=True)
    args = parser.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import numpy as np

    from frankenpaxos_tpu.bench.harness import free_port
    from frankenpaxos_tpu.deploy import DeployCtx, get_protocol
    from frankenpaxos_tpu.runtime import FakeLogger, LogLevel
    from frankenpaxos_tpu.runtime.serializer import PickleSerializer
    from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport
    from frankenpaxos_tpu.serve.backoff import RETRY_EXHAUSTED
    from frankenpaxos_tpu.statemachine import GetRequest, SetRequest

    with open(args.traffic) as f:
        traffic = json.load(f)
    if traffic["loop"] != "closed":
        raise SystemExit(f"loop kind {traffic['loop']!r}: this generator "
                         f"runs closed loops only")
    if traffic["key_distribution"] != "uniform":
        raise SystemExit(f"key_distribution "
                         f"{traffic['key_distribution']!r}: this generator "
                         f"draws keys uniformly only")
    if traffic["value_bytes"] < ID_DIGITS:
        raise SystemExit(f"value_bytes below {ID_DIGITS}: a value has to "
                         f"hold its write's id")
    with open(args.cluster) as f:
        cluster = json.load(f)
    num_loops = traffic["loops_per_proc"]
    num_keys = traffic["keys"]
    read_share = traffic["read_share"]
    padding = "x" * (traffic["value_bytes"] - ID_DIGITS)

    serializer = PickleSerializer()
    protocol = get_protocol(args.protocol)
    logger = FakeLogger(LogLevel.FATAL)
    transport = TcpTransport(("127.0.0.1", free_port()), logger)
    transport.start()
    # The client's own seed is folded to 31 bits: a driver's --seed may
    # be a little over 2**31.
    ctx = DeployCtx(config=protocol.load_config(cluster),
                    transport=transport, logger=logger,
                    overrides=json.loads(args.client_options),
                    seed=(args.seed * 1009 + args.index) % (2 ** 31 - 1))
    client = protocol.make_client(ctx, transport.listen_address)

    keys = [str(k) for k in range(num_keys)]
    rngs = [random.Random(f"{args.seed}/{args.index}/{p}")
            for p in range(num_loops)]
    writes_of = [0] * num_loops
    written: set = set()            # keys with an acknowledged write
    outstanding = [False] * num_loops
    issue_mono_s = array.array("d")
    latency_s = array.array("d")
    kinds = array.array("b")
    op_keys = array.array("i")
    op_values = array.array("q")
    counts = {"gave_up": 0, "live": num_loops}
    done = threading.Event()

    print("ready", flush=True)
    word, start, end = sys.stdin.readline().split()
    if word != "go":
        raise SystemExit(f"expected 'go <start> <end>', got {word!r}")
    end = float(end)
    wall_minus_mono_s = [wall_minus_mono()]

    def send(p: int, key: int, read: bool, then) -> None:
        """One operation of loop ``p``; ``then()`` once it is answered."""
        row = len(latency_s)
        issued = time.monotonic()
        issue_mono_s.append(issued)
        latency_s.append(-1.0)
        kinds.append(READ if read else WRITE)
        op_keys.append(key)
        outstanding[p] = True

        def answered() -> None:
            latency_s[row] = time.monotonic() - issued
            outstanding[p] = False
            then()

        if read:
            op_values.append(UNANSWERED)

            def on_read(raw) -> None:
                if raw is RETRY_EXHAUSTED:
                    counts["gave_up"] += 1
                    return
                op_values[row] = read_id(dict(serializer.from_bytes(
                    raw).key_values).get(keys[key]))
                answered()

            client.read(p, serializer.to_bytes(GetRequest((keys[key],))),
                        on_read)
        else:
            mine = write_id(args.index, p, writes_of[p])
            writes_of[p] += 1
            op_values.append(mine)

            def on_write(reply) -> None:
                if reply is RETRY_EXHAUSTED:
                    counts["gave_up"] += 1
                    return
                written.add(key)
                answered()

            client.write(p, serializer.to_bytes(SetRequest(
                ((keys[key], f"{mine:016x}" + padding),))), on_write)

    def issue(p: int) -> None:
        if time.monotonic() >= end:
            counts["live"] -= 1
            if counts["live"] == 0:
                done.set()
            return
        rng = rngs[p]
        key = rng.randrange(num_keys) if num_keys > 1 else 0
        read = read_share > 0 and rng.random() < read_share
        # Rescheduled, not recursed: see client_main._closed_loops.
        send(p, key, read, lambda: transport.loop.call_soon(issue, p))

    for p in range(num_loops):
        transport.loop.call_soon_threadsafe(issue, p)
    done.wait(timeout=max(0.0, end - time.monotonic()) + GRACE_S)

    # Read back every key this process wrote, on the loops that are free
    # (one operation per pseudonym), each loop its keys one after another.
    free = [p for p in range(num_loops) if not outstanding[p]]
    to_read = sorted(written) if free else []
    left = [len(to_read)]
    all_read = threading.Event()

    def read_next(p: int, mine: list, at: int) -> None:
        if at == len(mine):
            return

        def then() -> None:
            left[0] -= 1
            if left[0] == 0:
                all_read.set()
            read_next(p, mine, at + 1)

        send(p, mine[at], True, then)

    def read_all() -> None:
        for n, p in enumerate(free[:len(to_read)]):
            read_next(p, to_read[n::len(free)], 0)

    if to_read:
        transport.loop.call_soon_threadsafe(read_all)
        all_read.wait(timeout=GRACE_S)
    transport.stop()
    wall_minus_mono_s.append(wall_minus_mono())

    np.savez(args.out + ".npz",
             issue_mono_s=np.frombuffer(issue_mono_s, dtype=np.float64),
             latency_s=np.frombuffer(latency_s, dtype=np.float64),
             kind=np.frombuffer(kinds, dtype=np.int8),
             key=np.frombuffer(op_keys, dtype=np.int32),
             value=np.frombuffer(op_values, dtype=np.int64))
    with open(args.out + ".json", "w") as f:
        json.dump({"index": args.index, "keys": keys, "end_mono_s": end,
                   "wall_minus_mono_s": wall_minus_mono_s,
                   "gave_up": counts["gave_up"],
                   "loops_stuck": sum(outstanding)}, f)


if __name__ == "__main__":
    main()

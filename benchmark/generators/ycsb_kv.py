#!/usr/bin/env python3
"""One load-generator process of a YCSB-style mix: a table loaded before
the window, then closed loops of reads and updates on keys drawn from a
Zipfian law.

The contract is ``closed_kv.py``'s (which see: ``ready``, then ``go
<start> <end>`` on ``time.monotonic()``, the same columns, every write's
value beginning with its own 16-hex id, every instant monotonic, the
read-back, ``wall_minus_mono_s``), and this file is a copy of it with
two things added.

The load phase, before ``ready``. The table has ``keys`` records, named
"0", "1", ...; process ``index`` of ``client_procs`` inserts those whose
number is ``index`` modulo ``client_procs``, through ``Client.write`` on
the normal path, ``load_batch_records`` records a ``SetRequest``, on
``load_loops`` of its loops at a time (so what one drain of the
deployment carries stays far under the transport's frame limit). Each
record's value is its own id padded to ``value_bytes``. Each inserted
record is one row of the output, kind write, issued before the window;
the rows of one ``SetRequest`` share their instants. ``load_rows`` in
``<out>.json`` says how many leading rows they are.

The draws. Which key and whether to read come from ``Draws``: numpy
blocks of ``BLOCK`` draws, handed out one an operation to whichever loop
issues next. A key is a rank drawn from the Zipfian law over ``keys``
ranks (``P(rank r) ~ 1 / r**zipfian_constant``, by inverting the
cumulative sums), mapped to a record by a permutation made from
``--seed`` alone (``scrambled``: all processes share it, so they agree on
which records are hot, and another seed heats others). Reading or
updating is a second uniform draw against ``read_share``. The same seed
and index give the same stream.

Read-back: every key this process updated after the load, as
``closed_kv.py`` reads back what it wrote. The loaded records are held
to the replicas' stores instead, by the reference.

One column more than ``closed_kv.py`` writes: ``length``, the length of
the value a write carried or a read returned (0: absent).

The traffic file's parameters (YCSB's names beside them):

  keys               recordcount
  key_distribution   ``zipfian`` (requestdistribution)
  zipfian_constant   ZipfianGenerator.ZIPFIAN_CONSTANT
  scrambled          whether ranks are permuted over the records
  read_share         readproportion; the rest are updates
  value_bytes        fieldcount x fieldlength, read and written whole
  load_batch_records records a ``SetRequest`` of the load phase
  load_loops         loops of a process that load at a time
"""

from __future__ import annotations

import argparse
import array
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

GRACE_S = 60.0
#: The load phase gives up after this long without finishing.
LOAD_TIMEOUT_S = 240.0
WRITE, READ = 0, 1
ID_DIGITS = 16
ABSENT, UNREADABLE, UNANSWERED = -1, -2, -3
#: Draws made at a time.
BLOCK = 1 << 16


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
    """The wall clock against the monotonic one; nothing is compared
    with it (see ``closed_kv.py``)."""
    return time.time() - time.monotonic()


def zipfian_cdf(np, ranks: int, constant: float):
    """Cumulative probability of ranks 1..``ranks`` under
    ``P(r) ~ r**-constant``; the last is exactly 1."""
    weights = np.arange(1, ranks + 1, dtype=np.float64) ** -constant
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    cdf[-1] = 1.0
    return cdf


class Draws:
    """One process's stream of (key, read?) pairs."""

    def __init__(self, np, seed: int, index: int, traffic: dict):
        self.np = np
        keys = traffic["keys"]
        self.read_share = traffic["read_share"]
        self.cdf = zipfian_cdf(np, keys, traffic["zipfian_constant"])
        # rank - 1 -> record; from the seed alone, so shared.
        self.record_of = (
            np.random.default_rng([seed, 0x5C4A3B]).permutation(keys)
            if traffic["scrambled"] else np.arange(keys))
        self.rng = np.random.default_rng([seed, index])
        self.keys: list = []
        self.reads: list = []
        self.at = 0

    def block(self, count: int = BLOCK) -> tuple:
        """The next ``count`` draws as arrays: records, and whether each
        is a read."""
        # A draw is below 1 and the last cumulative sum is 1, so every
        # place found is a rank.
        ranks = self.np.searchsorted(self.cdf, self.rng.random(count),
                                     side="right")
        return self.record_of[ranks], self.rng.random(count) < self.read_share

    def next(self) -> tuple:
        if self.at == len(self.keys):
            keys, reads = self.block()
            self.keys, self.reads, self.at = keys.tolist(), reads.tolist(), 0
        self.at += 1
        return self.keys[self.at - 1], self.reads[self.at - 1]


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
    if traffic["key_distribution"] != "zipfian":
        raise SystemExit(f"key_distribution "
                         f"{traffic['key_distribution']!r}: this generator "
                         f"draws keys from a Zipfian law only")
    if traffic["value_bytes"] < ID_DIGITS:
        raise SystemExit(f"value_bytes below {ID_DIGITS}: a value has to "
                         f"hold its write's id")
    with open(args.cluster) as f:
        cluster = json.load(f)
    num_loops = traffic["loops_per_proc"]
    num_keys = traffic["keys"]
    value_bytes = traffic["value_bytes"]
    padding = "x" * (value_bytes - ID_DIGITS)
    draws = Draws(np, args.seed, args.index, traffic)

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
    writes_of = [0] * num_loops
    written: set = set()            # keys updated after the load
    outstanding = [False] * num_loops
    issue_mono_s = array.array("d")
    latency_s = array.array("d")
    kinds = array.array("b")
    op_keys = array.array("i")
    op_values = array.array("q")
    lengths = array.array("i")
    counts = {"gave_up": 0, "live": num_loops}

    # The load phase: this process's records, a batch a SetRequest, each
    # loading loop its batches one after another.
    mine = range(args.index, num_keys, traffic["client_procs"])
    batch = traffic["load_batch_records"]
    batches = iter([mine[at:at + batch]
                    for at in range(0, len(mine), batch)])
    loading = [min(num_loops, traffic["load_loops"])]
    loaded = threading.Event()

    def load_next(p: int) -> None:
        records = next(batches, None)
        if records is None:
            loading[0] -= 1
            if loading[0] == 0:
                loaded.set()
            return
        row = len(latency_s)
        issued = time.monotonic()
        pairs = []
        for key in records:
            value = write_id(args.index, p, writes_of[p])
            writes_of[p] += 1
            issue_mono_s.append(issued)
            latency_s.append(-1.0)
            kinds.append(WRITE)
            op_keys.append(key)
            op_values.append(value)
            lengths.append(value_bytes)
            pairs.append((keys[key], f"{value:016x}" + padding))

        def on_load(reply) -> None:
            if reply is RETRY_EXHAUSTED:
                counts["gave_up"] += 1
                return
            took = time.monotonic() - issued
            for at in range(row, row + len(pairs)):
                latency_s[at] = took
            transport.loop.call_soon(load_next, p)

        client.write(p, serializer.to_bytes(SetRequest(tuple(pairs))),
                     on_load)

    for p in range(loading[0]):
        transport.loop.call_soon_threadsafe(load_next, p)
    if not loaded.wait(timeout=LOAD_TIMEOUT_S):
        raise SystemExit(f"the table was not loaded in {LOAD_TIMEOUT_S} s: "
                         f"{len(latency_s)} of {len(mine)} records issued, "
                         f"{counts['gave_up']} inserts given up")
    load_rows = len(latency_s)
    done = threading.Event()

    print("ready", flush=True)
    word, start, end = sys.stdin.readline().split()
    if word != "go":
        raise SystemExit(f"expected 'go <start> <end>', got {word!r}")
    start, end = float(start), float(end)
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
            lengths.append(0)

            def on_read(raw) -> None:
                if raw is RETRY_EXHAUSTED:
                    counts["gave_up"] += 1
                    return
                value = dict(serializer.from_bytes(raw).key_values).get(
                    keys[key])
                op_values[row] = read_id(value)
                lengths[row] = 0 if value is None else len(value)
                answered()

            client.read(p, serializer.to_bytes(GetRequest((keys[key],))),
                        on_read)
        else:
            mine = write_id(args.index, p, writes_of[p])
            writes_of[p] += 1
            op_values.append(mine)
            lengths.append(value_bytes)

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
        key, read = draws.next()
        # Rescheduled, not recursed: see client_main._closed_loops.
        send(p, key, read, lambda: transport.loop.call_soon(issue, p))

    for p in range(num_loops):
        transport.loop.call_soon_threadsafe(issue, p)
    done.wait(timeout=max(0.0, end - time.monotonic()) + GRACE_S)

    # Read back every key this process updated, on the loops that are
    # free (one operation per pseudonym), each loop its keys one after
    # another.
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
             value=np.frombuffer(op_values, dtype=np.int64),
             length=np.frombuffer(lengths, dtype=np.int32))
    with open(args.out + ".json", "w") as f:
        json.dump({"index": args.index, "keys": keys,
                   "load_rows": load_rows, "start_mono_s": start,
                   "end_mono_s": end,
                   "wall_minus_mono_s": wall_minus_mono_s,
                   "gave_up": counts["gave_up"],
                   "loops_stuck": sum(outstanding)}, f)


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""One role process of a benchmarked deployment.

``role_entry.py <record_dir> <trace_seconds> <cli flags...>`` is
``frankenpaxos_tpu.cli.main`` with recorders around it (the shape of
``chip_smoke.py role``, from which the tracker recorder and the timed
claim are copied). What a process records, it writes to ``record_dir``
when it exits on SIGTERM:

  every process      <label>.json: whether it claimed a device, and what
                     JAX gave it
  the chip owner     for each device tracker, the votes it was fed and the
                     quorums it reported, interleaved in arrival order, as
                     arrays in <label>.tracker<k>.npz; the tracker's own
                     counters, the board's shape, the peak device memory
  a replica          every write its state machine executed, in order, as
                     (key id, value) arrays in <label>.replica.npz, and
                     the store's final contents

Inside the measured window every recorder costs O(1) per message: one
append of a tuple, an array or a list as it arrives (a replica keeps two
references per executed write, the key and the value it was given).
Expansion and dumping happen at exit. The same recording runs with and
without a trace.

With ``trace_seconds > 0`` the chip owner also waits for SIGUSR1, then
traces the device with ``jax.profiler`` for that long, with the counters
read at both ends of the span (``<label>.trace.json``), and wraps the
tracker's ``drain()`` and ``collect()`` in ``TraceAnnotation``.

Names of the program this file holds on to: ``proxy_leader.
TpuQuorumTracker`` with ``record`` / ``record_range`` / ``record_votes``
/ ``drain`` / ``collect``, its buffers ``_slots`` / ``_ranges`` /
``_array_votes``, its counters and ``checker.board.votes`` /
``checker.window_violations``; ``device.claim_tpu``;
``statemachine.KeyValueStore.typed_run`` and ``.kvs``.
"""

from __future__ import annotations

import atexit
import contextlib
import gc
import itertools
import json
import os
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DRAIN_SPAN = "bench.drain"
COLLECT_SPAN = "bench.collect"
TRACED_SPAN = "bench.span"

TRACKER_COUNTERS = ("device_drains", "host_drains", "device_votes",
                    "host_votes", "spilled_votes")


def main(argv: list, wrap_tracker=None, wrap_store=None) -> None:
    """``wrap_tracker(cls) -> cls`` and ``wrap_store(cls) -> None`` let
    the tests under ``tests/benchmark/faults`` break the timed path
    underneath the recorders; the benchmark passes neither."""
    record_dir, trace_s, cli_argv = argv[0], float(argv[1]), argv[2:]
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import numpy as np

    from frankenpaxos_tpu import cli, device, native
    from frankenpaxos_tpu.deploy import process_label
    from frankenpaxos_tpu.protocols.multipaxos import proxy_leader
    from frankenpaxos_tpu.statemachine import impls

    label = process_label(cli_argv[cli_argv.index("--role") + 1],
                          cli_argv[cli_argv.index("--index") + 1])
    trackers: list = []
    claimed: dict = {}
    cache = {"hits": 0, "misses": 0}
    annotate = contextlib.nullcontext

    base = proxy_leader.TpuQuorumTracker
    if wrap_tracker is not None:
        base = wrap_tracker(base)

    base_record = base.record
    base_record_range = base.record_range
    base_record_votes = base.record_votes
    chain = itertools.chain.from_iterable

    class RecordingTracker(base):
        def __init__(self, *args, **kwargs):
            t0 = time.monotonic()
            super().__init__(*args, **kwargs)
            self.init_s = time.monotonic() - t0
            # In arrival order: a 5-tuple is a range of votes, a 4-tuple
            # an array of votes, an [n, 2] array what one drain or
            # collect reported (an array, so that the reported tuples die
            # as they do without a recorder).
            self.events: list = []
            self.note = self.events.append
            self.reported = 0
            trackers.append(self)

        def record(self, slot, round, group_index, acceptor_index):
            self.note((slot, slot + 1, round, group_index, acceptor_index))
            base_record(self, slot, round, group_index, acceptor_index)

        def record_range(self, slot_start, slot_end, round, group_index,
                         acceptor_index):
            self.note((slot_start, slot_end, round, group_index,
                       acceptor_index))
            base_record_range(self, slot_start, slot_end, round,
                              group_index, acceptor_index)

        def record_votes(self, slots, rounds, group_index, acceptor_index):
            self.note((slots, rounds, group_index, acceptor_index))
            base_record_votes(self, slots, rounds, group_index,
                              acceptor_index)

        def drain(self):
            if self._slots or self._ranges or self._array_votes:
                with annotate(DRAIN_SPAN):
                    out = super().drain()
            else:
                out = super().drain()
            self._keep(out)
            return out

        def _keep(self, out) -> None:
            if out:
                self.note(np.fromiter(chain(out), dtype=np.int64,
                                      count=2 * len(out)).reshape(-1, 2))
                self.reported += len(out)

        def collect(self, dispatch):
            with annotate(COLLECT_SPAN):
                out = super().collect(dispatch)
            self._keep(out)
            return out

        def counters(self) -> dict:
            out = {name: getattr(self, name) for name in TRACKER_COUNTERS}
            out["window_violations"] = self.checker.window_violations
            out["reported"] = self.reported
            return out

    proxy_leader.TpuQuorumTracker = RecordingTracker

    # A replica's executed writes, in execution order: the key and the
    # value as given, two references a write (strings, which a garbage
    # collection does not visit).
    stores: list = []
    executed_keys: list = []
    executed_values: list = []
    store_init = impls.KeyValueStore.__init__
    store_run = impls.KeyValueStore.typed_run

    def recording_init(self, *args, **kwargs):
        store_init(self, *args, **kwargs)
        stores.append(self)

    def recording_run(self, input):
        writes = getattr(input, "key_values", None)
        if writes is not None:
            for key, value in writes:
                executed_keys.append(key)
                executed_values.append(value)
        return store_run(self, input)

    impls.KeyValueStore.__init__ = recording_init
    impls.KeyValueStore.typed_run = recording_run
    if wrap_store is not None:
        # Between the replica and its recorded store: the recorder sees
        # what the store was really given.
        wrap_store(impls.KeyValueStore)

    # Seconds this process spent in garbage collections, by generation.
    gc_pause_s = [0.0, 0.0, 0.0]
    gc_started = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            gc_started[0] = time.perf_counter()
        else:
            gc_pause_s[info["generation"]] += (time.perf_counter()
                                               - gc_started[0])

    gc.callbacks.append(on_gc)

    claim_tpu = device.claim_tpu

    def timed_claim() -> dict:
        import jax.monitoring

        def on_event(event: str, **_) -> None:
            if event.endswith("/cache_hits"):
                cache["hits"] += 1
            elif event.endswith("/cache_misses"):
                cache["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        t0 = time.monotonic()
        claimed["device"] = claim_tpu()
        claimed["claim_s"] = time.monotonic() - t0
        return claimed["device"]

    device.claim_tpu = timed_claim

    def snapshot() -> dict:
        import prometheus_client

        from frankenpaxos_tpu.bench.metrics import parse_exposition

        # A label for a reader of the file; nothing compares it.
        return {"unix_s": time.time(),
                "metrics": parse_exposition(
                    prometheus_client.generate_latest().decode()),
                "trackers": [t.counters() for t in trackers]}

    trace_wanted = threading.Event()
    trace_dir = os.path.join(record_dir, f"{label}.trace")

    def trace_when_asked() -> None:
        trace_wanted.wait()
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        t0 = time.monotonic()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        start_trace_s = time.monotonic() - t0
        with jax.profiler.TraceAnnotation(TRACED_SPAN):
            before = snapshot()
            time.sleep(trace_s)
            after = snapshot()
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        meta = {"before": before, "after": after,
                "start_trace_s": start_trace_s,
                "stop_trace_s": time.monotonic() - t0}
        with open(os.path.join(record_dir, f"{label}.trace.json.tmp"),
                  "w") as f:
            json.dump(meta, f)
        os.replace(os.path.join(record_dir, f"{label}.trace.json.tmp"),
                   os.path.join(record_dir, f"{label}.trace.json"))

    if trace_s > 0:
        import jax.profiler

        annotate = jax.profiler.TraceAnnotation
        signal.signal(signal.SIGUSR1, lambda *_: trace_wanted.set())
        threading.Thread(target=trace_when_asked, daemon=True,
                         name="bench-trace").start()

    def dump() -> None:
        record = {"label": label, "claimed": bool(claimed), **claimed,
                  "cache": cache, "gc_pause_s": gc_pause_s,
                  "gc_collections": [g["collections"]
                                     for g in gc.get_stats()],
                  "trackers": [], "stores": []}
        if claimed:
            import jax

            stats = jax.devices()[0].memory_stats() or {}
            record["memory_peak_bytes"] = int(
                stats.get("peak_bytes_in_use", 0))
            record["native"] = native.load() is not None
        for k, tracker in enumerate(trackers):
            votes, reports = expand(np, tracker.events)
            np.savez(os.path.join(record_dir, f"{label}.tracker{k}.npz"),
                     votes=votes, reports=reports)
            record["trackers"].append({
                "init_s": tracker.init_s,
                "board_shape": list(tracker.checker.board.votes.shape),
                **tracker.counters()})
        if stores:
            names = sorted(set(executed_keys))
            ids = {name: n for n, name in enumerate(names)}
            np.savez(os.path.join(record_dir, f"{label}.replica.npz"),
                     keys=np.fromiter((ids[k] for k in executed_keys),
                                      dtype=np.int32,
                                      count=len(executed_keys)),
                     values=np.array(executed_values, dtype="S"))
            record["key_names"] = names
            record["stores"] = [dict(store.kvs) for store in stores]
        with open(os.path.join(record_dir, f"{label}.json"), "w") as f:
            json.dump(record, f)

    atexit.register(dump)
    cli.main(cli_argv)


def expand(np, events: list) -> tuple:
    """``events`` as two int64 arrays: votes ``[n, 6]`` of (sequence
    number, first slot, end slot, round, group, index), one row per range
    or per vote of an array, and reports ``[m, 3]`` of (sequence number,
    slot, round). The sequence number is the event's place in arrival
    order."""
    ranges, arrays, reports = [], [], []
    for seq, event in enumerate(events):
        if not isinstance(event, tuple):
            block = np.empty((len(event), 3), dtype=np.int64)
            block[:, 0] = seq
            block[:, 1:] = event
            reports.append(block)
        elif len(event) == 5:
            ranges.append((seq, *event))
        else:
            slots, rounds, group, index = event
            block = np.empty((len(slots), 6), dtype=np.int64)
            block[:, 0] = seq
            block[:, 1] = slots
            block[:, 2] = block[:, 1] + 1
            block[:, 3] = rounds
            block[:, 4] = group
            block[:, 5] = index
            arrays.append(block)
    votes = np.concatenate(
        [np.asarray(ranges, dtype=np.int64).reshape(-1, 6), *arrays])
    votes = votes[np.argsort(votes[:, 0], kind="stable")]
    reported = (np.concatenate(reports) if reports
                else np.empty((0, 3), dtype=np.int64))
    return votes, reported


if __name__ == "__main__":
    main(sys.argv[1:])

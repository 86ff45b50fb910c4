#!/usr/bin/env python3
"""The quickest proof that the served MultiPaxos path runs on the chip.

``python3 chip_smoke.py`` runs three stages one after another, each in a
child process, and prints as its last line exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it; the line before it is a JSON summary of
what each stage found. It exits non-zero, and
prints no result, if JAX finds no TPU, if any stage fails a check, or if
any stage raises. This parent never touches JAX: one chip serves one
process at a time, so each stage's chip owner is reaped before the next
stage starts.

  B  served            BASELINE.json config 1 deployed through cli.py /
                       launch_roles over real sockets: f=1, 3 acceptors,
                       2 leaders, 2 proxy leaders, 2 replicas, KV store,
                       quorum_backend=tpu (the 2^20-slot vote board on
                       the device behind requests), coalesced run
                       pipeline; 4096 closed write loops from CPU-pinned
                       client_main processes. The launcher and every
                       role but the one hosting the proxy leaders stay
                       off the chip.
  C  kernels           TpuQuorumChecker at window 2^20 (majority and the
                       2x3 grid) across a ring wrap plus a sparse tail,
                       bit-identical to a host oracle; then the donated
                       bench/pipeline.py loop.
  D  mesh              (>= 4 devices only) the tracker's board and the
                       sharded pipeline over a 1x4 mesh, with every
                       device holding its quarter.

(Stage A was the tracker's synchronous mode and went with it; the
letters stay as the records use them.) Stage B passes only if every
write is acknowledged and read back, the tracker's drains launched
kernels (its own counters, read from /metrics), the board is resident at
the window with no vote outside it, and the chosen (slot, round) set
equals what a DictQuorumTracker reports for the same votes. To see those
votes the role processes start through ``chip_smoke.py role``, which is
cli.main with the tracker class wrapped by a recorder.

The stage functions take their sizes as arguments; tests/test_chip_smoke.py
runs them at toy size under JAX_PLATFORMS=cpu.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

#: Deployment sizes (BASELINE.json: 1M in-flight slots; BASELINE.md:
#: the reference's client scale is up to 20 procs x 200 clients).
WINDOW = 1 << 20
FULL = {
    "served": dict(client_procs=4, loops_per_proc=1024, duration_s=4.0,
                   window=WINDOW),
    "kernels": dict(window=WINDOW, dense_width=4096, sparse_votes=16384,
                    pipeline_iters=256, pipeline_block=1 << 15),
    "mesh": dict(window=WINDOW, dense_width=4096, pipeline_iters=256,
                 pipeline_block=1 << 15),
}
STAGE_TIMEOUT_S = 420.0


# --------------------------------------------------------------------------
# Stage B: the served path
# --------------------------------------------------------------------------


def stage_served(workdir: str, *, client_procs: int, loops_per_proc: int,
                 duration_s: float, window: int) -> dict:
    """Deploy config 1 with ``quorum_backend=tpu``, load it, check it."""
    from frankenpaxos_tpu import device

    device.pin_cpu()  # the launcher stays off the chip

    from frankenpaxos_tpu.bench.deploy_suite import role_process_env
    from frankenpaxos_tpu.bench.harness import BenchmarkDirectory, LocalHost
    from frankenpaxos_tpu.bench.metrics import scrape
    from frankenpaxos_tpu.bench.multipaxos_suite import (
        launch_with_retry,
        MultiPaxosInput,
    )
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
    )

    shutil.rmtree(workdir, ignore_errors=True)  # an earlier run's records
    bench = BenchmarkDirectory(workdir)
    audit_dir = bench.abspath("audit")
    os.makedirs(audit_dir, exist_ok=True)
    input = MultiPaxosInput(
        f=1, num_replicas=2, quorum_backend="tpu", coalesced=True,
        state_machine="KeyValueStore", prometheus=True)
    launch_overrides = {"tpu_window": str(window)}
    cache_before = _cache_entries()
    t0 = time.time()
    config_path, config = launch_with_retry(
        bench, input, entry=(os.path.abspath(__file__), "role", audit_dir),
        extra_overrides=launch_overrides)
    launch_s = time.time() - t0
    try:
        pinned = all(env.get("JAX_PLATFORMS") == "cpu"
                     for label, (_, env) in bench.role_commands.items()
                     if label != bench.chip_owner)
        clients = []
        for i in range(client_procs):
            verdict = bench.abspath(f"client_{i}_verdict.json")
            clients.append((verdict, bench.popen(
                LocalHost(), f"client_{i}",
                [sys.executable, "-m", "frankenpaxos_tpu.bench.client_main",
                 "--config", config_path, "--readback", verdict,
                 "--out", bench.abspath(f"client_{i}_data.csv"),
                 "--num_clients", str(loops_per_proc),
                 "--duration", str(duration_s), "--seed", str(i),
                 "--client_options",
                 json.dumps({"coalesce_writes": "true"})],
                env=role_process_env())))
        verdicts = []
        for verdict, proc in clients:
            code = proc.wait(timeout=duration_s + 150)
            if code != 0:
                raise RuntimeError(f"client exited with code {code}; "
                                   f"see {bench.path}")
            with open(verdict) as f:
                verdicts.append(json.load(f))
        # Every write was acknowledged, so every quorum has been
        # collected and reported already; the pause only lets the last
        # drain's /metrics update land.
        time.sleep(0.5)
        scrapes = {label: scrape(port)
                   for label, port in bench.prometheus_ports.items()}
    finally:
        bench.cleanup()  # SIGTERM and reap: the recorders dump at exit

    audits = []
    for name in sorted(os.listdir(audit_dir)):
        with open(os.path.join(audit_dir, name)) as f:
            audits.append(json.load(f))
    owner = audits[0] if len(audits) == 1 else {
        "device": None, "trackers": [], "native": False, "label": None}
    trackers = owner["trackers"]

    # The oracle: the reference's per-(slot, round) vote sets, fed the
    # votes each device tracker was fed, in the same order.
    chosen = 0
    oracle_equal = bool(trackers)
    for tracker in trackers:
        oracle = DictQuorumTracker(config)
        for start, end, rnd, group, index in tracker["votes"]:
            for slot in range(start, end):
                oracle.record(slot, rnd, group, index)
        got = [tuple(key) for key in tracker["chosen"]]
        chosen += len(got)
        oracle_equal &= (len(got) == len(set(got))
                         and set(got) == set(oracle.drain()))

    def metric(series: str) -> int:
        return int(scrapes[bench.chip_owner].get(
            f"multipaxos_proxy_leader_tpu_{series}", 0))

    def tracked(field: str) -> int:
        return sum(t[field] for t in trackers)

    counts = {"device_drains": metric('drains_total{path="device"}'),
              "device_votes": metric('votes_total{path="device"}'),
              "device_launches": metric("launches_total")}
    acked = sum(v["writes_acked"] for v in verdicts)
    checks = {
        "one_chip_owner": (bench.chip_owner is not None and pinned
                           and len(audits) == 1
                           and owner["label"] == bench.chip_owner),
        "native_codec_loaded": owner["native"],
        "every_write_acked": (acked > 0 and all(
            not v["unacked"] and v["writes_acked"] == v["writes_issued"]
            for v in verdicts)),
        "every_key_read_back": all(
            not v["mismatched"] and v["keys_read_back"] == v["keys"]
            for v in verdicts),
        "metrics_scraped": all(scrapes.values()),
        # The tracker's counts and what /metrics served agree.
        "metrics_match_tracker": all(
            counts[key] == tracked(key) for key in counts),
        # Every drain launched a kernel, and every fed vote went in one.
        "every_drain_launched": (
            counts["device_launches"] >= counts["device_drains"] > 0
            and counts["device_votes"] == sum(
                end - start for t in trackers
                for start, end, *_ in t["votes"])),
        "chosen_equals_oracle": oracle_equal and chosen > 0,
        "board_resident_at_window": all(
            t["board_shape"] == [3, window] for t in trackers),
        "no_window_violations": tracked("window_violations") == 0,
    }
    return {
        "stage": "served",
        "device": owner["device"],
        "chip_owner": bench.chip_owner,
        "processes": len(bench.role_commands) + client_procs,
        "set_up_s": {"launch_to_ready": round(launch_s, 2),
                     "claim_tpu": owner.get("claim_s"),
                     "tracker_prewarm": [t["init_s"] for t in trackers]},
        "compile_cache": {**owner.get("cache", {}),
                          "entries_before": cache_before,
                          "entries_after": _cache_entries()},
        "closed_loops": client_procs * loops_per_proc,
        "writes_acked": acked,
        "keys_read_back": sum(v["keys_read_back"] for v in verdicts),
        **counts,
        "chosen": chosen,
        "checks": checks,
    }


def audited_role(audit_dir: str, cli_argv: list) -> None:
    """A role process exactly as ``python -m frankenpaxos_tpu.cli`` starts
    it, except that a device tracker it builds also records the votes it
    is fed and the quorums it reports. A process that built one writes
    the record to ``audit_dir`` when it exits; the others write nothing."""
    import atexit

    from frankenpaxos_tpu import cli, device, native
    from frankenpaxos_tpu.deploy import process_label
    from frankenpaxos_tpu.protocols.multipaxos import proxy_leader

    trackers: list = []
    claimed: dict = {}
    cache = {"hits": 0, "misses": 0}

    class RecordingTracker(proxy_leader.TpuQuorumTracker):
        def __init__(self, *args, **kwargs):
            t0 = time.time()
            super().__init__(*args, **kwargs)
            self.init_s = round(time.time() - t0, 2)
            self.fed: list = []      # (start, end, round, group, index)
            self.reported: list = []
            trackers.append(self)

        def record(self, slot, round, group_index, acceptor_index):
            self.fed.append((slot, slot + 1, round, group_index,
                             acceptor_index))
            super().record(slot, round, group_index, acceptor_index)

        def record_range(self, slot_start, slot_end, round, group_index,
                         acceptor_index):
            self.fed.append((slot_start, slot_end, round, group_index,
                             acceptor_index))
            super().record_range(slot_start, slot_end, round, group_index,
                                 acceptor_index)

        def record_votes(self, slots, rounds, group_index, acceptor_index):
            self.fed.extend(
                (int(s), int(s) + 1, int(r), group_index, acceptor_index)
                for s, r in zip(slots, rounds))
            super().record_votes(slots, rounds, group_index,
                                 acceptor_index)

        def drain(self):
            out = super().drain()
            self.reported.extend(out)
            return out

        def collect(self, dispatch):
            out = super().collect(dispatch)
            self.reported.extend(out)
            return out

    proxy_leader.TpuQuorumTracker = RecordingTracker

    claim_tpu = device.claim_tpu

    def timed_claim() -> dict:
        import jax.monitoring

        def on_event(event: str, **_) -> None:
            if event.endswith("/cache_hits"):
                cache["hits"] += 1
            elif event.endswith("/cache_misses"):
                cache["misses"] += 1

        jax.monitoring.register_event_listener(on_event)
        t0 = time.time()
        claimed["device"] = claim_tpu()
        claimed["claim_s"] = round(time.time() - t0, 2)
        return claimed["device"]

    device.claim_tpu = timed_claim

    def dump() -> None:
        if not trackers:
            return
        record = {
            "label": process_label(
                cli_argv[cli_argv.index("--role") + 1],
                cli_argv[cli_argv.index("--index") + 1]),
            **claimed,
            "native": native.load() is not None,
            "cache": cache,
            "trackers": [{
                "init_s": t.init_s,
                "board_shape": list(t.checker.board.votes.shape),
                "window_violations": t.checker.window_violations,
                "device_drains": t.device_drains,
                "device_votes": t.device_votes,
                "device_launches": t.device_launches,
                "votes": t.fed,
                "chosen": t.reported,
            } for t in trackers],
        }
        with open(os.path.join(audit_dir, f"{os.getpid()}.json"),
                  "w") as f:
            json.dump(record, f)

    atexit.register(dump)
    cli.main(cli_argv)


# --------------------------------------------------------------------------
# Stage C: the kernels at deployment size, in one process
# --------------------------------------------------------------------------


class HostBoard:
    """The reference semantics with no ring and no kernels: every slot
    keeps its own vote set for the whole run (numpy), and a slot is
    newly chosen the first time ``QuorumSpec.evaluate`` holds for it."""

    def __init__(self, spec, num_slots: int):
        import numpy as np

        self.spec = spec
        self.votes = np.zeros((num_slots, spec.num_nodes), np.uint8)
        self.chosen = np.zeros(num_slots, bool)

    def record_block(self, start: int, block):
        span = slice(start, start + block.shape[1])
        self.votes[span] |= block.T
        hit = self.spec.evaluate(self.votes[span])
        newly = hit & ~self.chosen[span]
        self.chosen[span] |= hit
        return newly

    def record_and_check(self, slots, cols):
        before = self.chosen[slots]
        self.votes[slots, cols] = 1
        hit = self.spec.evaluate(self.votes[slots])
        self.chosen[slots] |= hit
        return hit & ~before


def check_checker(spec, *, window: int, dense_width: int,
                  sparse_votes: int, seed: int) -> dict:
    """Drive one ``TpuQuorumChecker`` through 1.25 rings of dense blocks
    (each slot voted on in two passes, so quorums straddle calls) and a
    sparse scatter tail; every returned mask must equal the host's."""
    import warnings

    import numpy as np

    from frankenpaxos_tpu.ops.quorum import TpuQuorumChecker

    rng = np.random.default_rng(seed)
    n = spec.num_nodes
    num_slots = window + window // 4
    host = HostBoard(spec, num_slots)
    t0 = time.time()
    checker = TpuQuorumChecker(spec, window=window)
    identical = True
    chosen = 0
    call_s = []
    for start in range(0, num_slots, dense_width):
        for _ in range(2):
            block = (rng.random((n, dense_width)) < 0.6).astype(np.uint8)
            t1 = time.time()
            got = checker.record_block(start, block)
            call_s.append(time.time() - t1)
            identical &= bool(np.array_equal(
                got, host.record_block(start, block)))
            chosen += int(got.sum())
    # Sparse tail over slots the ring still holds, duplicates included.
    sparse_s = []
    for _ in range(sparse_votes // 256):
        slots = rng.integers(num_slots - window // 4, num_slots, 256)
        cols = rng.integers(0, n, 256)
        t1 = time.time()
        got = checker.record_and_check(slots, cols)
        sparse_s.append(time.time() - t1)
        identical &= bool(np.array_equal(
            got, host.record_and_check(slots, cols)))
        chosen += int(np.unique(slots[got]).size)
    # A vote for a slot the ring has moved past must be dropped.
    # (One whose column a second-ring slot has in fact claimed.)
    stale = int(np.flatnonzero(
        ~host.chosen[:window // 4]
        & host.votes[window:window + window // 4].any(axis=1))[0])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # its own alarm
        dropped = not checker.record_and_check(
            [stale] * n, list(range(n))).any()
    return {
        "nodes": n, "slots": num_slots, "chosen": chosen,
        "bit_identical": identical,
        "stale_vote_dropped": dropped and checker.window_violations == 1,
        "host_chosen": int(host.chosen.sum()),
        "dense_first_call_s": round(call_s[0], 3),
        "dense_median_call_s": round(float(np.median(call_s)), 6),
        "sparse_first_call_s": round(sparse_s[0], 3),
        "sparse_median_call_s": round(float(np.median(sparse_s)), 6),
        "total_s": round(time.time() - t0, 2),
    }


def run_pipeline(*, window: int, iters: int, block: int) -> dict:
    """``bench/pipeline.py::run_steps`` with donation: twice, so the
    first call's compile shows beside the second call's run."""
    import jax

    from frankenpaxos_tpu.bench.pipeline import make_state, run_steps
    from frankenpaxos_tpu.quorums import SimpleMajority

    masks, thresholds, combine_any = SimpleMajority(
        range(3)).write_spec().as_arrays()
    masks_t = tuple(tuple(int(x) for x in row) for row in masks)
    thresholds_t = tuple(int(t) for t in thresholds)
    seconds, committed = [], []
    for _ in range(2):
        state = make_state(window, 3)
        jax.block_until_ready(state.votes)
        t0 = time.time()
        state = run_steps(state, iters, block, masks_t, thresholds_t,
                          combine_any)
        committed.append(int(state.committed))
        seconds.append(round(time.time() - t0, 3))
    return {
        "iters": iters, "block": block, "committed": committed[1],
        "first_call_s": seconds[0], "second_call_s": seconds[1],
        # bench.py's own tolerance: the last block's stragglers.
        "committed_as_expected": (
            committed[0] == committed[1]
            and abs(committed[1] - iters * block) <= 2 * block),
    }


def stage_kernels(*, window: int, dense_width: int, sparse_votes: int,
                  pipeline_iters: int, pipeline_block: int) -> dict:
    from frankenpaxos_tpu import device
    from frankenpaxos_tpu.quorums import Grid, SimpleMajority

    majority = check_checker(
        SimpleMajority(range(3)).write_spec(), window=window,
        dense_width=dense_width, sparse_votes=sparse_votes, seed=1)
    grid = check_checker(
        Grid([[0, 1, 2], [3, 4, 5]]).write_spec(), window=window,
        dense_width=dense_width, sparse_votes=sparse_votes, seed=2)
    pipeline = run_pipeline(window=window, iters=pipeline_iters,
                            block=pipeline_block)
    return {
        "stage": "kernels",
        "device": device.describe_devices(),
        "majority": majority, "grid": grid, "pipeline": pipeline,
        "compile_cache": {"entries_after": _cache_entries()},
        "checks": {
            "majority_bit_identical": (majority["bit_identical"]
                                       and majority["chosen"] > 0),
            "grid_bit_identical": (grid["bit_identical"]
                                   and grid["chosen"] > 0),
            "stale_votes_dropped": (majority["stale_vote_dropped"]
                                    and grid["stale_vote_dropped"]),
            "pipeline_committed": pipeline["committed_as_expected"],
        },
    }


# --------------------------------------------------------------------------
# Stage D: four chips
# --------------------------------------------------------------------------


def _quarters(array, devices) -> bool:
    """Does every device hold exactly its 1/len(devices) of the array's
    last axis?"""
    shards = array.addressable_shards
    width = array.shape[-1] // len(devices)
    return (len(shards) == len(devices)
            and {s.device for s in shards} == set(devices)
            and all(s.data.shape[-1] == width for s in shards))


def stage_mesh(*, window: int, dense_width: int, pipeline_iters: int,
               pipeline_block: int, num_devices: int = 4) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    import numpy as np

    from frankenpaxos_tpu import device
    from frankenpaxos_tpu.bench.pipeline import (
        make_sharded_runner,
        make_sharded_state,
    )
    from frankenpaxos_tpu.deploy import get_protocol
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )
    from frankenpaxos_tpu.quorums import SimpleMajority

    devices = jax.devices()[:num_devices]
    mesh = Mesh(np.array(devices).reshape(1, num_devices),
                ("group", "slot"))
    multipaxos = get_protocol("multipaxos")
    ports = iter(range(20000, 21000))
    config = multipaxos.load_config(
        multipaxos.cluster(1, lambda: ["127.0.0.1", next(ports)]))

    # The served tracker with its board over the mesh, against the
    # oracle, through 1.25 rings (as __graft_entry__ does at toy size).
    t0 = time.time()
    tracker = TpuQuorumTracker(config, window=window, mesh=mesh)
    prewarm_s = time.time() - t0
    oracle = DictQuorumTracker(config)
    equal, chosen = True, 0
    for base in range(0, window + window // 4, dense_width):
        for acceptor in (base % 3, (base + 1) % 3):
            tracker.record_range(base, base + dense_width, 0, 0, acceptor)
            oracle.record_range(base, base + dense_width, 0, 0, acceptor)
        tracker.drain()
        got = []
        while (dispatch := tracker.take_dispatch()) is not None:
            got.extend(tracker.collect(dispatch))
        equal &= sorted(got) == sorted(oracle.drain())
        chosen += len(got)
    board_placed = _quarters(tracker.checker.board.votes, devices)

    masks, thresholds, combine_any = SimpleMajority(
        range(3)).write_spec().as_arrays()
    runner, _ = make_sharded_runner(
        mesh, block_size=pipeline_block, masks=masks,
        thresholds=thresholds, combine_any=combine_any,
        iters=pipeline_iters)
    state, _, _ = make_sharded_state(mesh, window, pipeline_block, 3)
    t0 = time.time()
    state = runner(state, jnp.int32(0))
    committed = int(state.committed)
    runner_s = time.time() - t0
    return {
        "stage": "mesh",
        "device": device.describe_devices(),
        "mesh": {"group": 1, "slot": num_devices},
        "tracker_prewarm_s": round(prewarm_s, 2),
        "tracker_chosen": chosen,
        "device_drains": tracker.device_drains,
        "device_launches": tracker.device_launches,
        "runner_first_call_s": round(runner_s, 2),
        "runner_committed": committed,
        "checks": {
            "tracker_equals_oracle": (equal
                                      and chosen == window + window // 4),
            "no_window_violations": (
                tracker.checker.window_violations == 0),
            "board_quarter_per_device": board_placed,
            "pipeline_quarter_per_device": _quarters(state.votes,
                                                     devices),
            "pipeline_committed": (
                abs(committed - pipeline_iters * pipeline_block)
                <= 2 * pipeline_block),
        },
    }


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------


def _cache_entries() -> int:
    """Executables in the persistent compile cache, counted without
    importing JAX."""
    from frankenpaxos_tpu.device import COMPILE_CACHE_DIR

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or COMPILE_CACHE_DIR
    try:
        return sum(not name.endswith("-atime") for name in os.listdir(path))
    except FileNotFoundError:
        return 0


def run_stage(name: str, out_path: str) -> None:
    """One stage in THIS process, at full size, on the chip."""
    if name.startswith("served"):
        workdir = os.path.join(os.path.dirname(out_path), name)
        result = stage_served(workdir, **FULL[name])
    else:
        from frankenpaxos_tpu import device

        t0 = time.time()
        found = device.claim_tpu()
        claim_s = round(time.time() - t0, 2)
        if name == "mesh" and found["count"] < 4:
            result = {"stage": name, "device": found, "skipped":
                      f"needs 4 devices, found {found['count']}",
                      "checks": {}}
        else:
            result = {"mesh": stage_mesh,
                      "kernels": stage_kernels}[name](**FULL[name])
        result["claim_tpu_s"] = claim_s
    result["ok"] = (all(result["checks"].values())
                    and (result["device"] or {}).get("platform") == "tpu")
    print(json.dumps(result, indent=1), flush=True)
    with open(out_path, "w") as f:
        json.dump(result, f)
    if not result["ok"]:
        raise SystemExit(f"stage {name} FAILED: checks={result['checks']} "
                         f"device={result['device']}")


def result_line(device: dict) -> str:
    """The last line of stdout: this object and no other key. What the
    stages found goes on the line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main() -> None:
    if not os.path.isdir(os.path.join(REPO, "frankenpaxos_tpu")):
        raise SystemExit("chip_smoke.py drives the frankenpaxos_tpu "
                         "package beside it, and there is none")
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        raise SystemExit("JAX_PLATFORMS=cpu: JAX will find no TPU, and "
                         "chip_smoke.py checks nothing without one")
    out_dir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    started = time.time()
    stages = {}
    for name in ("served", "kernels", "mesh"):
        out_path = os.path.join(out_dir, f"{name}.json")
        t0 = time.time()
        # Its own session, so that whatever a stage leaves behind can
        # be stopped with it.
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "stage", name,
             out_path], cwd=REPO, start_new_session=True)
        try:
            code = child.wait(timeout=STAGE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            code = "timeout"
        finally:
            try:
                os.killpg(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass  # the usual case: the stage stopped all it started
            child.wait()
        if code != 0:
            raise SystemExit(f"stage {name} failed ({code}) after "
                             f"{time.time() - t0:.0f}s")
        with open(out_path) as f:
            stages[name] = json.load(f)
        stages[name]["stage_s"] = round(time.time() - t0, 1)
    device = stages["kernels"]["device"]
    print(json.dumps({
        "total_s": round(time.time() - started, 1),
        "stages": {name: {k: v for k, v in stage.items()
                          if k in ("stage_s", "checks", "skipped",
                                   "set_up_s", "compile_cache",
                                   "writes_acked", "keys_read_back",
                                   "device_drains", "device_votes",
                                   "device_launches", "chosen")}
                   for name, stage in stages.items()},
        "claim": None,
    }))
    print(result_line(device), flush=True)


if __name__ == "__main__":
    if sys.path[0] != REPO:
        sys.path.insert(0, REPO)
    if sys.argv[1:2] == ["stage"]:
        run_stage(sys.argv[2], sys.argv[3])
    elif sys.argv[1:2] == ["role"]:
        audited_role(sys.argv[2], sys.argv[3:])
    else:
        main()

"""Latency-throughput sweep: the real actor framework, dict vs tpu.

The analog of the reference's LT-curve methodology
(benchmarks/multipaxos/multipaxos.py:292-785 + e1_lt_surprise.py):
sweep offered load (client processes x closed loops) over the deployed
multipaxos cluster and record throughput/latency per point, for both
quorum backends:

  * ``dict``  -- host-dict vote tracking in the proxy leader (the
    reference's semantics; CPU-pinned role processes).
  * ``tpu``   -- the proxy leader's Phase2b votes collected on the
    accelerator via TpuQuorumTracker (dense record_block runs + sparse
    scatter tail on the vote board), one device call per event-loop
    drain as a rule, collected off the loop.

Also runs SimTransport comparisons (no TCP, same actor code) isolating
the per-drain tracker cost from network effects.

One chip serves one process, so this parent never touches JAX: the
deployed arms' chip owner is the role process launch_roles leaves
unpinned, and every sim/tracker comparison runs in a child of its own,
one at a time, on whatever platform JAX finds there -- which each
child reports and the result records beside its numbers.

Usage::

    python -m frankenpaxos_tpu.bench.lt_suite --out multipaxos_lt.json
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

from frankenpaxos_tpu.bench.harness import SuiteDirectory
from frankenpaxos_tpu.bench.multipaxos_suite import (
    MultiPaxosInput,
    run_benchmark,
)


def sim_transport_cmds_per_sec(quorum_backend: str,
                               num_commands: int = 300,
                               inflight: int = 1) -> float:
    """Drive the full actor pipeline over SimTransport (single process,
    no TCP): client -> leader -> proxy leader -> acceptors -> replicas,
    with the chosen quorum backend.

    ``inflight`` closed loops (client pseudonyms) issue concurrently and
    messages deliver in coalesced waves -- the real event loop's drain
    granularity (TcpTransport defers on_drain to the end of a loop
    pass), so a proxy leader drain carries ~inflight * (f+1) votes. At
    inflight=1 this degenerates to the serial one-command-per-drain
    workload, the device path's worst case.

    Both backends run with jax initialized and a warm XLA client:
    merely having the XLA runtime resident (its thread pool + heap)
    costs the whole actor pipeline ~10% on a single-CPU host, measured
    identically for a dict-backend run with an idle checker. Holding
    that state constant isolates what this sweep is after: the
    incremental cost of HOW votes are tracked, dict ops vs device
    kernels."""
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    import numpy as _np

    from frankenpaxos_tpu.ops.quorum import TpuQuorumChecker
    from frankenpaxos_tpu.quorums import SimpleMajority

    warm_checker = TpuQuorumChecker(
        SimpleMajority(range(6)).write_spec(), window=1 << 12)
    warm_block = _np.zeros((6, 64), dtype=_np.uint8)
    warm_block[0, 0] = 1
    warm_checker.record_block(0, warm_block)

    from tests.protocols.multipaxos_harness import (
        deliver_and_flush,
        make_multipaxos,
    )

    sim = make_multipaxos(f=1, quorum_backend=quorum_backend)
    results = []
    # Warm up (the tracker's kernels compile in its constructor).
    sim.clients[0].write(0, b"warmup", results.append)
    deliver_and_flush(sim, coalesced=True)
    assert len(results) == 1
    batches = max(1, num_commands // inflight)
    t0 = time.perf_counter()
    _drive_waves(sim, inflight, batches, b"w", results)
    elapsed = time.perf_counter() - t0
    assert len(results) == batches * inflight + 1
    return batches * inflight / elapsed


def _drive_waves(sim, inflight: int, waves: int, tag: bytes,
                 results: list) -> None:
    """Issue ``waves`` closed-loop waves of ``inflight`` writes each and
    deliver them in coalesced waves (the real event loop's drain
    granularity). Shared by every sim-pipeline benchmark here so the
    driving protocol cannot drift between them. ``flush_writes`` ships
    a coalescing client's staged array (no-op otherwise), standing in
    for the real event loop's end-of-pass flush; the proxy leaders'
    flush timers stand in for the collector thread."""
    from tests.protocols.multipaxos_harness import deliver_and_flush

    for b in range(waves):
        for p in range(inflight):
            sim.clients[0].write(p, b"%s%d.%d" % (tag, b, p),
                                 results.append)
        sim.clients[0].flush_writes()
        deliver_and_flush(sim, coalesced=True)


def sim_ab_pipeline(inflights, reps: int = 6, waves: int = 0,
                    warm: int = 4) -> dict:
    """Interleaved A/B/C of the full SimTransport actor pipeline in ONE
    process with XLA resident throughout:

      * ``dict``     -- the reference design: per-message Python
        (ClientRequest/Phase2a/Phase2b/Chosen per slot), host-dict vote
        tracking. The baseline.
      * ``tpu``      -- the tpu-first design: the drain-granular run
        pipeline (ClientRequestArray -> Phase2aRun -> Phase2bRange ->
        ChosenRun -> ClientReplyArray; per-message Python scales with
        drains, not commands) with the device-backed quorum tracker.
      * ``dict+run`` -- ablation: the same run pipeline over the
        host-dict tracker, isolating how much of tpu-vs-dict comes
        from drain-granular message structure vs device vote tracking.

    Per in-flight width: ``reps`` triples of runs with rotating order,
    each yielding per-pair ratios; the MEDIAN of paired ratios is
    robust to the two confounds that made cross-process comparisons
    jitter +-30% on this 1-CPU host: process-to-process variance and
    the monotonic in-process slowdown drift."""
    import gc
    import statistics

    from tests.protocols.multipaxos_harness import (
        deliver_and_flush,
        make_multipaxos,
    )

    ARMS = {
        "dict": dict(quorum_backend="dict", coalesced=False),
        "tpu": dict(quorum_backend="tpu", coalesced=True),
        "dict+run": dict(quorum_backend="dict", coalesced=True),
    }

    def measure(arm: str, inflight: int, w: int) -> float:
        gc.collect()
        sim = make_multipaxos(f=1, **ARMS[arm])
        results = []
        sim.clients[0].write(0, b"warmup", results.append)
        sim.clients[0].flush_writes()
        deliver_and_flush(sim, coalesced=True)
        _drive_waves(sim, inflight, warm, b"w", results)
        t0 = time.perf_counter()
        _drive_waves(sim, inflight, w, b"x", results)
        elapsed = time.perf_counter() - t0
        assert len(results) == 1 + (warm + w) * inflight
        return w * inflight / elapsed

    measure("tpu", 16, 4)  # XLA + tracker kernels resident before timing
    order = ["dict", "tpu", "dict+run"]
    table = {}
    for inflight in inflights:
        # Enough waves that per-run noise stays small at narrow
        # widths; wide widths carry plenty of commands per wave, so
        # fewer waves keep a run to seconds.
        w = waves or max(12 if inflight >= 2048 else 24,
                         2048 // inflight)
        runs: dict[str, list] = {arm: [] for arm in ARMS}
        ratios: dict[str, list] = {"tpu_over_dict": [],
                                   "run_over_dict": [],
                                   "tpu_over_run": []}
        for rep in range(reps):
            rot = order[rep % 3:] + order[:rep % 3]
            got = {arm: measure(arm, inflight, w) for arm in rot}
            for arm in ARMS:
                runs[arm].append(got[arm])
            ratios["tpu_over_dict"].append(got["tpu"] / got["dict"])
            ratios["run_over_dict"].append(got["dict+run"] / got["dict"])
            ratios["tpu_over_run"].append(got["tpu"] / got["dict+run"])
        table[str(inflight)] = {
            "dict_cmds_per_sec": round(statistics.median(runs["dict"]), 1),
            "tpu_cmds_per_sec": round(statistics.median(runs["tpu"]), 1),
            "dict_run_cmds_per_sec": round(
                statistics.median(runs["dict+run"]), 1),
            "tpu_over_dict_ratio": round(
                statistics.median(ratios["tpu_over_dict"]), 3),
            "run_over_dict_ratio": round(
                statistics.median(ratios["run_over_dict"]), 3),
            "tpu_over_run_ratio": round(
                statistics.median(ratios["tpu_over_run"]), 3),
        }
    return table


def run_in_child(call: str) -> dict:
    """Evaluate ``call`` (an expression over this module's names) in a
    child process that takes the chip (device.claim_tpu: a TPU, or an
    explicit JAX_PLATFORMS=cpu), and return ``{"value": ..., "device":
    ...}`` with the device it ran on. A failure raises."""
    import subprocess
    import sys

    run = subprocess.run(
        [sys.executable, "-c",
         "import json\n"
         "from frankenpaxos_tpu.device import claim_tpu\n"
         "device = claim_tpu()\n"
         "from frankenpaxos_tpu.bench.lt_suite import *\n"
         f"value = {call}\n"
         "print(json.dumps({'value': value, 'device': device}))"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))))
    if run.returncode != 0:
        raise RuntimeError(f"{call} failed (rc={run.returncode}): "
                           f"{run.stderr[-500:]}")
    return json.loads(run.stdout.strip().splitlines()[-1])


def tracker_votes_per_sec(quorum_backend: str, drain_width: int,
                          num_votes: int = 200_000,
                          ranged: bool = False) -> float:
    """Replay an identical synthetic steady-state Phase2b stream into
    one QuorumTracker: contiguous slot runs of ``drain_width`` slots,
    2f+1 votes per slot, one drain per run -- the ProxyLeader hot loop
    (ProxyLeader.scala:217-258) with the actor pipeline stripped away.

    ``ranged=False`` delivers per-slot votes (the reference's Phase2b
    shape); ``ranged=True`` delivers one Phase2bRange per acceptor per
    drain (the framework's batched-ack shape) -- O(1) Python into the
    device tracker, per-slot expansion in the dict oracle.

    This isolates the exact component the backends differ in: per-vote
    dict/set updates vs batched recording + one device call per
    drain, each collected at once (no overlap: the replay has no next
    drain's decode to hide the fetch behind)."""
    import sys

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        DictQuorumTracker,
        TpuQuorumTracker,
    )
    from tests.protocols.multipaxos_harness import (
        drain_and_collect,
        make_multipaxos,
    )

    config = make_multipaxos(f=1).config
    if quorum_backend == "tpu":
        tracker = TpuQuorumTracker(config, window=1 << 14)
    else:
        tracker = DictQuorumTracker(config)
    acceptors = 2 * config.f + 1
    drains = max(1, num_votes // (drain_width * acceptors))
    # Warm one drain (compiles nothing new; buckets prewarm at init).
    base = 0
    for slot in range(base, base + drain_width):
        for acc in range(acceptors):
            tracker.record(slot, 0, 0, acc)
    drain_and_collect(tracker)
    base += drain_width
    chosen = 0
    t0 = time.perf_counter()
    if ranged:
        for _ in range(drains):
            for acc in range(acceptors):
                tracker.record_range(base, base + drain_width, 0, 0, acc)
            chosen += len(drain_and_collect(tracker))
            base += drain_width
    else:
        for _ in range(drains):
            record = tracker.record
            for slot in range(base, base + drain_width):
                for acc in range(acceptors):
                    record(slot, 0, 0, acc)
            chosen += len(drain_and_collect(tracker))
            base += drain_width
    elapsed = time.perf_counter() - t0
    assert chosen == drains * drain_width, (chosen, drains, drain_width)
    return drains * drain_width * acceptors / elapsed


def _overlap_metrics(role_metrics: dict) -> dict:
    """Aggregate the proxy leaders' dispatch instrumentation (scraped
    /metrics) into the overlap summary the deployed tpu point carries:
    how deep the in-flight dispatch queue runs (0 = every fetch is
    serialized behind its drain, i.e. nothing overlaps) and what each
    device collect costs."""
    sums = {"dispatches": 0.0, "inflight_sum": 0.0, "inflight_count": 0.0,
            "collect_sum_s": 0.0, "collect_count": 0.0}
    p = "multipaxos_proxy_leader_tpu_"
    for label, metrics in role_metrics.items():
        if not label.startswith("proxy_leader"):
            continue
        sums["dispatches"] += metrics.get(f"{p}dispatches_total", 0.0)
        sums["inflight_sum"] += metrics.get(
            f"{p}inflight_at_dispatch_sum", 0.0)
        sums["inflight_count"] += metrics.get(
            f"{p}inflight_at_dispatch_count", 0.0)
        sums["collect_sum_s"] += metrics.get(
            f"{p}collect_seconds_sum", 0.0)
        sums["collect_count"] += metrics.get(
            f"{p}collect_seconds_count", 0.0)
    return {
        "dispatches": sums["dispatches"],
        "mean_inflight_at_dispatch": round(
            sums["inflight_sum"] / sums["inflight_count"], 3)
        if sums["inflight_count"] else None,
        "collects": sums["collect_count"],
        "mean_collect_ms": round(
            1e3 * sums["collect_sum_s"] / sums["collect_count"], 1)
        if sums["collect_count"] else None,
    }


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--duration", type=float, default=3.0)
    parser.add_argument("--scales", type=str, default="1x5,2x10,4x10",
                        help="comma-separated client_procs x loops points")
    parser.add_argument("--tpu_scales", type=str, default="4x1024",
                        help="sweep points to also run with the tpu "
                             "backend (wide enough that drains pass "
                             "the tracker's device threshold)")
    parser.add_argument("--sim_commands", type=int, default=300)
    parser.add_argument("--sim_inflight", type=str,
                        default="1,256,1024,4096",
                        help="in-flight widths for the coalesced-wave "
                             "sim batch sweep (both backends)")
    parser.add_argument("--sim_repeats", type=int, default=4,
                        help="A/B pairs per width per batch (and runs "
                             "per tracker-sweep point)")
    parser.add_argument("--sim_ab_batches", type=int, default=3,
                        help="independent subprocess batches pooled "
                             "for the sim A/B (process-scoped bias)")
    parser.add_argument("--tracker_widths", type=str,
                        default="16,64,256,1024,4096,8192",
                        help="drain widths for the tracker-only replay "
                             "sweep")
    parser.add_argument("--suite_dir", default=None)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    def parse_scales(text):
        out = []
        for part in text.split(","):
            procs, loops = part.lower().split("x")
            out.append((int(procs), int(loops)))
        return out

    root = args.suite_dir or tempfile.mkdtemp(prefix="fpx_lt_")
    suite = SuiteDirectory(root, "multipaxos_lt")

    # Deployed arms. The dict arm is the reference design; dict+run is
    # the drain-granular pipeline on the host tracker; tpu+run is the
    # same pipeline on the device tracker's vote board, instrumented
    # (prometheus) to measure dispatch overlap. The tpu arm's chip
    # owner refuses to start without a TPU (cli.py), and an arm that
    # fails fails the suite.
    arms = [
        ("dict", dict()),
        ("dict+run", dict(coalesced=True)),
        ("tpu+run", dict(quorum_backend="tpu", coalesced=True,
                         prometheus=True)),
    ]
    points = []
    for arm, kwargs in arms:
        backend = kwargs.get("quorum_backend", "dict")
        scales = parse_scales(args.scales if backend == "dict"
                              else args.tpu_scales)
        for procs, loops in scales:
            stats = run_benchmark(
                suite.benchmark_directory(),
                MultiPaxosInput(num_clients=loops, client_procs=procs,
                                duration_s=args.duration, **kwargs))
            point = {
                "arm": arm,
                "quorum_backend": backend,
                "coalesced": bool(kwargs.get("coalesced")),
                "client_procs": procs,
                "loops_per_proc": loops,
                "duration_s": args.duration,
                "throughput_p90_1s": stats.get("start_throughput_1s.p90"),
                "latency_median_ms": stats.get("latency.median_ms"),
                "latency_p99_ms": stats.get("latency.p99_ms"),
                "num_requests": stats["num_requests"],
            }
            if backend == "tpu":
                point["overlap_metrics"] = _overlap_metrics(
                    stats.get("role_metrics") or {})
            points.append(point)
            print(json.dumps(point))

    # Sim-pipeline comparison: the interleaved paired A/B
    # (sim_ab_pipeline) pooled over INDEPENDENT child processes.
    # Pairing inside one process cancels drift within a batch, but
    # batches carry a +-5-8% process-scoped bias (thread placement, CPU
    # state); the per-width ratio is the median over all batches'
    # pair medians, with the range recorded.
    import statistics as _stats

    inflights = [int(x) for x in args.sim_inflight.split(",")]
    per_width: dict = {str(i): [] for i in inflights}
    child_device = None
    for _batch in range(args.sim_ab_batches):
        out = run_in_child(
            f"sim_ab_pipeline({inflights!r}, reps={args.sim_repeats})")
        child_device = out["device"]
        print(json.dumps({"sim_ab_batch": out}))
        for key, row in out["value"].items():
            per_width[key].append(row)
    sim_ab = {}
    for key, rows in per_width.items():
        if not rows:
            continue
        ratios = [r["tpu_over_dict_ratio"] for r in rows]
        run_ratios = [r["run_over_dict_ratio"] for r in rows]
        tpu_run_ratios = [r["tpu_over_run_ratio"] for r in rows]
        sim_ab[key] = {
            "tpu_over_dict_ratio": round(_stats.median(ratios), 3),
            "ratio_range": [min(ratios), max(ratios)],
            "run_over_dict_ratio": round(_stats.median(run_ratios), 3),
            "run_over_dict_range": [min(run_ratios), max(run_ratios)],
            "tpu_over_run_ratio": round(
                _stats.median(tpu_run_ratios), 3),
            "batches": len(rows),
            "dict_cmds_per_sec_med": round(_stats.median(
                r["dict_cmds_per_sec"] for r in rows), 1),
            "tpu_cmds_per_sec_med": round(_stats.median(
                r["tpu_cmds_per_sec"] for r in rows), 1),
            "dict_run_cmds_per_sec_med": round(_stats.median(
                r["dict_run_cmds_per_sec"] for r in rows), 1),
        }
    crossover = next((i for i in inflights
                      if sim_ab.get(str(i), {})
                      .get("tpu_over_dict_ratio", 0) >= 1.0), None)
    print(json.dumps({"sim_ab_pipeline": sim_ab,
                      "crossover_inflight": crossover}))

    # The serial workload (one command per drain), the device path's
    # worst case: a launch and a collect for a couple of votes.
    sim_rows = {
        backend: round(run_in_child(
            f"sim_transport_cmds_per_sec({backend!r}, "
            f"{args.sim_commands})")["value"], 1)
        for backend in ("dict", "tpu")}
    print(json.dumps({"sim_serial_cmds_per_sec": sim_rows}))

    import statistics

    def child_sweep(fn_name: str, points: dict, digits: int) -> dict:
        """{backend: {point_label: call_args}} -> median table, every
        sample from a child of its own."""
        table = {}
        for backend, by_label in points.items():
            table[backend] = {}
            for label, call_args in by_label.items():
                samples = [run_in_child(f"{fn_name}({call_args})")["value"]
                           for _ in range(args.sim_repeats)]
                table[backend][label] = round(
                    statistics.median(samples), digits) if digits \
                    else round(statistics.median(samples))
        return table

    def first_crossover(table: dict, labels) -> "int | None":
        return next(
            (x for x in labels
             if table.get("tpu", {}).get(str(x), 0)
             >= table.get("dict", {}).get(str(x), float("inf"))), None)

    # Tracker replay: the ProxyLeader vote-collection component alone
    # (no actor pipeline), identical synthetic Phase2b streams, drain
    # width swept. This is where the dict-vs-device crossover is
    # measured directly.
    widths = [int(x) for x in args.tracker_widths.split(",")]
    tracker = child_sweep("tracker_votes_per_sec", {
        backend: {str(w): f"{backend!r}, {w}" for w in widths}
        for backend in ("dict", "tpu")}, digits=0)
    tracker_crossover = first_crossover(tracker, widths)
    print(json.dumps({"tracker_votes_per_sec": tracker,
                      "tracker_crossover_width": tracker_crossover}))

    # The same replay with RANGED acks (Phase2bRange, the acceptors'
    # batched steady-state shape): O(1) Python per ranged message into
    # the device tracker vs per-slot expansion in the dict oracle --
    # the regime where the device path structurally wins.
    tracker_ranged = child_sweep("tracker_votes_per_sec", {
        backend: {str(w): f"{backend!r}, {w}, ranged=True"
                  for w in widths}
        for backend in ("dict", "tpu")}, digits=0)
    ranged_crossover = first_crossover(tracker_ranged, widths)
    print(json.dumps({
        "tracker_ranged_votes_per_sec": tracker_ranged,
        "tracker_ranged_crossover_width": ranged_crossover}))

    result = {
        "benchmark": "multipaxos_lt",
        "host_cpus": os.cpu_count(),
        "duration_s": args.duration,
        # What the sim/tracker children's JAX ran on. "tpu" in the keys
        # below names the quorum_backend option, not this.
        "child_device": child_device,
        "deployed_points": points,
        "sim_ab_pipeline": sim_ab,
        "crossover_inflight": crossover,
        "sim_serial_cmds_per_sec": sim_rows,
        "tracker_votes_per_sec": tracker,
        "tracker_crossover_width": tracker_crossover,
        "tracker_ranged_votes_per_sec": tracker_ranged,
        "tracker_ranged_crossover_width": ranged_crossover,
        "sim_ab_methodology": (
            "per-width ratio = median over independent subprocess "
            "batches of each batch's paired-A/B median; ranges "
            "recorded"),
        "note": ("sim_ab_pipeline: full actor pipeline over "
                 "SimTransport, interleaved paired A/B/C medians. "
                 "'dict' is the reference design (per-message Python, "
                 "host-dict vote tracking); 'tpu' is the drain-granular "
                 "run pipeline (ClientRequestArray -> Phase2aRun -> "
                 "Phase2bRange -> ChosenRun -> ClientReplyArray) over "
                 "the device-backed tracker; run_over_dict_ratio is the "
                 "dict-tracker ablation of the same run pipeline, "
                 "isolating message-structure wins from vote-tracking "
                 "wins. The tpu tracker sends every vote to the vote "
                 "board on the device, one launch a drain as a rule. "
                 "tracker_votes_per_sec isolates the ProxyLeader "
                 "vote-collection component, each drain collected at "
                 "once."),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    return result


if __name__ == "__main__":
    main()

"""paxload overload A/B: goodput/p99/p999 vs offered load, 1x-20x.

THE GATE (ISSUE 6): at 10x measured capacity, with admission ON,

  * goodput (commands completing within the SLO deadline) stays
    >= 70% of the 1x peak,
  * admitted-request p99 stays <= 5x the 1x-load p99,
  * no unbounded queue growth (max queue depth across the run stays
    within a constant factor of the 1x depth),

and the paired no-admission BASELINE arm violates the gate -- the
degrade-by-shedding vs degrade-by-collapse A/B "The Performance of
Paxos in the Cloud" (PAPERS.md) motivates.

Model: the serve/loadgen.py virtual-time service model over the
coalesced multipaxos SimTransport pipeline -- 1M-session SoA open-loop
arrivals (the SHARED bench/workload.OpenLoopWorkload), a CPU budget of
one virtual second per virtual second (1/capacity per completed
command + a per-message cost), timers on virtual deadlines. Fully
deterministic per seed.

Also records ``admission_overhead``: the trace_overhead-style paired
A/B proving the DISABLED admission hooks (transport ``is None`` tests
+ the leader's _admit early-outs) cost <3% -- every deployment pays
the disabled path.

Usage::

    python -m frankenpaxos_tpu.bench.overload_lt \
        --out bench_results/overload_lt.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import time

#: The virtual service model (loadgen.SimOverloadDriver): cluster
#: capacity in commands/virtual-second and the per-message CPU cost.
CAPACITY_CMDS_S = 500.0
MSG_COST_S = 0.0001
#: Nominal 1x offered rate: under effective capacity (capacity minus
#: per-message overhead) so the 1x arm is a healthy system.
NOMINAL_1X = 300.0
SLO_DEADLINE_S = 1.0
LOADS = (1, 2, 5, 10, 20)

#: The admission arm's server/client knobs (docs/SERVING.md): token
#: bucket at ~the effective capacity, a watermark-tied in-flight
#: budget of ~0.5s of capacity, a bounded client-lane inbox, explicit
#: reject-newest, and client backoff with a bounded retry budget.
ADMISSION = dict(
    admission_token_rate=430.0,
    admission_token_burst=30.0,
    admission_inflight_limit=80,
    admission_inbox_capacity=64,
    admission_inbox_policy="reject",
    admission_retry_after_ms=100,
)
CLIENT_RETRY_BUDGET = 4
#: Client backoff under rejection: starts high enough that one
#: rejected burst does not re-arrive within the next few ticks.
CLIENT_BACKOFF = dict(initial_s=0.15, max_s=2.0, multiplier=2.0,
                      jitter=0.5)


def run_arm(load_x: float, admission_on: bool, *, duration_s: float,
            num_sessions: int, seed: int = 0) -> dict:
    from frankenpaxos_tpu.bench.workload import OpenLoopWorkload
    from frankenpaxos_tpu.serve.loadgen import SimOverloadDriver
    from tests.protocols.multipaxos_harness import make_multipaxos

    from frankenpaxos_tpu.serve.backoff import Backoff

    sim = make_multipaxos(
        f=1, coalesced=True, seed=seed,
        leader_admission=ADMISSION if admission_on else None,
        client_retry_budget=CLIENT_RETRY_BUDGET if admission_on else 0,
        client_backoff=Backoff(**CLIENT_BACKOFF) if admission_on
        else None)
    workload = OpenLoopWorkload(rate=NOMINAL_1X * load_x,
                                zipf_s=1.1, num_keys=1 << 16)
    driver = SimOverloadDriver(
        sim, workload, num_sessions=num_sessions,
        capacity_cmds_per_s=CAPACITY_CMDS_S, msg_cost_s=MSG_COST_S,
        slo_deadline_s=SLO_DEADLINE_S, seed=seed + int(load_x * 100))
    t0 = time.perf_counter()
    stats = driver.run(duration_s=duration_s, warmup_s=1.0,
                       settle_s=10.0)
    stats["load_x"] = load_x
    stats["admission"] = {"enabled": admission_on, **stats["admission"]}
    stats["wall_seconds"] = round(time.perf_counter() - t0, 1)
    return stats


def evaluate_gate(arms: dict) -> dict:
    """arms: {"admission"/"baseline": {load_x: stats}}.

    The p99 in the gate is the ADMITTED-request p99
    (``p99_admitted_s``): ops the server admitted on arrival, so the
    number is the latency the admission-controlled pipeline delivered
    -- client backoff sleeps from earlier rejections are a different
    (intended, bounded) cost, reported separately as the end-to-end
    ``p99_latency_s``. For the baseline nothing is ever rejected, so
    the two coincide -- the A/B compares like with like."""
    adm, base = arms["admission"], arms["baseline"]
    peak_1x = adm[1]["goodput_cmds_per_s"]
    p99_1x = adm[1]["p99_admitted_s"] or 1e-9
    depth_1x = max(1, adm[1]["max_queue_depth"])
    ten = adm[10]
    ten_base = base[10]
    goodput_ok = ten["goodput_cmds_per_s"] >= 0.7 * peak_1x
    p99_ok = (ten["p99_admitted_s"] or float("inf")) <= 5 * p99_1x
    # "Bounded": the admission knobs bound the queue by construction
    # (inbox capacity + in-flight budget + token burst, times a small
    # constant for replies in flight), independent of offered load or
    # duration -- the baseline's depth instead grows with both.
    depth_bound = 16 * depth_1x + 2 * (
        ADMISSION["admission_inbox_capacity"]
        + ADMISSION["admission_inflight_limit"]
        + int(ADMISSION["admission_token_burst"]))
    depth_ok = ten["max_queue_depth"] <= depth_bound
    # Load-independence: when the sweep includes 20x, the 20x depth
    # must not outgrow the 10x depth by more than jitter.
    depth_flat = None
    if 20 in adm:
        depth_flat = (adm[20]["max_queue_depth"]
                      <= 1.5 * max(1, ten["max_queue_depth"]))
        depth_ok = depth_ok and depth_flat
    baseline_violations = []
    if ten_base["goodput_cmds_per_s"] < 0.7 * peak_1x:
        baseline_violations.append("goodput")
    if (ten_base["p99_admitted_s"] or float("inf")) > 5 * p99_1x:
        baseline_violations.append("p99")
    if ten_base["max_queue_depth"] > depth_bound:
        baseline_violations.append("queue_growth")
    return {
        "peak_1x_goodput": peak_1x,
        "p99_1x_s": p99_1x,
        "at_10x": {
            "goodput": ten["goodput_cmds_per_s"],
            "goodput_floor": round(0.7 * peak_1x, 2),
            "goodput_ok": goodput_ok,
            "p99_admitted_s": ten["p99_admitted_s"],
            "p99_e2e_s": ten["p99_latency_s"],
            "p99_ceiling_s": round(5 * p99_1x, 4),
            "p99_ok": p99_ok,
            "max_queue_depth": ten["max_queue_depth"],
            "queue_depth_bound": depth_bound,
            "depth_flat_10x_to_20x": depth_flat,
            "queue_bounded": depth_ok,
        },
        "baseline_at_10x": {
            "goodput": ten_base["goodput_cmds_per_s"],
            "p99_admitted_s": ten_base["p99_admitted_s"],
            "max_queue_depth": ten_base["max_queue_depth"],
            "violations": baseline_violations,
        },
        "gate_passed": bool(goodput_ok and p99_ok and depth_ok
                            and baseline_violations),
    }


# --- disabled-hook overhead A/B (trace_overhead methodology) --------------


def _nohooks_patch():
    """(enter, exit) swapping the paxload hook sites for hook-free
    bodies: SimTransport send without the bounded-inbox admission
    check, and the leader client-request handlers without the _admit
    early-outs.

    Post-paxsim the benched delivery path is the wave engine
    (``_run_wave``), where the admission-off inbox cost is one falsy
    branch per delivered frame -- there is no per-message ``_deliver``
    hook left to strip, and patching ``_deliver`` would disable the
    wave fast path in this arm only (sim_transport.WAVE_SAFE_DELIVERS),
    so the A/B would measure engines, not hooks."""
    from frankenpaxos_tpu.protocols.multipaxos import leader as leader_mod
    from frankenpaxos_tpu.protocols.multipaxos.leader import (
        Leader,
        _Inactive,
        _Phase1,
    )
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        ClientRequestBatch,
        CommandBatch,
        NotLeaderClient,
        Phase2aRun,
    )
    from frankenpaxos_tpu.runtime.sim_transport import (
        SimMessage,
        SimTransport,
    )

    def send(self, src, dst, data):
        tracer = self.tracer
        trace = tracer.current if tracer is not None else None
        self.messages.append(
            SimMessage(next(self._ids), src, dst, data, trace))

    def _handle_client_request(self, src, request):
        if isinstance(self.state, _Inactive):
            self.send(src, NotLeaderClient())
        elif isinstance(self.state, _Phase1):
            self.state.pending_batches.append(
                ClientRequestBatch(CommandBatch((request.command,))))
        else:
            self._process_client_request_batch(
                ClientRequestBatch(CommandBatch((request.command,))))

    def _handle_client_request_array(self, src, array):
        if not array.commands:
            return
        if isinstance(self.state, _Inactive):
            self.send(src, NotLeaderClient())
            return
        if isinstance(self.state, _Phase1):
            for command in array.commands:
                self.state.pending_batches.append(
                    ClientRequestBatch(CommandBatch((command,))))
            return
        if self.config.num_acceptor_groups > 1 and not self.config.flexible:
            for command in array.commands:
                self._process_client_request_batch(
                    ClientRequestBatch(CommandBatch((command,))))
            return
        pending = self._epoch_buffering()
        if pending is not None:
            pending.extend(CommandBatch((c,)) for c in array.commands)
            return
        if self._epoch_tagging:
            self._send_epoch_runs(
                tuple(CommandBatch((c,)) for c in array.commands))
            return
        run = Phase2aRun(
            start_slot=self.next_slot, round=self.round,
            values=tuple(CommandBatch((c,)) for c in array.commands))
        k = len(array.commands)
        self.next_slot += k
        dst = self._proxy_leader_address()
        self.send(dst, run)
        self._account_sent_slots(dst, k)

    def _handle_chosen_watermark(self, src, msg):
        self.chosen_watermark = max(self.chosen_watermark, msg.slot)

    originals = (SimTransport.send,
                 Leader._handle_client_request,
                 Leader._handle_client_request_array,
                 Leader._handle_chosen_watermark)

    def enter():
        SimTransport.send = send
        Leader._handle_client_request = _handle_client_request
        Leader._handle_client_request_array = _handle_client_request_array
        Leader._handle_chosen_watermark = _handle_chosen_watermark
        leader_mod  # keep the import referenced

    def exit():
        (SimTransport.send,
         Leader._handle_client_request,
         Leader._handle_client_request_array,
         Leader._handle_chosen_watermark) = originals

    return enter, exit


#: ~1K commands per interleave chunk, 32 timed chunks per arm per
#: block (~32K commands timed per arm), 4 warm-up chunks discarded.
OVERHEAD_CHUNK_CMDS = 1024
OVERHEAD_CHUNKS = 32
OVERHEAD_WARMUP_CHUNKS = 4


def measure_overhead_block(inflight: int) -> float:
    """One chunk-interleaved A/B block: two persistent sims (shipped
    hooks with admission OFF vs verbatim pre-paxload bodies via
    `_nohooks_patch`) driven alternately in ~1K-command chunks with GC
    disabled, arm order flipped every chunk; returns the off/no-hooks
    throughput ratio from the summed per-arm times.

    Why this shape (calibrated on a 2-CPU container): separate
    whole-rep arms flake against the
    3% gate no matter the estimator -- per-rep noise is ~+-20% at
    0.5s reps and an A/A control (two IDENTICAL sims) still spread
    +-8% at 2s reps because gen2 GC pauses over the sims' growing
    heaps land on whichever arm is running. Fine interleaving makes
    the two arms share every throttle/steal window, and disabling GC
    during the timed chunks removes the pause lottery: the same A/A
    control lands within ~1.5% after process warm-up."""
    import gc

    from frankenpaxos_tpu.bench.wal_lt import _drive_waves
    from tests.protocols.multipaxos_harness import make_multipaxos

    enter, exit = _nohooks_patch()
    chunk_waves = max(1, OVERHEAD_CHUNK_CMDS // inflight)
    sims: dict = {}
    results: dict = {}
    for arm in ("off", "no-hooks"):
        if arm == "no-hooks":
            enter()
        try:
            sims[arm] = make_multipaxos(f=1, coalesced=True)
            results[arm] = []
            _drive_waves(sims[arm], inflight, 2, b"w", results[arm])
        finally:
            if arm == "no-hooks":
                exit()
    total = {"off": 0.0, "no-hooks": 0.0}
    gc.collect()
    gc.disable()
    try:
        for k in range(OVERHEAD_WARMUP_CHUNKS + OVERHEAD_CHUNKS):
            order = (("off", "no-hooks") if k % 2
                     else ("no-hooks", "off"))
            for arm in order:
                if arm == "no-hooks":
                    enter()
                try:
                    t0 = time.perf_counter()
                    _drive_waves(sims[arm], inflight, chunk_waves, b"x",
                                 results[arm])
                    elapsed = time.perf_counter() - t0
                finally:
                    if arm == "no-hooks":
                        exit()
                if k >= OVERHEAD_WARMUP_CHUNKS:
                    total[arm] += elapsed
    finally:
        gc.enable()
    expected = (2 + (OVERHEAD_WARMUP_CHUNKS + OVERHEAD_CHUNKS)
                * chunk_waves) * inflight
    assert len(results["off"]) == len(results["no-hooks"]) == expected
    return total["no-hooks"] / total["off"]


def admission_overhead(inflights=(16, 256, 1024), blocks: int = 7) -> dict:
    """Paired chunk-interleaved A/B (`measure_overhead_block`); the
    reported ratio is the MEDIAN over ``blocks`` independent blocks
    (fresh sims each, so one cold-process or GC-debt-laden block
    cannot swing it). Per-block ratios are recorded as ratio_range
    for noise visibility."""
    table = {}
    worst = 0.0
    for inflight in inflights:
        ratios = sorted(measure_overhead_block(inflight)
                        for _ in range(blocks))
        chunk_waves = max(1, OVERHEAD_CHUNK_CMDS // inflight)
        row = {
            "ratio_off_over_no_hooks": round(statistics.median(ratios), 4),
            "ratio_range": [round(ratios[0], 4), round(ratios[-1], 4)],
            "commands_timed": chunk_waves * inflight * OVERHEAD_CHUNKS
            * blocks,
        }
        overhead_pct = round((1.0 - row["ratio_off_over_no_hooks"]) * 100,
                             2)
        row["off_overhead_pct"] = overhead_pct
        worst = max(worst, overhead_pct)
        table[str(inflight)] = row
    return {"per_width": table,
            "off_overhead_pct_worst_width": round(worst, 2),
            "gate": "admission-off per-message overhead must be < 3%",
            "estimator": ("median of chunk-interleaved gc-disabled "
                          "block ratios"),
            "gate_passed": worst < 3.0}


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None)
    parser.add_argument("--smoke", action="store_true",
                        help="2-minute CI smoke: fewer loads, shorter "
                             "windows, smaller session array")
    parser.add_argument("--num_sessions", type=int, default=None)
    parser.add_argument("--duration", type=float, default=None)
    parser.add_argument("--skip_overhead", action="store_true")
    args = parser.parse_args(argv)

    loads = (1, 10) if args.smoke else LOADS
    duration_s = args.duration or (4.0 if args.smoke else 8.0)
    num_sessions = args.num_sessions or (
        1 << 18 if args.smoke else 1_000_000)

    arms: dict = {"admission": {}, "baseline": {}}
    for load_x in loads:
        for name, on in (("baseline", False), ("admission", True)):
            stats = run_arm(load_x, on, duration_s=duration_s,
                            num_sessions=num_sessions)
            arms[name][load_x] = stats
            print(json.dumps({"arm": name, **{
                k: stats[k] for k in ("load_x", "goodput_cmds_per_s",
                                      "p99_admitted_s", "p99_latency_s",
                                      "p999_latency_s",
                                      "max_queue_depth", "giveups",
                                      "wall_seconds")}}), flush=True)

    gate = evaluate_gate(arms)
    result = {
        "benchmark": "overload_lt",
        "host_cpus": os.cpu_count(),
        "model": {
            "capacity_cmds_per_s": CAPACITY_CMDS_S,
            "msg_cost_s": MSG_COST_S,
            "nominal_1x_rate": NOMINAL_1X,
            "slo_deadline_s": SLO_DEADLINE_S,
            "num_sessions": num_sessions,
            "duration_s": duration_s,
            "admission_knobs": ADMISSION,
            "client_retry_budget": CLIENT_RETRY_BUDGET,
        },
        "curves": {name: {str(k): v for k, v in rows.items()}
                   for name, rows in arms.items()},
        "gate": gate,
        "methodology": (
            "serve/loadgen.py virtual-time service model over the "
            "coalesced multipaxos SimTransport pipeline: open-loop "
            "Zipf(1.1) arrivals from the shared OpenLoopWorkload over "
            "an SoA session array, cluster CPU budget = 1 virtual "
            "second/second (1/capacity per completed command + "
            "msg_cost per delivery), timers on virtual deadlines; "
            "goodput counts completions within the SLO deadline among "
            "commands ISSUED in the measured window; paired arms "
            "share seeds. Deterministic per seed."),
    }
    if not args.skip_overhead:
        # Full-strength A/B even in the smoke: whole-rep arms flake
        # against the 3% gate on this container at ANY rep count
        # (see measure_overhead_block), so the smoke only trims the
        # width list, never the blocks.
        result["admission_overhead"] = admission_overhead(
            inflights=(16, 256) if args.smoke else (16, 256, 1024))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    print(json.dumps({"gate": gate,
                      "overhead": result.get("admission_overhead", {}).get(
                          "off_overhead_pct_worst_width")}, indent=2))
    return result


if __name__ == "__main__":
    main()

"""Block-size frontier sweep for the device pipeline (bench.py).

Sweeps the per-drain block size of the steady-state MultiPaxos pipeline
(`bench.pipeline.run_steps`) at the 1M-slot window and records, per
block size, committed cmds/s and per-drain latency. The committed JSON
(`bench_results/block_sweep.json`) justifies the BLOCK constant in
`bench.py`: pick the highest-throughput point whose per-drain latency
stays under the 50us BASELINE.json target.

Run: python -m frankenpaxos_tpu.bench.block_sweep
"""

from __future__ import annotations

import json
import pathlib
import time

import jax

from frankenpaxos_tpu.bench.pipeline import (
    drain_latency_distribution,
    make_state,
    run_steps,
)
from frankenpaxos_tpu.quorums import SimpleMajority

WINDOW = 1 << 20
NUM_ACCEPTORS = 3
BLOCKS = [1 << 12, 1 << 13, 1 << 14, 1 << 15, 1 << 16, 1 << 17]
TARGET_US = 50.0


def measure(block: int, iters: int, repeats: int = 3) -> dict:
    """One block size, ``repeats`` timed runs after one warm/compile
    run. Per-run numbers are recorded and the point is summarized by
    its WORST run: the frontier choice must be robust to run-to-run
    jitter, not lucky."""
    masks, thresholds, combine_any = (
        SimpleMajority(range(NUM_ACCEPTORS)).write_spec().as_arrays())
    masks_t = tuple(tuple(int(x) for x in row) for row in masks)
    thresholds_t = tuple(int(t) for t in thresholds)

    state = make_state(WINDOW, NUM_ACCEPTORS)
    state = run_steps(state, iters, block, masks_t, thresholds_t,
                      combine_any)
    jax.block_until_ready(state.committed)
    warm_committed = int(state.committed)

    runs = []
    for _ in range(repeats):
        state = make_state(WINDOW, NUM_ACCEPTORS)
        jax.block_until_ready(state.votes)
        t0 = time.perf_counter()
        state = run_steps(state, iters, block, masks_t, thresholds_t,
                          combine_any)
        committed = int(state.committed)  # fetch orders after compute
        elapsed = time.perf_counter() - t0
        assert committed == warm_committed, "nondeterministic pipeline"
        assert abs(committed - iters * block) <= 2 * block, (
            committed, iters * block)
        runs.append({
            "elapsed_s": round(elapsed, 4),
            "cmds_per_sec": round(committed / elapsed, 1),
            "drain_latency_us": round(elapsed / iters * 1e6, 2),
        })
    worst = min(runs, key=lambda r: r["cmds_per_sec"])
    # True per-drain distribution at this block size (chunked
    # host-timed dispatches; see pipeline.drain_latency_distribution).
    dist = drain_latency_distribution(
        (masks_t, thresholds_t, combine_any), NUM_ACCEPTORS, WINDOW,
        block, worst["drain_latency_us"], time_budget_s=8.0,
        target_samples=256)
    return {
        "block_slots": block,
        "iters": iters,
        "committed": warm_committed,
        "runs": runs,
        "cmds_per_sec": worst["cmds_per_sec"],
        "drain_latency_us": max(r["drain_latency_us"] for r in runs),
        **{k: dist[k] for k in ("p50_drain_latency_us",
                                "p99_drain_latency_us",
                                "latency_samples",
                                "drains_per_sample")},
    }


def main() -> None:
    rows = []
    for block in BLOCKS:
        # Keep total committed work roughly constant across points so
        # each measurement lasts long enough to swamp the one-time
        # dispatch + result fetch, which otherwise dominates
        # sub-second runs.
        iters = max(2048, (1 << 30) // block)
        row = measure(block, iters)
        rows.append(row)
        print(json.dumps(row))

    eligible = [r for r in rows if r["drain_latency_us"] < TARGET_US]
    best = max(eligible or rows, key=lambda r: r["cmds_per_sec"])
    out = {
        "suite": "block_sweep",
        "window_slots": WINDOW,
        "num_acceptors": NUM_ACCEPTORS,
        "target_drain_latency_us": TARGET_US,
        "device": str(jax.devices()[0]),
        "rows": rows,
        "chosen_block": best["block_slots"],
        "target_met": bool(eligible),
        "round_history_cmds_per_sec": {
            "r01": 815e6, "r02": 549e6, "r03": 1.64e9},
        "note": ("each point is 3 quiet runs after a warm run; "
                 "cmds_per_sec / drain_latency_us summarize the WORST "
                 "run, so bench.py's BLOCK (the highest worst-case "
                 "throughput under the 50us latency target) is robust "
                 "to run noise, not tuned to a lucky run. "
                 "round_history records the r01-r03 headline swing "
                 "(815M -> 549M -> 1.64B cmds/s) this methodology "
                 "addresses."
                 if eligible else
                 "WARNING: no block size met the latency target on this "
                 "run; chosen_block is the fastest point regardless."),
    }
    path = pathlib.Path(__file__).resolve().parents[2] / "bench_results"
    path.mkdir(exist_ok=True)
    (path / "block_sweep.json").write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps({"chosen_block": best["block_slots"],
                      "written": str(path / "block_sweep.json")}))


if __name__ == "__main__":
    main()

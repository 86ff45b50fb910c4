"""paxmesh A/B: the sharded drain pipeline vs one chip, same window.

THE ARTIFACT (ISSUE 17): ``bench_results/multichip_lt.json`` -- a
paired 1-chip vs mesh A/B over the SAME global window (1M slots, plus
an 8M arm), per-shard p50/p99 drain latency, and the correctness gates
that make the number trustworthy:

  * **bit-identity oracle gates**: the sharded step replayed at >= 3
    mesh shapes -- including a NON-DIVISIBLE slot split (a block that
    does not divide over the slot shards, exercising the round-up +
    pad-mask path) -- must match the unsharded host oracle on every
    state leaf, compared through ``pipeline.gathered_layout``.
  * **ingest routing gate**: ``ingest.shard.route_block`` /
    ``place_block`` round-trips a drain block onto the mesh (one
    explicitly placed ``device_put`` per mesh slice) and back.
  * **full-scale cross-arm equality**: after equal drains the two
    arms' committed / sm_state registers must agree exactly -- the
    oracle gate's bit-identity, enforced at headline scale for free.

Methodology (the overload_lt shape): both arms PERSISTENT, driven
alternately in equal chunks with the order flipped every chunk and GC
disabled during the timed region, warmup chunks discarded, per-arm
times summed, and the reported speedup the MEDIAN over independent
blocks. Chunks resume the drain counter (``run_steps_from`` / the
sharded runner take a traced start), so every chunk reuses one
compiled executable and the ring keeps rolling.

It runs on the TPU's chips (``device.claim_tpu``: no TPU, no run).
The one exception is an explicit ``JAX_PLATFORMS=cpu`` -- CI's
multichip-smoke lane -- under which the A/B runs on a FORCED 8-device
host-platform mesh and the artifact says so (``"host_mesh": true``).

Usage::

    python -m frankenpaxos_tpu.bench.multichip_lt \
        --out bench_results/multichip_lt.json [--smoke]
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

from frankenpaxos_tpu import device

#: Headline arms: the bench.py 1M-slot window and the scale-out 8M one,
#: both at the frontier-swept 32K-slot drain (bench_results/
#: block_sweep.json) with the bench.py f=1 majority.
NUM_ACCEPTORS = 3
BLOCK = 1 << 15
ARMS_FULL = (("window_1m", 1 << 20), ("window_8m", 1 << 23))
ARMS_SMOKE = (("window_16k", 1 << 14),)
SMOKE_BLOCK = 1 << 10

#: Alternating-chunk A/B knobs (measure_overhead_block's shape).
FULL_CHUNKS = dict(warmup=2, chunks=8, iters=64, blocks=3)
SMOKE_CHUNKS = dict(warmup=1, chunks=4, iters=8, blocks=2)

#: Per-shard latency pass: host-timed dispatches of LAT_ITERS fused
#: drains, per-shard completion via each device shard's
#: block_until_ready (an UPPER bound: a shard's wait includes any
#: cross-shard collective it participates in).
LAT_ITERS = 8
LAT_SAMPLES_FULL = 48
LAT_SAMPLES_SMOKE = 12


def _force_host_mesh() -> None:
    """Force an 8-device host-platform mesh BEFORE jax's backend
    initializes (the __graft_entry__ dryrun pattern)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def _spec_arrays():
    from frankenpaxos_tpu.quorums import SimpleMajority

    spec = SimpleMajority(range(NUM_ACCEPTORS)).write_spec()
    masks, thresholds, combine_any = spec.as_arrays()
    return masks, thresholds, combine_any


def _null_rtt_us(jax, jnp) -> float:
    noop = jax.jit(lambda x: x + 1)
    x = jnp.int32(0)
    for _ in range(3):
        x = noop(x)
        _ = int(x)
    null = []
    for _ in range(20):
        t0 = time.perf_counter()
        x = noop(x)
        _ = int(x)
        null.append(time.perf_counter() - t0)
    import numpy as np

    return float(np.percentile(null, 50) * 1e6)


def measure_ab_block(mesh, window: int, block_size: int, *,
                     warmup: int, chunks: int, iters: int) -> dict:
    """One chunk-interleaved A/B block: persistent 1-chip and mesh
    states over the same GLOBAL window, driven alternately in
    ``iters``-drain chunks (order flipped each chunk) with GC off;
    returns summed per-arm times + the cross-arm equality check."""
    import jax
    import numpy as np

    from frankenpaxos_tpu.bench import pipeline as pl

    masks, thresholds, combine_any = _spec_arrays()
    masks_t = tuple(tuple(int(x) for x in row) for row in masks)
    thresholds_t = tuple(int(t) for t in thresholds)

    # Arm A: one chip, the unsharded pipeline, chunked with a traced
    # start so the ring continues across chunks.
    one = pl.make_state(window, NUM_ACCEPTORS)

    def run_one(state, start):
        return pl.run_steps_from(state, start, iters, block_size,
                                 masks_t, thresholds_t, combine_any)

    # Arm B: the mesh, same global window (padded iff non-divisible --
    # the headline block divides, so w_padded == window here).
    msh, _, w_padded = pl.make_sharded_state(mesh, window, block_size,
                                             NUM_ACCEPTORS)
    runner, _ = pl.make_sharded_runner(
        mesh, block_size=block_size, masks=masks, thresholds=thresholds,
        combine_any=combine_any, iters=iters)

    import jax.numpy as jnp

    # Warm both executables at the exact timed shapes.
    start = jnp.int32(0)
    one = run_one(one, start)
    msh = runner(msh, start)
    assert int(one.committed) == int(msh.committed), (
        int(one.committed), int(msh.committed))
    at = iters

    total = {"one": 0.0, "mesh": 0.0}
    gc.collect()
    gc.disable()
    try:
        for k in range(warmup + chunks):
            order = ("one", "mesh") if k % 2 else ("mesh", "one")
            start = jnp.int32(at)
            for arm in order:
                t0 = time.perf_counter()
                if arm == "one":
                    one = run_one(one, start)
                    _ = int(one.committed)  # value fetch: full sync
                else:
                    msh = runner(msh, start)
                    _ = int(msh.committed)
                if k >= warmup:
                    total[arm] += time.perf_counter() - t0
            at += iters
    finally:
        gc.enable()
    committed_one = int(one.committed)
    committed_mesh = int(msh.committed)
    sm_one, sm_mesh = int(one.sm_state), int(msh.sm_state)
    drains = chunks * iters
    cmds = drains * block_size
    return {
        "one_s": total["one"],
        "mesh_s": total["mesh"],
        "onechip_cmds_per_sec": cmds / total["one"],
        "mesh_cmds_per_sec": cmds / total["mesh"],
        "speedup": total["one"] / total["mesh"],
        "arms_agree": (committed_one == committed_mesh
                       and sm_one == sm_mesh),
        "committed": committed_mesh,
        "padded_window": w_padded,
    }


def measure_arm(mesh, window: int, block_size: int, knobs: dict) -> dict:
    """MEDIAN-of-blocks A/B for one window arm (fresh states per block
    so one GC-debt-laden or cold block cannot swing the ratio)."""
    rows = [measure_ab_block(mesh, window, block_size,
                             warmup=knobs["warmup"],
                             chunks=knobs["chunks"],
                             iters=knobs["iters"])
            for _ in range(knobs["blocks"])]
    ratios = sorted(r["speedup"] for r in rows)
    mid = rows[[r["speedup"] for r in rows].index(
        ratios[len(ratios) // 2])]
    return {
        "window_slots": window,
        "block_slots": block_size,
        "padded_window_slots": mid["padded_window"],
        "chunks": knobs["chunks"],
        "iters_per_chunk": knobs["iters"],
        "blocks": knobs["blocks"],
        "onechip_cmds_per_sec": round(mid["onechip_cmds_per_sec"], 1),
        "mesh_cmds_per_sec": round(mid["mesh_cmds_per_sec"], 1),
        "speedup": round(mid["speedup"], 3),
        "speedup_range": [round(r, 3) for r in ratios],
        "arms_agree": all(r["arms_agree"] for r in rows),
        "committed_per_block": mid["committed"],
    }


def per_shard_latency(mesh, window: int, block_size: int,
                      samples: int) -> dict:
    """Per-shard p50/p99 drain latency: host-timed dispatches of
    LAT_ITERS fused drains; each device shard's completion observed via
    ``block_until_ready`` on ITS piece of the chosen window, in
    rotating shard order so no one shard always pays the full wait.
    Upper bounds (collectives serialize shards), minus the null RTT."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from frankenpaxos_tpu.bench import pipeline as pl

    masks, thresholds, combine_any = _spec_arrays()
    state, _, _ = pl.make_sharded_state(mesh, window, block_size,
                                        NUM_ACCEPTORS)
    runner, _ = pl.make_sharded_runner(
        mesh, block_size=block_size, masks=masks, thresholds=thresholds,
        combine_any=combine_any, iters=LAT_ITERS)
    null_us = _null_rtt_us(jax, jnp)
    state = runner(state, jnp.int32(0))
    _ = int(state.committed)
    at = LAT_ITERS
    n_shards = len(state.chosen.sharding.device_set)
    times: dict = {}
    for s in range(samples):
        t0 = time.perf_counter()
        state = runner(state, jnp.int32(at))
        at += LAT_ITERS
        shards = list(state.chosen.addressable_shards)
        for off in range(len(shards)):
            shard = shards[(s + off) % len(shards)]
            shard.data.block_until_ready()
            dev = repr(shard.device)
            times.setdefault(dev, []).append(time.perf_counter() - t0)
    out = {}
    for dev in sorted(times):
        us = np.maximum(np.asarray(times[dev]) * 1e6 - null_us, 0.0) \
            / LAT_ITERS
        out[dev] = {"p50_us": round(float(np.percentile(us, 50)), 2),
                    "p99_us": round(float(np.percentile(us, 99)), 2)}
    worst = max(v["p50_us"] for v in out.values())
    return {
        "per_shard": out,
        "worst_shard_p50_us": worst,
        "num_shards": n_shards,
        "samples": samples,
        "drains_per_sample": LAT_ITERS,
        "null_rtt_p50_us": round(null_us, 1),
        "method": ("host-timed dispatches of drains_per_sample fused "
                   "drains; per-shard completion via each device "
                   "shard's block_until_ready in rotating order; "
                   "per-drain = (t_shard - null_rtt_p50) / "
                   "drains_per_sample (upper bound: collectives tie "
                   "shards together)"),
    }


def oracle_gate(group_dim: int, slot_dim: int, block_size: int,
                window: int, drains: int = 7) -> dict:
    """Replay ``drains`` steps sharded at (group, slot) vs the
    unsharded host oracle; compare EVERY state leaf bit-for-bit
    through ``gathered_layout``. n=8 acceptors so every group split
    divides."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from frankenpaxos_tpu.bench import pipeline as pl
    from frankenpaxos_tpu.quorums import SimpleMajority

    n = 8
    spec = SimpleMajority(range(n)).write_spec()
    masks, thresholds, combine_any = spec.as_arrays()
    devices = jax.devices()
    if group_dim * slot_dim > len(devices):
        return {"mesh": f"{group_dim}x{slot_dim}", "skipped":
                f"needs {group_dim * slot_dim} devices, "
                f"have {len(devices)}"}
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices[:group_dim * slot_dim]).reshape(
        group_dim, slot_dim), ("group", "slot"))

    host = pl.make_state(window, n)
    for it in range(drains):
        host = pl.steady_state_step(
            host, jnp.int32(it), block_size=block_size, masks=masks,
            thresholds=thresholds, combine_any=combine_any)

    state, _, w_padded = pl.make_sharded_state(mesh, window, block_size,
                                               n)
    step, _ = pl.make_sharded_step(mesh, block_size=block_size,
                                   masks=masks, thresholds=thresholds,
                                   combine_any=combine_any)
    for it in range(drains):
        state = step(state, jnp.int32(it))

    b_local, pad = pl.local_block(block_size, slot_dim)
    w_local = w_padded // slot_dim
    logical, valid = pl.gathered_layout(slot_dim, w_local, b_local,
                                        block_size)

    def gathered(x):
        x = np.asarray(x)
        if x.ndim == 1:
            out = np.zeros(window, x.dtype)
            out[logical[valid]] = x[valid]
            return out
        out = np.zeros((x.shape[0], window), x.dtype)
        out[:, logical[valid]] = x[:, valid]
        return out

    ok = (int(state.committed) == int(host.committed)
          and int(state.sm_state) == int(host.sm_state)
          and int(state.exec_wm) == int(host.exec_wm))
    for field in ("votes", "chosen", "commands", "results"):
        ok = ok and bool(np.array_equal(
            gathered(getattr(state, field)),
            np.asarray(getattr(host, field))))
    # Pad columns (non-divisible splits only) must stay all-zero.
    if pad:
        ok = ok and not np.asarray(state.votes)[:, ~valid].any() \
            and not np.asarray(state.commands)[~valid].any()
    return {
        "mesh": f"{group_dim}x{slot_dim}",
        "block": block_size,
        "window": window,
        "padded_window": w_padded,
        "non_divisible": pad > 0,
        "drains": drains,
        "bit_identical": bool(ok),
    }


def ingest_gate(mesh, block_size: int) -> dict:
    """Round-trip a drain block through the per-shard ingest routing:
    one placed ``device_put`` per mesh slice, gathered back in lane
    order."""
    import numpy as np

    from frankenpaxos_tpu.bench.pipeline import (
        gathered_layout,
        local_block,
    )
    from frankenpaxos_tpu.ingest.shard import place_block

    slot_dim = mesh.shape["slot"]
    ids = (np.arange(block_size, dtype=np.int32) * 7 + 1)
    placed = place_block(mesh, ids, block_size)
    b_local, _ = local_block(block_size, slot_dim)
    logical, valid = gathered_layout(slot_dim, b_local, b_local,
                                     block_size)
    flat = np.asarray(placed)
    out = np.zeros(block_size, np.int32)
    out[logical[valid]] = flat[valid]
    ok = bool(np.array_equal(out, ids)) and not flat[~valid].any()
    n_puts = len(placed.sharding.addressable_devices_indices_map(
        placed.shape))
    return {
        "round_trip_ok": ok,
        "device_puts_per_drain": n_puts,
        "block": block_size,
        "note": ("one explicitly placed device_put per mesh slice "
                 "(ingest.shard.place_block); lanes land on their "
                 "owning slot shard"),
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="bench_results/multichip_lt.json")
    parser.add_argument("--smoke", action="store_true",
                        help="reduced CI shape: small window, few "
                             "chunks, same gates")
    args = parser.parse_args(argv)

    host_mesh = device.explicit_cpu()
    if host_mesh:
        _force_host_mesh()
    found = device.claim_tpu()

    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    # Acceptors stay whole per shard for the f=1 majority headline
    # (group=1); all devices shard the slot window.
    mesh = Mesh(np.array(devices).reshape(1, len(devices)),
                ("group", "slot"))

    block = SMOKE_BLOCK if args.smoke else BLOCK
    knobs = SMOKE_CHUNKS if args.smoke else FULL_CHUNKS
    arms = ARMS_SMOKE if args.smoke else ARMS_FULL
    lat_samples = LAT_SAMPLES_SMOKE if args.smoke else LAT_SAMPLES_FULL

    arm_rows = {}
    for name, window in arms:
        arm_rows[name] = measure_arm(mesh, window, block, knobs)
        print(f"# {name}: mesh "
              f"{arm_rows[name]['mesh_cmds_per_sec']:.3g} cmds/s, "
              f"1-chip {arm_rows[name]['onechip_cmds_per_sec']:.3g}, "
              f"speedup {arm_rows[name]['speedup']}x",
              file=sys.stderr)

    lat = per_shard_latency(mesh, arms[0][1], block, lat_samples)

    # Bit-identity gates: 1x1 (the degenerate control), 2x4 and 8x1
    # (the ISSUE shapes), and 2x3 with a 100-slot block -- the
    # NON-DIVISIBLE slot split (100 % 3 != 0) through the round-up +
    # pad-mask path.
    gates = [
        oracle_gate(1, 1, 128, 512),
        oracle_gate(2, 4, 128, 512),
        oracle_gate(8, 1, 128, 512),
        oracle_gate(2, 3, 100, 400),
    ]
    ing = ingest_gate(mesh, block)

    ran = [g for g in gates if "bit_identical" in g]
    gates_pass = (len(ran) >= 3
                  and all(g["bit_identical"] for g in ran)
                  and any(g["non_divisible"] for g in ran)
                  and ing["round_trip_ok"]
                  and all(r["arms_agree"] for r in arm_rows.values()))

    artifact = {
        "kind": "multichip_lt",
        "mode": "smoke" if args.smoke else "full",
        "degraded": False,
        "host_mesh": host_mesh,
        "device": found,
        "mesh_shape": {"group": 1, "slot": len(devices)},
        "num_acceptors": NUM_ACCEPTORS,
        "arms": arm_rows,
        "per_shard_latency": lat,
        "oracle_gates": gates,
        "ingest_gate": ing,
        "gates_pass": gates_pass,
        "methodology": (
            "alternating-chunk paired A/B (overload_lt shape): both "
            "arms persistent over the SAME global window, driven in "
            "equal iters_per_chunk-drain chunks with order flipped "
            "each chunk, GC disabled in the timed region, warmup "
            "chunks discarded, speedup = summed 1-chip time / summed "
            "mesh time, median over independent blocks"),
        "host_mesh_note": (
            "JAX_PLATFORMS=cpu: A/B ran on a FORCED 8-device "
            "host-platform (CPU XLA) mesh -- methodology and "
            "bit-identity are real, the speedup is NOT a hardware "
            "claim (8 virtual devices share this host's cores)"
            if host_mesh else ""),
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(json.dumps({k: artifact[k] for k in
                      ("kind", "mode", "host_mesh", "gates_pass")}
                     | {"arms": {k: v["speedup"]
                                 for k, v in arm_rows.items()}}))
    return 0 if gates_pass else 1


if __name__ == "__main__":
    sys.exit(main())

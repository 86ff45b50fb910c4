"""Library microbenchmarks: the hot host data structures + device ops.

The reference ships ScalaMeter benches for its data structures
(jvm/src/bench/scala/frankenpaxos/: BufferMapBench, IntPrefixSetBench,
DependencyGraphBench, VertexIdPrefixSetBench). This is the analog:
per-structure operation throughput, committed as
``bench_results/libbench.json`` so regressions become visible
round-over-round.

Covered: BufferMap put/get/GC, IntPrefixSet add/union/materialized
diff, the three dependency-graph implementations on the EPaxos commit ->
execute shape, the watermark/depset device kernels, and the wire
serializer (binary vs pickle on the hottest message).

Usage::

    python -m frankenpaxos_tpu.bench.libbench \
        --out bench_results/libbench.json
"""

from __future__ import annotations

import argparse
import json
import time


def _rate(n: int, f) -> float:
    """ops/s of f() (which performs n operations), best of 3."""
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return n / best


def bench_buffer_map(n: int = 200_000) -> dict:
    from frankenpaxos_tpu.utils.buffer_map import BufferMap

    def puts():
        m = BufferMap(grow_size=5000)
        for i in range(n):
            m.put(i, i)

    filled = BufferMap(grow_size=5000)
    for i in range(n):
        filled.put(i, i)

    def gets():
        for i in range(n):
            filled.get(i)

    def put_gc():
        m = BufferMap(grow_size=5000)
        for i in range(n):
            m.put(i, i)
            if i % 10_000 == 9_999:
                m.garbage_collect(i - 5_000)

    return {"put_ops_per_s": round(_rate(n, puts)),
            "get_ops_per_s": round(_rate(n, gets)),
            "put_gc_ops_per_s": round(_rate(n, put_gc))}


def bench_int_prefix_set(n: int = 200_000) -> dict:
    from frankenpaxos_tpu.compact import IntPrefixSet

    def adds_in_order():
        s = IntPrefixSet()
        for i in range(n):
            s.add(i)

    def adds_scattered():
        s = IntPrefixSet()
        for i in range(0, 2 * n, 2):
            s.add(i)

    a = IntPrefixSet.from_watermark(n)
    b = IntPrefixSet.from_watermark(n // 2)
    for i in range(n // 2, n, 7):
        b.add(i)

    def diffs():
        for _ in range(200):
            list(a.materialized_diff(b))

    diff_items = 200 * len(list(a.materialized_diff(b)))
    return {"add_in_order_ops_per_s": round(_rate(n, adds_in_order)),
            "add_scattered_ops_per_s": round(_rate(n, adds_scattered)),
            "materialized_diff_items_per_s": round(
                _rate(diff_items, diffs))}


def bench_depgraphs(n: int = 20_000, conflict_stride: int = 10) -> dict:
    """EPaxos shape: command i depends on the previous command touching
    its key (i - conflict_stride), committed in order, executed in
    batches (DependencyGraphBench's commit/execute mix)."""
    from frankenpaxos_tpu.depgraph import make_dependency_graph

    out = {}
    for name in ("tarjan", "incremental", "zigzag", "naive"):
        # The naive oracle is quadratic; keep its input small.
        size = n if name != "naive" else n // 20

        def run_sized(name=name, size=size):
            if name == "zigzag":
                # Zigzag keys decompose into (leader, id) vertex ids.
                g = make_dependency_graph(name, num_leaders=1)
                key = (lambda i: (0, i))
            else:
                g = make_dependency_graph(name)
                key = (lambda i: i)
            for i in range(size):
                deps = ([key(i - conflict_stride)]
                        if i >= conflict_stride else [])
                g.commit(key(i), 0, deps)
                if i % 100 == 99:
                    g.execute()
            g.execute()

        out[f"{name}_commit_execute_ops_per_s"] = round(
            _rate(size, run_sized))
    return out


def bench_device_ops(batch: int = 4096, iters: int = 50) -> dict:
    """The watermark + depset kernels (device twins of QuorumWatermark /
    EPaxos dep sets) at a realistic batch width."""
    import numpy as np

    from frankenpaxos_tpu.ops.depset import DepSetBatch, union, union_reduce
    from frankenpaxos_tpu.ops.watermark import (
        contiguous_prefix_length,
        quorum_watermark_vector,
    )

    import jax
    import jax.numpy as jnp

    from frankenpaxos_tpu.ops.watermark import quorum_watermark

    watermarks = np.random.default_rng(0).integers(
        0, 1 << 20, size=(5, batch)).astype(np.int32)
    quorum_watermark_vector(watermarks, 3)  # compile + sync-path check
    watermarks_dev = jnp.asarray(watermarks.T)  # [batch, nodes]
    quorum_size = jnp.int32(3)

    def watermark_run():
        outs = [quorum_watermark(watermarks_dev, quorum_size)
                for _ in range(iters)]
        jax.block_until_ready(outs)

    present = np.ones(batch, dtype=bool)
    present[batch // 2] = False
    present_dev = jnp.asarray(present)
    contiguous_prefix_length(present_dev)  # compile

    # Device runs chain all iterations and sync ONCE: a per-iteration
    # fetch would measure dispatch+fetch, not the kernel.
    def prefix_run():
        outs = [contiguous_prefix_length(present_dev)
                for _ in range(iters)]
        jax.block_until_ready(outs)

    rng = np.random.default_rng(1)
    leaders, window = 3, 64
    deps = DepSetBatch(
        watermarks=jnp.asarray(rng.integers(
            0, 1 << 16, size=(batch, leaders)), dtype=jnp.int32),
        tails=jnp.asarray(rng.integers(
            0, 2, size=(batch, leaders, window)), dtype=jnp.uint8),
        tail_base=jnp.int32(1 << 16))
    np.asarray(union(deps, deps).watermarks)  # compile
    np.asarray(union_reduce(deps).watermarks)

    def depset_run():
        outs = [union_reduce(union(deps, deps)).watermarks
                for _ in range(iters)]
        jax.block_until_ready(outs)

    return {
        "quorum_watermark_slots_per_s": round(
            _rate(iters * batch, watermark_run)),
        "contiguous_prefix_slots_per_s": round(
            _rate(iters * batch, prefix_run)),
        "depset_union_reduce_deps_per_s": round(
            _rate(iters * batch, depset_run)),
    }


def bench_serializer(n: int = 50_000) -> dict:
    import frankenpaxos_tpu.protocols.multipaxos  # noqa: F401 - codecs
    from frankenpaxos_tpu.protocols.multipaxos.messages import Phase2b
    from frankenpaxos_tpu.runtime.serializer import (
        DEFAULT_SERIALIZER,
        PickleSerializer,
    )

    message = Phase2b(group_index=1, acceptor_index=2, slot=123456,
                      round=3)

    def binary():
        s = DEFAULT_SERIALIZER
        for _ in range(n):
            s.from_bytes(s.to_bytes(message))

    def pickled():
        s = PickleSerializer()
        for _ in range(n):
            s.from_bytes(s.to_bytes(message))

    return {"phase2b_binary_roundtrips_per_s": round(_rate(n, binary)),
            "phase2b_pickle_roundtrips_per_s": round(_rate(n, pickled))}


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    # The device rows run on whatever JAX finds and carry its name: a
    # CPU run is labelled one, never passed off as a device row.
    from frankenpaxos_tpu.device import describe_devices

    device_ops = {"device": describe_devices(), **bench_device_ops()}
    result = {
        "benchmark": "libbench",
        "buffer_map": bench_buffer_map(),
        "int_prefix_set": bench_int_prefix_set(),
        "depgraph": bench_depgraphs(),
        "device_ops": device_ops,
        "serializer": bench_serializer(),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
    print(json.dumps(result, indent=2))
    return result


if __name__ == "__main__":
    main()

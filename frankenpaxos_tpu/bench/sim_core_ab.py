"""sim_core_ab: the paxsim wave engine vs the frozen legacy sim core.

Core-isolated A/B (the paxwire discipline: the legacy arm is the REAL
pre-refactor machinery, pinned verbatim in runtime/sim_legacy.py) over
workloads shaped like the schedules the simulator actually runs, with
sink actors cheap enough that the measurement is the delivery
machinery, not protocol handler Python:

* ``geo-storm/soak-scale`` -- THE GATE. The geo-chaos soak shape
  (tests/soak.py geo-chaos/*: jittered wide-area topology, partition/
  heal cycles, resend-storm backlogs of thousands of frames in
  flight) replayed at the soak's 500x250 event volume. The legacy
  core pays a ``list.remove`` dataclass-``__eq__`` scan per delivered
  frame -- linear in the backlog, quadratic over a storm -- which is
  exactly what capped chaos soaks at ~dozen-zone topologies. Gate:
  >= 10x events/s.
* ``geo/1000-zones`` -- a 1000-zone topology at storm depth; ratio
  measured at a size the legacy core can still complete, then the SoA
  core alone at full size against a CI wall-clock budget.
* ``geo/million-event`` -- >= 1M-event schedule through the SoA core
  against a CI budget (history recording off: 1M+ DeliverMessage
  dataclasses are bookkeeping no oracle reads). The legacy core's
  cost is quadratic in backlog depth (measured slope reported from
  the 1000-zone row); it does not complete this schedule in useful
  time and is not timed here.
* ``fifo/deep-wave`` and ``fifo/shallow-wave`` -- context rows, no
  gate: plain FIFO waves at overload-queue depth (legacy pays an
  O(depth) pointer memmove per frame) and at chaos-soak depth (the
  legacy remove hits index 0; both cores are handler-bound, ~1x --
  reported so the headline can't be mistaken for a claim about
  shallow buffers).

Methodology (overload_lt calibration): the
gate workload alternates the two arms in identical per-round chunks
with GC disabled and warm-up rounds discarded, and the ratio is the
median over independent blocks. Before timing, both arms replay a
reduced storm with history on and must produce BYTE-IDENTICAL
delivery histories (the golden-equivalence contract of
tests/test_sim_core.py, re-asserted on every bench run).

Run::

    python -m frankenpaxos_tpu.bench.sim_core_ab \
        --out bench_results/sim_core_ab.json

``--smoke`` runs the CI-sized variant (reduced rounds, same storm
depth, gates enforced at the reduced size).
"""

from __future__ import annotations

import argparse
import gc
import json
import random
import statistics
import time

from frankenpaxos_tpu.geo.topology import GeoTopology
from frankenpaxos_tpu.geo.transport import GeoSimTransport
from frankenpaxos_tpu.runtime.actor import Actor
from frankenpaxos_tpu.runtime.logger import Logger
from frankenpaxos_tpu.runtime.sim_legacy import (
    LegacyGeoSimTransport,
    LegacySimTransport,
)
from frankenpaxos_tpu.runtime.sim_transport import SimTransport


class _NullLogger(Logger):
    def debug(self, m):
        pass

    def info(self, m):
        pass

    def warn(self, m):
        pass

    def error(self, m):
        pass

    def fatal(self, m):
        raise RuntimeError(m)


class _RawSerializer:
    """Identity codec: sink payloads are opaque bytes, so neither arm
    pays pickle and the A/B isolates the delivery machinery."""

    def to_bytes(self, m):
        return m

    def from_bytes(self, d):
        return d


_ECHO = 1  # payload flag: re-send one hop to a deterministic peer


class StormSink(Actor):
    """Counts deliveries; frames flagged ``_ECHO`` re-send one hop to
    a deterministic peer (cross-zone chatter). The ``receive_batch``
    override is the SoA-native path the wave engine exploits; the
    legacy core delivers per message through ``receive``. Both paths
    process frames in arrival order, so the two arms stay
    schedule-identical."""

    serializer = _RawSerializer()

    def __init__(self, address, transport, logger, peers, index):
        super().__init__(address, transport, logger)
        self.peers = peers
        self.index = index
        self.n = 0
        self.drains = 0

    def _react(self, data):
        if data[0] == _ECHO:
            hop = (self.index + data[1]) % len(self.peers)
            self.send(self.peers[hop], bytes((0, data[1])))

    def receive(self, src, data):
        self.n += 1
        self._react(data)

    def receive_batch(self, batch):
        self.n += len(batch)
        react = self._react
        for _, data in batch:
            react(data)

    def on_drain(self):
        self.drains += 1


class GeoStorm:
    """One arm of the geo storm: a jittered multi-region topology,
    per-zone sinks, and a deterministic per-round schedule -- burst
    sends to pseudo-random zones (a slice flagged to echo one hop),
    partition/heal cycles on a rotating link pair, and a short
    ``run_for`` so a multi-round backlog stays in flight (the
    resend-storm regime of the geo-chaos soaks)."""

    def __init__(self, transport_cls, zones: int, burst: int,
                 seed: int = 0, dwell_s: float = 0.003,
                 record_history: bool = False):
        per_region = 10 if zones >= 100 else 3
        regions = {f"r{i}": [f"z{i}-{j}" for j in range(per_region)]
                   for i in range(zones // per_region)}
        self.topology = GeoTopology(regions, seed=seed)
        self.transport = transport_cls(self.topology, _NullLogger())
        self.transport.record_history = record_history
        self.burst = burst
        self.dwell_s = dwell_s
        self.rng = random.Random(f"sim_core_ab|{seed}")
        self.peers = [f"sink-{i}" for i in range(len(self.topology.zones))]
        self.sinks = [
            StormSink(addr, self.transport, self.transport.logger,
                      self.peers, i)
            for i, addr in enumerate(self.peers)]
        for sink, zone in zip(self.sinks, self.topology.zones):
            self.topology.place(sink.address, zone)
        self.topology.place("driver", self.topology.zones[0])
        self.round = 0

    def run_round(self) -> None:
        r = self.round
        self.round += 1
        rng = self.rng
        send = self.transport.send
        n = len(self.peers)
        for k in range(self.burst):
            flag = _ECHO if k % 4 == 0 else 0
            send("driver", self.peers[rng.randrange(n)],
                 bytes((flag, rng.randrange(7))))
        zones = self.topology.zones
        if r % 20 == 4:
            a = zones[r % len(zones)]
            b = zones[(r * 7 + 3) % len(zones)]
            if a != b:
                self.topology.partition_link(a, b)
        if r % 20 == 14:
            self.topology.heal_all()
        self.transport.run_for(self.dwell_s)

    def finish(self) -> int:
        self.topology.heal_all()
        self.transport.run_until_quiescent()
        return sum(s.n for s in self.sinks)


def _projection(transport) -> list:
    from frankenpaxos_tpu.runtime.sim_transport import DeliverMessage

    return [(c.message.id, str(c.message.src), str(c.message.dst),
             bytes(c.message.data))
            for c in transport.history if isinstance(c, DeliverMessage)]


def golden_equivalence(rounds: int = 40, burst: int = 100) -> bool:
    """Reduced storm, history on, both arms: byte-identical delivered
    schedules (asserted -- a silent divergence would invalidate every
    ratio below)."""
    projections = []
    for cls in (LegacyGeoSimTransport, GeoSimTransport):
        storm = GeoStorm(cls, zones=9, burst=burst, seed=5,
                         record_history=True)
        for _ in range(rounds):
            storm.run_round()
        storm.finish()
        projections.append(_projection(storm.transport))
    assert projections[0] == projections[1], \
        "legacy/SoA delivery schedules diverged"
    assert len(projections[0]) > rounds * burst // 2
    return True


def measure_storm_block(rounds: int, burst: int, zones: int,
                        warmup: int, seed: int) -> dict:
    """One chunk-interleaved block: two persistent storms (legacy /
    SoA) driven alternately one round at a time with GC disabled, arm
    order flipped every round; returns summed per-arm seconds and the
    per-arm delivered totals (must match)."""
    storms = {
        "legacy": GeoStorm(LegacyGeoSimTransport, zones, burst,
                           seed=seed),
        "soa": GeoStorm(GeoSimTransport, zones, burst, seed=seed),
    }
    total = {"legacy": 0.0, "soa": 0.0}
    gc.collect()
    gc.disable()
    try:
        for r in range(warmup + rounds):
            order = (("legacy", "soa") if r % 2 else ("soa", "legacy"))
            for arm in order:
                t0 = time.perf_counter()
                storms[arm].run_round()
                elapsed = time.perf_counter() - t0
                if r >= warmup:
                    total[arm] += elapsed
    finally:
        gc.enable()
    events = {arm: storm.finish() for arm, storm in storms.items()}
    assert events["legacy"] == events["soa"], events
    return {"seconds": total, "events": events["soa"],
            "timed_events": events["soa"] * rounds // (warmup + rounds)}


def bench_storm(rounds: int, burst: int, zones: int, blocks: int,
                warmup: int) -> dict:
    ratios = []
    per_block = []
    events = timed = 0
    for b in range(blocks):
        block = measure_storm_block(rounds, burst, zones, warmup,
                                    seed=b)
        ratio = block["seconds"]["legacy"] / block["seconds"]["soa"]
        ratios.append(ratio)
        events = block["events"]
        timed = block["timed_events"]
        per_block.append({
            "legacy_s": round(block["seconds"]["legacy"], 3),
            "soa_s": round(block["seconds"]["soa"], 3),
            "ratio": round(ratio, 2),
        })
    ratios.sort()
    return {
        "zones": zones,
        "rounds_per_block": rounds,
        "burst_per_round": burst,
        "events_per_arm_per_block": events,
        "timed_events_per_arm_per_block": timed,
        "blocks": per_block,
        "ratio_median": round(statistics.median(ratios), 2),
        "ratio_range": [round(ratios[0], 2), round(ratios[-1], 2)],
    }


def bench_big_geo(zones: int, burst: int, rounds: int,
                  legacy_rounds: int) -> dict:
    """SoA core at full size against wall clock; legacy at a reduced
    round count for the ratio (its per-event cost grows with backlog
    depth, so the full-size ratio would only be LARGER -- recorded as
    a lower bound)."""
    gc.collect()
    results = {}
    for arm, cls, arm_rounds in (
            ("soa", GeoSimTransport, rounds),
            ("legacy", LegacyGeoSimTransport, legacy_rounds)):
        storm = GeoStorm(cls, zones=zones, burst=burst, seed=11)
        gc.disable()
        try:
            t0 = time.perf_counter()
            for _ in range(arm_rounds):
                storm.run_round()
            n = storm.finish()
            dt = time.perf_counter() - t0
        finally:
            gc.enable()
        results[arm] = {"rounds": arm_rounds, "events": n,
                        "seconds": round(dt, 2),
                        "events_per_s": round(n / dt)}
    ratio = (results["soa"]["events_per_s"]
             / results["legacy"]["events_per_s"])
    return {
        "zones": zones,
        "soa_full": results["soa"],
        "legacy_reduced": results["legacy"],
        "events_per_s_ratio_at_reduced_size_lower_bound": round(ratio, 1),
    }


def bench_million(zones: int, events_target: int, burst: int) -> dict:
    """>= ``events_target`` delivered frames through the SoA core
    (history off); the legacy core is quadratic in backlog depth at
    this scale and is not timed (see the 1000-zone row's reduced-size
    ratio for its measured slope)."""
    storm = GeoStorm(GeoSimTransport, zones=zones, burst=burst,
                     seed=13, dwell_s=0.02)
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        while True:
            storm.run_round()
            # Sends are >= deliveries-to-come; stop bursting once
            # enough frames are in the schedule, then drain.
            if storm.round * burst >= events_target:
                break
        n = storm.finish()
        dt = time.perf_counter() - t0
    finally:
        gc.enable()
    return {"zones": zones, "events": n, "seconds": round(dt, 2),
            "events_per_s": round(n / dt)}


# --- plain-FIFO context rows (no gate) ------------------------------------


class FifoSink(Actor):
    serializer = _RawSerializer()

    def __init__(self, address, transport, logger):
        super().__init__(address, transport, logger)
        self.n = 0

    def receive(self, src, data):
        self.n += 1

    def receive_batch(self, batch):
        self.n += len(batch)


def bench_fifo(depth: int, total_events: int) -> dict:
    out = {}
    for arm, cls in (("legacy", LegacySimTransport),
                     ("soa", SimTransport)):
        t = cls(_NullLogger())
        sinks = [FifoSink(f"s{i}", t, t.logger) for i in range(13)]
        payload = b"\x00" * 24
        reps = max(1, total_events // depth)
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            for _ in range(reps):
                for i in range(depth):
                    t.send("c", f"s{i % 13}", payload)
                t.deliver_all_coalesced()
            dt = time.perf_counter() - t0
        finally:
            gc.enable()
        n = sum(s.n for s in sinks)
        out[arm] = {"events": n, "seconds": round(dt, 2),
                    "events_per_s": round(n / dt)}
    out["ratio"] = round(out["soa"]["events_per_s"]
                         / out["legacy"]["events_per_s"], 2)
    out["wave_depth"] = depth
    return out


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default=None)
    parser.add_argument("--smoke", action="store_true",
                        help="CI-sized run: fewer rounds/blocks at the "
                             "same storm depth, gates enforced")
    args = parser.parse_args(argv)

    if args.smoke:
        storm_rounds, blocks, warmup = 60, 3, 4
        big_zones, big_rounds, big_legacy_rounds = 300, 60, 8
        million_target = 120_000
        budget_big_s, budget_million_s = 120.0, 120.0
    else:
        # Soak scale: 500 timed rounds x 250-frame bursts per block =
        # the 500x250 chaos-soak event volume per arm per block.
        storm_rounds, blocks, warmup = 500, 3, 10
        big_zones, big_rounds, big_legacy_rounds = 1000, 120, 10
        million_target = 1_000_000
        budget_big_s, budget_million_s = 180.0, 300.0

    golden = golden_equivalence()

    storm = bench_storm(rounds=storm_rounds, burst=250, zones=12,
                        blocks=blocks, warmup=warmup)
    storm["gate"] = ">= 10x events/s over the legacy core"
    storm["gate_passed"] = storm["ratio_median"] >= 10.0

    big = bench_big_geo(zones=big_zones, burst=500, rounds=big_rounds,
                        legacy_rounds=big_legacy_rounds)
    big["budget_s"] = budget_big_s
    big["gate"] = (f"{big_zones}-zone storm completes within "
                   f"{budget_big_s:.0f}s on the SoA core")
    big["gate_passed"] = big["soa_full"]["seconds"] <= budget_big_s

    million = bench_million(zones=big_zones, events_target=million_target,
                            burst=5000)
    million["budget_s"] = budget_million_s
    million["gate"] = (f">= {million_target} events within "
                       f"{budget_million_s:.0f}s on the SoA core")
    million["gate_passed"] = (million["events"] >= million_target
                              and million["seconds"]
                              <= budget_million_s)

    fifo_deep = bench_fifo(depth=32768, total_events=131072)
    fifo_shallow = bench_fifo(depth=250, total_events=100_000)

    summary = {
        "benchmark": "sim_core_ab",
        "legacy_arm": "runtime/sim_legacy.py (verbatim pre-paxsim "
                      "delivery machinery)",
        "methodology": (
            "core-isolated: raw-bytes sink actors so the measurement "
            "is delivery machinery, not handlers; gate workload uses "
            "alternating per-round chunks with GC disabled, warm-up "
            "discarded, median ratio over independent blocks "
            "(overload_lt calibration); both arms verified "
            "byte-identical on a reduced schedule first"),
        "smoke": bool(args.smoke),
        "golden_equivalent": golden,
        "geo_storm_soak_scale": storm,
        "geo_1000_zones" if not args.smoke else "geo_300_zones": big,
        "geo_million_event" if not args.smoke else "geo_120k_event":
            million,
        "context_fifo_deep_wave": {
            **fifo_deep,
            "note": "plain FIFO at overload-queue depth; legacy pays "
                    "an O(depth) pointer memmove per frame",
        },
        "context_fifo_shallow_wave": {
            **fifo_shallow,
            "note": "chaos-soak depth: legacy remove hits index 0; "
                    "both cores handler-bound -- the headline gate is "
                    "about storm backlogs, not shallow buffers",
        },
        "gate_passed": bool(storm["gate_passed"] and big["gate_passed"]
                            and million["gate_passed"]),
    }
    print(json.dumps({k: v for k, v in summary.items()
                      if not k.startswith("context")}, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)
            f.write("\n")
    if not summary["gate_passed"]:
        raise SystemExit(1)
    return summary


if __name__ == "__main__":
    main()

"""Reference-scale randomized protocol soak.

The reference soaks every protocol at ``runLength=250, numRuns=500``
across ``f in {1, 2}`` x config flags (e.g.
shared/src/test/scala/multipaxos/MultiPaxosTest.scala:8-42). The
regular test suite here runs the same simulators at regression-smoke
scale (15-20 runs) so CI stays fast; THIS module is the full-scale
soak, run standalone::

    python -m tests.soak --num_runs 500 --run_length 250 \
        --out bench_results/soak_summary.json

or through pytest, gated behind an env var so it never slows CI::

    FPX_SOAK=1 python -m pytest tests/soak.py -q

Each entry below is (name, factory, runs_scale) where the factory
builds a SimulatedSystem configured like one row of the reference's
soak matrix and runs_scale multiplies --num_runs (device-backed rows
run fewer: every drain pays a device call). Fixed-topology harnesses
(Scalog's 2 shards, MMP's 6 acceptors) get small subclasses threading
f=2 through their factories.
"""

from __future__ import annotations

import argparse
import json
import os
import time

# Pin JAX to local CPU XLA exactly like tests/conftest.py: the soak
# checks protocol safety, not the chip. Must happen before anything
# constructs a tracker.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402

from frankenpaxos_tpu.sim import Simulator  # noqa: E402
from tests.protocols.test_epaxos import EPaxosSimulated, make_epaxos
from tests.protocols.test_fasterpaxos import (
    FasterPaxosF1OptSimulated,
    FasterPaxosSimulated,
    make_fasterpaxos,
)
from tests.protocols.test_fastmultipaxos import (
    FastMultiPaxosSimulated,
    make_fmp,
)
from tests.protocols.test_horizontal import (
    HorizontalSimulated,
    make_horizontal,
)
from tests.protocols.test_matchmakermultipaxos import (
    make_mmp,
    MMPReconfigHeavySimulated,
    MMPSimulated,
)
from tests.protocols.test_mencius import MenciusSimulated
from tests.protocols.test_multipaxos import MultiPaxosSimulated
from tests.protocols.test_scalog import make_scalog, ScalogSimulated
from tests.protocols.test_simplebpaxos import BPaxosSimulated, make_bpaxos
from tests.protocols.test_simplegcbpaxos import (
    GcBPaxosSimulated,
    make_gc_bpaxos,
)
from tests.protocols.test_small_protocols import (
    CraqSimulated,
    UnanimousBPaxosSimulated,
)
from tests.protocols.test_vanillamencius import (
    make_vanilla,
    VanillaMenciusSimulated,
)


class EPaxosF2Simulated(EPaxosSimulated):
    def new_system(self, seed):
        transport, config, replicas, clients = make_epaxos(
            f=2, num_clients=2, seed=seed, dep_backend=self.dep_backend)
        return dict(transport=transport, replicas=replicas,
                    clients=clients, counter=0)


class BPaxosF2Simulated(BPaxosSimulated):
    def new_system(self, seed):
        transport, config, replicas, clients = make_bpaxos(
            f=2, num_clients=2, seed=seed)
        return dict(transport=transport, replicas=replicas,
                    clients=clients, counter=0)


class GcBPaxosF2Simulated(GcBPaxosSimulated):
    def make_system(self, seed):
        transport, config, proposers, acceptors, replicas, clients = \
            make_gc_bpaxos(f=2, send_gc_every_n=2, seed=seed)
        return dict(transport=transport, replicas=replicas,
                    clients=clients)


class VanillaMenciusF2Simulated(VanillaMenciusSimulated):
    def new_system(self, seed):
        transport, config, servers, clients = make_vanilla(f=2, seed=seed)
        return dict(transport=transport, servers=servers, clients=clients,
                    counter=0)


class ScalogF2Simulated(ScalogSimulated):
    def make_system(self, seed):
        transport, config, servers, aggregator, replicas, clients = \
            make_scalog(f=2, num_shards=2, num_clients=2, seed=seed)
        return dict(transport=transport, replicas=replicas,
                    clients=clients)


class HorizontalF2Simulated(HorizontalSimulated):
    def make_system(self, seed):
        transport, config, leaders, acceptors, replicas, clients = \
            make_horizontal(f=2, num_acceptors=5, seed=seed)
        return dict(transport=transport, replicas=replicas,
                    clients=clients)


class MMPF2Simulated(MMPSimulated):
    def make_system(self, seed):
        (transport, config, leaders, matchmakers, reconfigurer, acceptors,
         replicas, clients) = make_mmp(
             f=2, num_acceptors=self.NUM_ACCEPTORS,
             num_matchmakers=self.NUM_MATCHMAKERS, seed=seed)
        return dict(transport=transport, leaders=leaders,
                    matchmakers=matchmakers, reconfigurer=reconfigurer,
                    replicas=replicas, clients=clients, deaths=0)


class FasterPaxosF2Simulated(FasterPaxosSimulated):
    def make_system(self, seed):
        transport, config, servers, clients = make_fasterpaxos(
            f=2, num_clients=2, seed=seed)
        return dict(transport=transport, servers=servers, clients=clients)


class FastMultiPaxosF2Simulated(FastMultiPaxosSimulated):
    def make_system(self, seed):
        sim = make_fmp(f=2, seed=seed)
        return dict(transport=sim[0], leaders=sim[2],
                    acceptors=sim[3], clients=sim[4])


class FMPTpuQuorumsSimulated(FastMultiPaxosSimulated):
    def make_system(self, seed):
        sim = make_fmp(f=1, seed=seed, quorum_backend="tpu")
        return dict(transport=sim[0], leaders=sim[2],
                    acceptors=sim[3], clients=sim[4])


class UnanimousBPaxosF2Simulated(UnanimousBPaxosSimulated):
    F = 2
    NUM_LEADERS = 3


class CraqChain5Simulated(CraqSimulated):
    CHAIN_LEN = 5


#: The soak matrix: the multi-role protocols VERDICT r3 called out
#: (the single-decree sims already run at 500x250 in the regular suite,
#: tests/protocols/test_single_decree_sims.py).
CONFIGS: list[tuple] = [
    ("multipaxos/f1", lambda: MultiPaxosSimulated(f=1)),
    ("multipaxos/f1-groups2",
     lambda: MultiPaxosSimulated(f=1, num_acceptor_groups=2)),
    ("multipaxos/f1-grid",
     lambda: MultiPaxosSimulated(f=1, flexible=True, grid_shape=(2, 2))),
    ("multipaxos/f1-batched",
     lambda: MultiPaxosSimulated(f=1, num_batchers=2, batch_size=2)),
    ("multipaxos/f2", lambda: MultiPaxosSimulated(f=2)),
    ("mencius/f1", lambda: MenciusSimulated(f=1)),
    ("mencius/f1-groups2",
     lambda: MenciusSimulated(f=1, num_acceptor_groups=2)),
    ("mencius/f2", lambda: MenciusSimulated(f=2)),
    ("vanillamencius/f1", VanillaMenciusSimulated),
    ("vanillamencius/f2", VanillaMenciusF2Simulated),
    ("epaxos/f1", EPaxosSimulated),
    ("epaxos/f2", EPaxosF2Simulated),
    ("simplebpaxos/f1", BPaxosSimulated),
    ("simplebpaxos/f2", BPaxosF2Simulated),
    ("simplegcbpaxos/f1", GcBPaxosSimulated),
    ("simplegcbpaxos/f2", GcBPaxosF2Simulated),
    ("unanimousbpaxos/f1", UnanimousBPaxosSimulated),
    ("craq/chain3", CraqSimulated),
    ("scalog/f1", ScalogSimulated),
    ("scalog/f2", ScalogF2Simulated),
    ("horizontal/f1", HorizontalSimulated),
    ("horizontal/f2", HorizontalF2Simulated),
    ("matchmakermultipaxos/f1", MMPSimulated),
    ("matchmakermultipaxos/f1-reconfig-heavy", MMPReconfigHeavySimulated),
    ("matchmakermultipaxos/f2", MMPF2Simulated),
    ("fasterpaxos/f1", FasterPaxosSimulated),
    ("fasterpaxos/f1-opt", FasterPaxosF1OptSimulated),
    ("fasterpaxos/f2", FasterPaxosF2Simulated),
    ("fastmultipaxos/f1", FastMultiPaxosSimulated),
    ("fastmultipaxos/f2", FastMultiPaxosF2Simulated),
    ("unanimousbpaxos/f2", UnanimousBPaxosF2Simulated),
    ("craq/chain5", CraqChain5Simulated),
    # Device-backed configs at FULL scale (500x250 like every other
    # row): the TPU quorum tracker / dependency kernels under the
    # randomized interleaving exploration. The multipaxos tracker's
    # drains dispatch to the vote board and its flush timer collects
    # them: a real sim timer, so the exploration fires it at arbitrary
    # points relative to deliveries. The module-level platform pin
    # keeps every device call on local CPU XLA.
    ("multipaxos/f1-tpu-backend",
     lambda: MultiPaxosSimulated(f=1, quorum_backend="tpu")),
    ("multipaxos/f1-grid-tpu-backend",
     lambda: MultiPaxosSimulated(f=1, flexible=True, grid_shape=(2, 2),
                                 quorum_backend="tpu")),
    ("epaxos/f1-tpu-deps",
     lambda: EPaxosSimulated(dep_backend="tpu")),
    # The drain-granular run pipeline (ClientRequestArray -> Phase2aRun
    # -> ChosenRun -> ClientReplyArray), host + device trackers + grid.
    ("multipaxos/f1-coalesced",
     lambda: MultiPaxosSimulated(f=1, coalesced=True)),
    ("multipaxos/f1-coalesced-tpu",
     lambda: MultiPaxosSimulated(f=1, coalesced=True,
                                 quorum_backend="tpu")),
    ("multipaxos/f1-coalesced-grid",
     lambda: MultiPaxosSimulated(f=1, coalesced=True, flexible=True,
                                 grid_shape=(2, 2))),
    ("multipaxos/f2-coalesced",
     lambda: MultiPaxosSimulated(f=2, coalesced=True)),
    # Coalescing and per-message clients COEXISTING: the run pipeline
    # and the per-slot path interleave against the proxy leader's dual
    # pending stores under the randomized exploration.
    ("multipaxos/f1-coalesced-mixed",
     lambda: MultiPaxosSimulated(f=1, coalesced="mixed")),
]

# paxruns chaos (runs/, docs/RUN_PIPELINE.md): the dependency-set and
# quorum-spec device backends under randomized interleaving --
# EPaxos/BPaxos unions through ops/depset kernels, Fast (Multi)Paxos
# fast/classic/recovery quorums through runs/quorums.SpecChecker --
# all under the same chosen-uniqueness / exactly-once oracles as the
# host rows above.
from tests.protocols.test_single_decree_sims import FastPaxosSimulated  # noqa: E402

CONFIGS.extend([
    ("depset-chaos/epaxos-f2-tpu-deps",
     lambda: EPaxosF2Simulated(dep_backend="tpu")),
    ("depset-chaos/simplebpaxos-f1-tpu-deps",
     lambda: BPaxosSimulated(dep_backend="tpu")),
    ("fastquorum-chaos/fastpaxos-f1",
     lambda: FastPaxosSimulated()),
    ("fastquorum-chaos/fastpaxos-f1-tpu-quorums",
     lambda: FastPaxosSimulated(quorum_backend="tpu")),
    ("fastquorum-chaos/fastmultipaxos-f1-tpu-quorums",
     lambda: FMPTpuQuorumsSimulated()),
])

# The paxlog crash-restart chaos arms (wal/): randomized kill -9 +
# restart-from-WAL of acceptors/replicas interleaved with drops,
# partitions, and leader changes. Kept in their own list so
# ``--only wal`` (and the wal_chaos_soak artifact) can run exactly
# this family; run_soak covers CONFIGS + WAL_CHAOS_CONFIGS.
from tests.protocols.test_mencius_wal import MenciusWalSimulated  # noqa: E402
from tests.protocols.test_multipaxos_wal import MultiPaxosWalSimulated  # noqa: E402

WAL_CHAOS_CONFIGS: list[tuple] = [
    ("wal-chaos/multipaxos-f1",
     lambda: MultiPaxosWalSimulated(f=1)),
    ("wal-chaos/multipaxos-f1-coalesced",
     lambda: MultiPaxosWalSimulated(f=1, coalesced=True)),
    ("wal-chaos/multipaxos-f2-mixed",
     lambda: MultiPaxosWalSimulated(f=2, coalesced="mixed")),
    ("wal-chaos/mencius-groups2",
     lambda: MenciusWalSimulated(num_leader_groups=2, lag_threshold=2)),
    ("wal-chaos/mencius-coalesced",
     lambda: MenciusWalSimulated(num_leader_groups=2, lag_threshold=2,
                                 coalesced=True)),
    ("wal-chaos/mencius-coalesced-groups2x2",
     lambda: MenciusWalSimulated(num_leader_groups=2,
                                 num_acceptor_groups=2, lag_threshold=2,
                                 coalesced=True)),
]
CONFIGS.extend(WAL_CHAOS_CONFIGS)

# paxingest chaos (ingest/, docs/TRANSPORT.md): WAL-free disseminator
# kill/restart interleaved with the WAL chaos schedule -- a batcher
# death must cost client retries, never acked-write loss or duplicate
# execution (chosen-uniqueness/exactly-once oracle).
from tests.protocols.test_ingest_chaos import MultiPaxosIngestSimulated  # noqa: E402

CONFIGS.extend([
    ("ingest-chaos/multipaxos-batchers2",
     lambda: MultiPaxosIngestSimulated(f=1, num_ingest_batchers=2)),
    ("ingest-chaos/multipaxos-batchers2-coalesced",
     lambda: MultiPaxosIngestSimulated(f=1, num_ingest_batchers=2,
                                       coalesced=True)),
    ("ingest-chaos/multipaxos-f2-batchers3-mixed",
     lambda: MultiPaxosIngestSimulated(f=2, num_ingest_batchers=3,
                                       coalesced="mixed")),
    # paxfan: the 4-shard ring with a 1-run descriptor window (every
    # ship waits on an IngestCredit watermark) under the full kill x
    # partition x leader-change schedule.
    ("ingest-chaos/multipaxos-ring4-window1",
     lambda: MultiPaxosIngestSimulated(f=1, num_ingest_batchers=4,
                                       ingest_pipeline_window=1)),
])

# Live reconfiguration interleaved with the WAL chaos schedule
# (reconfig/, docs/RECONFIG.md): member swaps to fresh replacement
# acceptors mid-traffic under the same SM-prefix + chosen-uniqueness
# + exactly-once oracle.
from tests.protocols.test_protocol_reconfig import MultiPaxosReconfigSimulated  # noqa: E402

CONFIGS.extend([
    ("reconfig-chaos/multipaxos-f1",
     lambda: MultiPaxosReconfigSimulated(f=1)),
    ("reconfig-chaos/multipaxos-f1-coalesced",
     lambda: MultiPaxosReconfigSimulated(f=1, coalesced=True)),
    ("reconfig-chaos/multipaxos-f2-mixed",
     lambda: MultiPaxosReconfigSimulated(f=2, coalesced="mixed")),
])

# paxload overload chaos (serve/, docs/SERVING.md): burst load past
# the armed in-flight budget + bounded inbox, interleaved with the
# kill-restart and reconfiguration schedules above. Adds two oracles:
# acked writes are never missing from executed state, and
# control-plane frames are never refused by a bounded inbox.
from tests.protocols.test_overload_chaos import MultiPaxosOverloadSimulated  # noqa: E402

CONFIGS.extend([
    ("overload-chaos/multipaxos-f1",
     lambda: MultiPaxosOverloadSimulated(f=1)),
    ("overload-chaos/multipaxos-f1-coalesced",
     lambda: MultiPaxosOverloadSimulated(f=1, coalesced=True)),
    ("overload-chaos/multipaxos-f2-mixed",
     lambda: MultiPaxosOverloadSimulated(f=2, coalesced="mixed")),
])

# paxgeo chaos (geo/ + protocols/wpaxos, docs/GEO.md): object steals
# interleaved with link partitions, zone kills (all roles down,
# acceptors restart from WAL), and crash-restarts, under the
# chosen-uniqueness / exactly-once oracle -- the full scenario matrix
# at soak scale.
from tests.protocols.test_wpaxos import WPaxosGeoSimulated  # noqa: E402

GEO_CHAOS_CONFIGS: list[tuple] = [
    ("geo-chaos/wpaxos-z3", lambda: WPaxosGeoSimulated()),
    ("geo-chaos/wpaxos-z2-groups2",
     lambda: WPaxosGeoSimulated(num_zones=2, row_width=3,
                                num_groups=2)),
    ("geo-chaos/wpaxos-z4-wide",
     lambda: WPaxosGeoSimulated(num_zones=4, row_width=3,
                                num_groups=4)),
    ("geo-chaos/wpaxos-high-jitter",
     lambda: WPaxosGeoSimulated(jitter=4.0)),
    # paxsim size growth: the vectorized sim core (docs/SIMULATION.md)
    # makes wider geo meshes affordable at full soak scale -- these
    # two rows are the registered post-paxsim sizes (6 zones x 6
    # groups, and a 2x-depth z4 exploration via runs_scale).
    ("geo-chaos/wpaxos-z6-groups6",
     lambda: WPaxosGeoSimulated(num_zones=6, row_width=3,
                                num_groups=6)),
    ("geo-chaos/wpaxos-z4-deep",
     lambda: WPaxosGeoSimulated(num_zones=4, row_width=3,
                                num_groups=4, jitter=2.0), 2.0),
]
CONFIGS.extend(GEO_CHAOS_CONFIGS)


class WPaxosGeoStorm1000(WPaxosGeoSimulated):
    """The paxworld 1000-zone storm row: the full steal/partition/
    crash chaos schedule at planetary zone count (3000 acceptors,
    1000 leaders/replicas/clients) riding the wave engine. The
    per-command safety oracle is SAMPLED 1-in-25 (plus the run-final
    check the Simulator always performs): the full-density oracle
    scans every leader's and replica's log per command -- quadratic
    in zones, ~100x the sim's own cost at this size -- and a
    divergence still fails the run, just with a coarser minimization
    anchor. get_state returns the LAST SAMPLE between samples, so the
    step (SM-prefix-regression) oracle compares sample-to-sample --
    intermediate steps see two references to one tuple (trivially
    equal) and each fresh sample is checked against the previous one
    across the 25-command gap."""

    CHECK_EVERY = 25

    def __init__(self):
        super().__init__(num_zones=1000, row_width=3, num_groups=3,
                         jitter=2.0)
        self._checks = 0
        self._sampled = ()

    def new_system(self, seed: int):
        # The Simulator reuses ONE SimulatedSystem instance across
        # runs and minimization replays: the sampling counter and the
        # cached sample must reset per run, or run N+1's first sample
        # gets step-compared against run N's last one (a spurious
        # "SM sequence rewrote" the moment the row commits anything).
        self._checks = 0
        self._sampled = ()
        return super().new_system(seed)

    def state_invariant(self, sim):
        self._checks += 1
        if self._checks % self.CHECK_EVERY:
            return None
        return super().state_invariant(sim)

    def get_state(self, sim):
        if self._checks % self.CHECK_EVERY == 0:
            self._sampled = super().get_state(sim)
        return self._sampled


# paxworld (scenarios/, docs/GLOBAL.md): the post-ISSUE-13 geo-chaos
# growth -- deeper fault interleavings (2x chaos density per run), a
# wide high-jitter mesh, and the 1000-zone storm. Registered behind
# the existing rows so `--only geo-chaos` covers old and new alike.
GEO_CHAOS_CONFIGS.extend([
    ("geo-chaos/wpaxos-z4-chaos2x",
     lambda: WPaxosGeoSimulated(num_zones=4, row_width=3,
                                num_groups=4, jitter=2.0,
                                chaos_scale=2.0), 2.0),
    ("geo-chaos/wpaxos-z10-storm",
     lambda: WPaxosGeoSimulated(num_zones=10, row_width=3,
                                num_groups=8, jitter=2.0,
                                chaos_scale=1.5), 0.5),
    ("geo-chaos/wpaxos-z1000-storm", WPaxosGeoStorm1000, 0.004),
])
CONFIGS.extend(GEO_CHAOS_CONFIGS[-3:])


def _expand(entry, num_runs: int):
    """(name, factory[, runs_scale]) -> (name, factory, scaled runs) --
    the ONE place the optional scale element is interpreted."""
    name, factory = entry[0], entry[1]
    scale = entry[2] if len(entry) > 2 else 1.0
    return name, factory, max(1, int(num_runs * scale))


def run_soak(num_runs: int = 500, run_length: int = 250, seed: int = 0,
             only: str | None = None, out: str | None = None) -> dict:
    rows = []
    t_start = time.time()
    for entry in CONFIGS:
        name, factory, runs = _expand(entry, num_runs)
        if only and only not in name:
            continue
        t0 = time.time()
        simulator = Simulator(factory(), run_length=run_length,
                              num_runs=runs, minimize=True)
        try:
            failure = simulator.run(seed=seed)
            failure = str(failure) if failure is not None else None
        except Exception as e:  # a crash IS a soak finding, not an abort
            failure = f"crash: {type(e).__name__}: {e}"
        seconds = time.time() - t0
        # events/s = sim commands executed per wall second (system
        # construction + invariant checks included in the denominator:
        # this tracks what a soak COSTS, per config, across PRs --
        # the paxsim acceptance metric, bench_results/soak_summary.json).
        events = simulator.commands_run
        row = {
            "config": name,
            "num_runs": runs,
            "run_length": run_length,
            "seed": seed,
            "seconds": round(seconds, 1),
            "events": events,
            "events_per_s": round(events / seconds) if seconds else None,
            "failure": failure,
        }
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {
        "benchmark": "protocol_soak",
        "reference_scale":
            "shared/src/test/scala/multipaxos/MultiPaxosTest.scala:8-42 "
            "(runLength=250, numRuns=500, f in {1,2} x config flags)",
        "total_seconds": round(time.time() - t_start, 1),
        "failures": sum(1 for r in rows if r["failure"]),
        "rows": rows,
    }
    if out:
        with open(out, "w") as f:
            json.dump(summary, f, indent=2)
    return summary


@pytest.mark.skipif(not os.environ.get("FPX_SOAK"),
                    reason="full-scale soak; set FPX_SOAK=1 (takes hours)")
@pytest.mark.parametrize("entry", CONFIGS,
                         ids=[entry[0] for entry in CONFIGS])
def test_soak(entry):
    name, factory, runs = _expand(entry, 500)
    failure = Simulator(factory(), run_length=250, num_runs=runs,
                        minimize=True).run(seed=0)
    assert failure is None, f"{name}: {failure}"


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--num_runs", type=int, default=500)
    parser.add_argument("--run_length", type=int, default=250)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--only", default=None,
                        help="substring filter on config names")
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)
    summary = run_soak(args.num_runs, args.run_length, args.seed,
                       args.only, args.out)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0 if summary["failures"] == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())

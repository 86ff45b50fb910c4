"""The REAL protocol quorum path sharded over the device mesh.

Unlike ``test_multichip.py`` (which shards the synthetic device
pipeline), this shards the actual ``TpuQuorumChecker`` vote board used
by the MultiPaxos ProxyLeader -- slot axis partitioned over a
``(group, slot)`` mesh (SURVEY.md section 2.3: slot partitioning over
acceptor groups, multipaxos/DistributionScheme) -- and replays a REAL
vote stream recorded from a full MultiPaxos SimTransport run. Sharded
drain output must be bit-identical to the unsharded tracker and to the
host dict oracle on the same stream.
"""

import random

import pytest

from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
    DictQuorumTracker,
    TpuQuorumTracker,
)
from tests.protocols.multipaxos_harness import drain_and_collect


@pytest.fixture(autouse=True)
def _devices(need_8_devices):
    """All tests here need the shared 8-device mesh (conftest.py)."""


def record_real_vote_stream(num_batches: int = 12,
                            inflight: int = 16) -> tuple:
    """Run a real MultiPaxos deployment over SimTransport and capture
    every ``record()`` the ProxyLeaders' trackers see, grouped by drain.

    Returns (config, [[(slot, round, group, acceptor), ...] per drain]).
    """
    from tests.protocols.multipaxos_harness import make_multipaxos

    drains: list[list[tuple]] = []
    pending: list[tuple] = []

    class RecordingTracker(DictQuorumTracker):
        def record(self, slot, round, group_index, acceptor_index):
            pending.append((slot, round, group_index, acceptor_index))
            super().record(slot, round, group_index, acceptor_index)

        def drain(self):
            nonlocal pending
            if pending:
                drains.append(pending)
                pending = []
            return super().drain()

    sim = make_multipaxos(f=1)
    for proxy in sim.proxy_leaders:
        proxy.tracker = RecordingTracker(sim.config)
    got = []
    for batch in range(num_batches):
        for p in range(inflight):
            sim.clients[0].write(p, b"b%d.%d" % (batch, p), got.append)
        sim.transport.deliver_all_coalesced()
    assert len(got) == num_batches * inflight
    assert drains, "no vote drains captured"
    return sim.config, drains


def replay(tracker, drains) -> list:
    out = []
    for drain in drains:
        for slot, round, group, acceptor in drain:
            tracker.record(slot, round, group, acceptor)
        out.append(sorted(drain_and_collect(tracker)))
    return out


def assert_board_sharded_over(tracker, mesh) -> None:
    """The tracker's vote board is laid out over every device of
    ``mesh``, each holding its share of the slot axis, and the tracker
    launched kernels on it."""
    votes = tracker.checker.board.votes
    assert votes.sharding.device_set == set(mesh.devices.flat)
    window = votes.shape[1]
    assert {shard.data.shape for shard in votes.addressable_shards} \
        == {(votes.shape[0], window // mesh.size)}
    assert tracker.device_launches > 0


def test_sharded_checker_matches_unsharded_on_real_stream(mesh_factory):
    """2x4 (group, slot) mesh: the ProxyLeader's vote board shards its
    slot window 8 ways; per-drain chosen reports are bit-identical to
    the unsharded board and the dict oracle."""
    config, drains = record_real_vote_stream()
    oracle = replay(DictQuorumTracker(config), drains)
    unsharded_tracker = TpuQuorumTracker(config, window=1 << 10)
    unsharded = replay(unsharded_tracker, drains)
    mesh = mesh_factory(2, 4)
    sharded_tracker = TpuQuorumTracker(config, window=1 << 10, mesh=mesh)
    sharded = replay(sharded_tracker, drains)
    assert unsharded == oracle
    assert sharded == oracle
    assert sum(len(d) for d in oracle) > 0
    assert unsharded_tracker.device_launches >= len(drains)
    assert_board_sharded_over(sharded_tracker, mesh)


def test_sharded_checker_ring_wrap_on_mesh(mesh_factory):
    """Ring wrap under sharding: slots pass several multiples of the
    window, so column reclaim happens on every shard."""
    config, _ = record_real_vote_stream(num_batches=1, inflight=1)
    window = 256
    oracle = DictQuorumTracker(config)
    mesh = mesh_factory(1, 8)
    sharded = TpuQuorumTracker(config, window=window, mesh=mesh)
    rng = random.Random(7)
    for base in range(0, 4 * window, 64):
        votes = []
        for slot in range(base, base + 64):
            for acc in rng.sample(range(3), 2):
                votes.append((slot, acc))
        rng.shuffle(votes)
        for slot, acc in votes:
            oracle.record(slot, 0, 0, acc)
            sharded.record(slot, 0, 0, acc)
        assert sorted(oracle.drain()) \
            == sorted(drain_and_collect(sharded)), base
    assert_board_sharded_over(sharded, mesh)

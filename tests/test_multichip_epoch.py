"""EpochSegmentedChecker property tests UNDER SHARDING.

The epoch planes are the reconfiguration state the tentpole rule says
must ride REPLICATED over the mesh while the vote board's slot axis
shards; these tests drive the same random vote streams through the
unsharded checker, a 1x1 mesh, and a 2x4 ``(group, slot)`` mesh, and
demand bit-identity with each other and with the two-config
``quorums/systems.py`` oracle (tests/test_reconfig.py) -- across a
reconfig landing MID-WINDOW, universe shrink/grow transitions, and
permuted universe orderings. The geo steal planes (GeoQuorumTracker's
tpu backend) ride the same rule, checked against the dict oracle.
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from frankenpaxos_tpu.geo.epochs import GeoEpoch, ObjectEpochStore
from frankenpaxos_tpu.geo.quorum import GeoQuorumTracker
from frankenpaxos_tpu.ops.quorum import EpochSegmentedChecker
from frankenpaxos_tpu.quorums import SimpleMajority, ZoneGrid
from tests.test_reconfig import _random_system, TwoConfigOracle

WINDOW = 128  # must divide the 8-device mesh size

MESH_SHAPES = [None, (1, 1), (2, 4)]  # unsharded + two mesh shapes


@pytest.fixture(autouse=True)
def _devices(need_8_devices):
    """All tests here need the shared 8-device mesh (conftest.py)."""


def _checkers(mesh_factory, specs, boundaries, window=WINDOW):
    """The same checker unsharded, on 1x1, and on the 2x4 mesh."""
    return [EpochSegmentedChecker(
        specs, list(boundaries), window=window,
        mesh=None if shape is None else mesh_factory(*shape))
        for shape in MESH_SHAPES]


@pytest.mark.parametrize("seed", range(6))
def test_sharded_check_batch_matches_two_config_oracle(seed,
                                                       mesh_factory):
    """Random two-config universes (grids and majorities over random,
    PERMUTED member orderings): batch chosen-ness on every mesh shape
    matches the host oracle exactly."""
    rng = random.Random(seed)
    pool = list(range(40))
    old = _random_system(rng, pool)
    new = _random_system(rng, pool)
    boundary = rng.randrange(1, 64)
    oracle = TwoConfigOracle(old, new, boundary)

    seen: dict = {}
    union = list(old.nodes()) + list(new.nodes())
    rng.shuffle(union)  # permuted universe ordering
    for node in union:
        seen.setdefault(node, len(seen))
    universe = tuple(seen)
    specs = [old.write_spec().reindexed(universe),
             new.write_spec().reindexed(universe)]
    checkers = _checkers(mesh_factory, specs, [0, boundary])

    slots = np.asarray([rng.randrange(0, WINDOW) for _ in range(50)])
    present = np.zeros((50, len(universe)), dtype=np.uint8)
    voters = []
    for i in range(50):
        vs = rng.sample(universe, rng.randrange(0, len(universe) + 1))
        voters.append(vs)
        for v in vs:
            present[i, seen[v]] = 1
    want = [oracle.chosen(int(s), vs) for s, vs in zip(slots, voters)]
    for checker in checkers:
        assert checker.universe == universe
        assert checker.check_batch(present, slots).tolist() == want


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("direction", ["grow", "shrink"])
def test_sharded_reconfig_mid_window_matches_oracle(seed, direction,
                                                    mesh_factory):
    """A reconfig lands MID-WINDOW via ``add_epoch`` while votes are in
    flight: the board reshape (universe grows or shrinks, surviving
    columns permute) must report the same newly-chosen stream on every
    mesh shape, and every report must agree with the oracle on the
    voter set accumulated at that moment."""
    rng = random.Random(300 + seed)
    if direction == "grow":
        old = SimpleMajority(range(5))
        new = SimpleMajority(range(2, 10))
    else:
        old = SimpleMajority(range(7))
        new = SimpleMajority(range(2, 5))
    boundary = rng.randrange(6, 24)
    oracle = TwoConfigOracle(old, new, boundary)

    old_universe = tuple(sorted(old.nodes()))
    checkers = _checkers(
        mesh_factory, [old.write_spec().reindexed(old_universe)], [0])
    voters_by_slot: dict = {}
    chosen_at: dict = {}

    def feed(slot_range, universe_now):
        for _ in range(100):
            slot = rng.randrange(*slot_range)
            voter = rng.choice(universe_now)
            voters_by_slot.setdefault(slot, set()).add(voter)
            newlies = []
            for checker in checkers:
                col = checker.column_of(voter)
                newlies.append(
                    checker.record_and_check([slot], [col], [0])[0])
            # Sharded and unsharded agree on every single report.
            assert len(set(bool(n) for n in newlies)) == 1, (slot, voter)
            if newlies[0]:
                chosen_at.setdefault(slot, set(voters_by_slot[slot]))

    feed((0, boundary), list(checkers[0].universe))
    for checker in checkers:
        checker.add_epoch(new.write_spec(), boundary)
    assert (checkers[0].universe == checkers[1].universe
            == checkers[2].universe)
    feed((0, min(boundary + 30, WINDOW)), list(checkers[0].universe))

    assert chosen_at, "stream never completed a quorum"
    for slot, voters in voters_by_slot.items():
        if slot in chosen_at:
            assert oracle.chosen(slot, chosen_at[slot]), (
                slot, chosen_at[slot])
        else:
            assert not oracle.chosen(slot, voters), (slot, voters)


def test_sharded_dense_blocks_match_the_unsharded_board_and_the_oracle(
        mesh_factory):
    """``record_block`` of the epoch-segmented checker (ISSUE 38) with
    the board's slot axis sharded: blocks that cross shard edges and
    three epoch boundaries, two rounds, report what the unsharded
    checker reports and leave the same board, and every report agrees
    with the ``quorums/systems.py`` oracle (tests/test_reconfig.py)."""
    from tests.test_reconfig import BoardModel, EpochsOracle

    rng = random.Random(38)
    systems = [SimpleMajority(members) for members in
               ((0, 1, 2), (3, 4, 5), (1, 3, 5), (0, 2, 4))]
    starts = [0, 20, 45, 70]
    checkers = _checkers(mesh_factory,
                         [s.write_spec() for s in systems], starts)
    model = BoardModel(EpochsOracle(systems, starts), WINDOW)
    chosen = 0
    for start, round in ((0, 0), (30, 0), (0, 0), (30, 1), (30, 1),
                         (WINDOW + 8, 1), (WINDOW + 8, 1)):
        votes = [(rng.randrange(64), rng.randrange(6)) for _ in range(100)]
        block = np.zeros((checkers[0].num_nodes, 64), dtype=np.uint8)
        for offset, node in votes:
            block[checkers[0].column_of(node), offset] = 1
        newlies = [checker.record_block(start, block, vote_round=round)
                   for checker in checkers]
        for sharded in newlies[1:]:
            np.testing.assert_array_equal(sharded, newlies[0])
        assert {start + int(o) for o in np.flatnonzero(newlies[0])} \
            == model.block(start, votes, round)
        chosen += int(newlies[0].sum())
        for checker in checkers[1:]:
            for got, want in zip(checker.board, checkers[0].board):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
    assert chosen >= 40


def test_window_must_divide_mesh_size(mesh_factory):
    spec = SimpleMajority(range(3)).write_spec()
    with pytest.raises(ValueError, match="multiple of the mesh size"):
        EpochSegmentedChecker([spec], [0], window=100,
                              mesh=mesh_factory(2, 4))


def test_geo_tracker_sharded_matches_dict_oracle(mesh_factory):
    """GeoQuorumTracker's tpu backend over the 2x4 mesh: the ZoneGrid
    steal planes replicate, the board shards, and the drain stream is
    bit-identical to the dict oracle and the unsharded tpu backend."""
    grid = ZoneGrid([[0, 1, 2], [3, 4, 5], [6, 7, 8]])
    store = ObjectEpochStore(2, [0, 1])
    assert store.offer(GeoEpoch(group=0, epoch=1, start_slot=8,
                                home_zone=2, ballot=5)) == "new"
    trackers = [
        GeoQuorumTracker(store, 0, grid, backend="dict"),
        GeoQuorumTracker(store, 0, grid, backend="tpu", window=WINDOW),
        GeoQuorumTracker(store, 0, grid, backend="tpu", window=WINDOW,
                         mesh=mesh_factory(2, 4)),
    ]
    rng = random.Random(11)
    votes = []
    for slot in range(16):
        ballot = 0 if slot < 8 else 5
        for acceptor in rng.sample(range(9), rng.randint(1, 9)):
            votes.append((slot, ballot, acceptor))
    rng.shuffle(votes)
    outs = [[], [], []]
    for i, (slot, ballot, acceptor) in enumerate(votes):
        for t, out in zip(trackers, outs):
            t.record(slot, ballot, acceptor)
            if i % 5 == 4:
                out.extend(t.drain())
    for t, out in zip(trackers, outs):
        out.extend(t.drain())
    assert sorted(outs[0]) == sorted(outs[1]) == sorted(outs[2])
    assert outs[0], "no quorums completed"


def test_geo_tracker_sharded_steal_mid_stream(mesh_factory):
    """A steal handover lands between drains: the sharded checker's
    appended plane (replicated) keeps parity with the oracle."""
    grid = ZoneGrid([[0, 1, 2], [3, 4, 5], [6, 7, 8]])
    store = ObjectEpochStore(1, [0])
    trackers = [
        GeoQuorumTracker(store, 0, grid, backend="dict"),
        GeoQuorumTracker(store, 0, grid, backend="tpu", window=WINDOW,
                         mesh=mesh_factory(1, 8)),
    ]
    for t in trackers:
        t.record(0, 0, 0)
        t.record(0, 0, 1)
    store.offer(GeoEpoch(group=0, epoch=1, start_slot=1,
                         home_zone=1, ballot=4))
    for t in trackers:
        t.note_epochs()
        t.record(1, 4, 3)
        t.record(1, 4, 4)
    assert sorted(trackers[0].drain()) == \
        sorted(trackers[1].drain()) == [(0, 0), (1, 4)]

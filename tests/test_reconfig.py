"""paxepoch unit + property tests: the epoch store, the WAL record,
the extended-page codecs, and -- the acceptance gate -- bit-identity of
the TPU epoch-reshape kernels against a two-config
``quorums/systems.py`` oracle on non-square grids, permuted universes,
and shrink/grow transitions."""

from __future__ import annotations

import random

import numpy as np
import pytest

from frankenpaxos_tpu.ops.quorum import (
    epoch_column_map,
    EpochSegmentedChecker,
    reshape_block,
    TpuQuorumChecker,
)
from frankenpaxos_tpu.quorums import Grid, SimpleMajority
from frankenpaxos_tpu.reconfig import (
    decode_epoch_config,
    encode_epoch_config,
    EpochAck,
    EpochCommit,
    EpochConfig,
    EpochPhase2aRun,
    EpochQuorumTracker,
    EpochStore,
    Reconfigure,
)
from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
from frankenpaxos_tpu.wal import MemStorage, Wal, WalEpoch


# --- EpochStore -------------------------------------------------------------


def _store():
    return EpochStore.from_members(("a0", "a1", "a2"), f=1)


def test_epoch_store_slot_partition():
    store = _store()
    store.add(EpochConfig(epoch=1, start_slot=10, f=1,
                          members=("a0", "a1", "a3")))
    store.add(EpochConfig(epoch=2, start_slot=25, f=1,
                          members=("a1", "a3", "a4")))
    assert store.epoch_of_slot(0).epoch == 0
    assert store.epoch_of_slot(9).epoch == 0
    assert store.epoch_of_slot(10).epoch == 1
    assert store.epoch_of_slot(24).epoch == 1
    assert store.epoch_of_slot(10 ** 9).epoch == 2
    assert [c.epoch for c in store.epochs_covering(0)] == [0, 1, 2]
    assert [c.epoch for c in store.epochs_covering(10)] == [1, 2]
    assert [c.epoch for c in store.epochs_covering(25)] == [2]
    assert [c.epoch for c in store.epochs_covering(11)] == [1, 2]
    # Universe ids are first-seen stable.
    assert store.all_members() == ("a0", "a1", "a2", "a3", "a4")
    assert store.column_of("a3") == 3
    assert store.column_of("nobody") is None


def test_epoch_store_offer_round_monotone():
    store = _store()
    c1a = EpochConfig(epoch=1, start_slot=10, f=1,
                      members=("a0", "a1", "a3"))
    c1b = EpochConfig(epoch=1, start_slot=12, f=1,
                      members=("a0", "a2", "a4"))
    assert store.offer(c1a, round=3) == "new"
    assert store.offer(c1a, round=3) == "dup"
    assert store.offer(c1b, round=2) == "stale"     # lower round
    assert store.offer(c1b, round=5) == "replaced"  # newest superseded
    assert store.current().members == ("a0", "a2", "a4")
    assert store.round_of(1) == 5
    # Non-contiguous epochs wait for the gap's resend.
    c3 = EpochConfig(epoch=3, start_slot=40, f=1,
                     members=("a0", "a2", "a4"))
    assert store.offer(c3, round=9) == "stale"
    # A non-newest epoch is never replaced.
    store.offer(EpochConfig(epoch=2, start_slot=20, f=1,
                            members=("a0", "a2", "a5")), round=6)
    assert store.offer(EpochConfig(epoch=1, start_slot=12, f=1,
                                   members=("a7", "a8", "a9")),
                       round=99) == "stale"


def test_epoch_store_validation():
    with pytest.raises(ValueError):
        EpochConfig(epoch=1, start_slot=0, f=1, members=("a", "b"))
    with pytest.raises(ValueError):
        EpochConfig(epoch=1, start_slot=0, f=1, members=("a", "a", "b"))
    store = _store()
    with pytest.raises(ValueError):  # start slot regression
        store.offer(EpochConfig(epoch=1, start_slot=-5, f=1,
                                members=("a0", "a1", "a3")), 0)


# --- wire + WAL -------------------------------------------------------------


def test_extended_page_codecs_round_trip():
    for message in (
            Reconfigure(members=("x", ("10.0.0.7", 80), "z")),
            EpochCommit(epoch=3, start_slot=999, f=2, round=7,
                        members=tuple(f"m{i}" for i in range(5))),
            EpochAck(epoch=3, round=7)):
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] == 0  # the extended page escape
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_epoch_phase2a_run_codec_round_trip():
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        Command,
        CommandBatch,
        CommandId,
        NOOP,
    )

    batch = CommandBatch((Command(CommandId(("h", 1), 0, 4), b"p"),))
    run = EpochPhase2aRun(epoch=2, start_slot=17, round=1,
                          values=(batch, NOOP))
    got = DEFAULT_SERIALIZER.from_bytes(DEFAULT_SERIALIZER.to_bytes(run))
    assert (got.epoch, got.start_slot, got.round) == (2, 17, 1)
    assert tuple(got.values) == (batch, NOOP)


def test_wal_epoch_record_survives_recovery():
    storage = MemStorage()
    wal = Wal(storage)
    payload = encode_epoch_config(1, 64, 1, 3,
                                  ("a0", ("10.0.0.2", 9001), "a3"))
    wal.append(WalEpoch(payload=payload))
    wal.sync()
    recovered = Wal(storage).recover()
    assert recovered == [WalEpoch(payload=payload)]
    assert decode_epoch_config(recovered[0].payload) == (
        1, 64, 1, 3, ("a0", ("10.0.0.2", 9001), "a3"))


# --- the two-config oracle --------------------------------------------------


def _random_system(rng, universe_pool):
    """A random quorum system over a random (permuted) universe drawn
    from ``universe_pool`` -- majorities and non-square grids."""
    if rng.random() < 0.5:
        n = rng.choice([3, 5, 7])
        members = rng.sample(universe_pool, n)
        return SimpleMajority(members)
    rows = rng.choice([2, 3])
    cols = rng.choice([2, 3, 4])
    cells = rng.sample(universe_pool, rows * cols)
    return Grid([cells[r * cols:(r + 1) * cols] for r in range(rows)])


class EpochsOracle:
    """Any number of epochs of ``quorums/systems.py`` systems (the
    authority): a slot's system is the last whose start is at or below
    it."""

    def __init__(self, systems, starts):
        self.systems, self.starts = systems, starts

    def system_of(self, slot):
        at = max(k for k, start in enumerate(self.starts) if start <= slot)
        return self.systems[at]

    def chosen(self, slot, voters) -> bool:
        system = self.system_of(slot)
        return system.is_superset_of_write_quorum(
            set(voters) & set(system.nodes()))


class TwoConfigOracle(EpochsOracle):
    """slot < boundary: old system's write quorums; else the new's."""

    def __init__(self, old, new, boundary):
        super().__init__([old, new], [float("-inf"), boundary])


@pytest.mark.parametrize("seed", range(8))
def test_epoch_segmented_check_batch_matches_two_config_oracle(seed):
    rng = random.Random(seed)
    pool = list(range(40))
    old = _random_system(rng, pool)
    new = _random_system(rng, pool)
    boundary = rng.randrange(1, 64)
    oracle = TwoConfigOracle(old, new, boundary)

    # The union-universe store view: reindex both write specs.
    seen: dict = {}
    for node in tuple(sorted(old.nodes())) + tuple(sorted(new.nodes())):
        seen.setdefault(node, len(seen))
    universe = tuple(seen)
    specs = [old.write_spec().reindexed(universe),
             new.write_spec().reindexed(universe)]
    checker = EpochSegmentedChecker(specs, [0, boundary], window=256)
    assert checker.universe == universe

    slots = np.asarray([rng.randrange(0, 128) for _ in range(50)])
    present = np.zeros((50, len(universe)), dtype=np.uint8)
    voters = []
    for i in range(50):
        vs = rng.sample(universe, rng.randrange(0, len(universe) + 1))
        voters.append(vs)
        for v in vs:
            present[i, seen[v]] = 1
    got = checker.check_batch(present, slots)
    want = [oracle.chosen(int(s), vs) for s, vs in zip(slots, voters)]
    assert got.tolist() == want


@pytest.mark.parametrize("seed", range(8))
def test_epoch_segmented_record_and_check_matches_oracle(seed):
    """Stateful scatter across the handover boundary: cumulative
    chosen-ness per slot must match the oracle on the accumulated voter
    sets, including votes recorded BEFORE the new epoch was added
    (the board reshape must preserve them)."""
    rng = random.Random(100 + seed)
    pool = list(range(30))
    old = _random_system(rng, pool)
    new = _random_system(rng, pool)
    boundary = rng.randrange(4, 40)
    oracle = TwoConfigOracle(old, new, boundary)

    old_universe = tuple(sorted(old.nodes()))
    checker = EpochSegmentedChecker([old.write_spec().reindexed(
        old_universe)], [0], window=128)
    voters_by_slot: dict = {}
    chosen_at: dict = {}

    def feed(slot_range, universe_now):
        for _ in range(60):
            slot = rng.randrange(*slot_range)
            voter = rng.choice(universe_now)
            voters_by_slot.setdefault(slot, set()).add(voter)
            col = checker.column_of(voter)
            newly = checker.record_and_check([slot], [col], [0])
            if newly[0]:
                chosen_at.setdefault(slot, set(voters_by_slot[slot]))

    feed((0, boundary), list(checker.universe))
    # Handover: the new epoch arrives mid-collection; the board
    # reshapes in place (pad/shrink + permutation).
    checker.add_epoch(new.write_spec(), boundary)
    feed((0, boundary + 30), list(checker.universe))

    for slot, voters in voters_by_slot.items():
        relevant = voters
        if slot in chosen_at:
            # Chosen is sticky on the board; the oracle must agree it
            # was chosen at the moment the kernel said so.
            assert oracle.chosen(slot, chosen_at[slot]), (
                slot, chosen_at[slot])
        else:
            assert not oracle.chosen(slot, relevant), (slot, relevant)


@pytest.mark.parametrize("seed", range(6))
def test_tpu_checker_reshape_matches_fresh_board(seed):
    """TpuQuorumChecker.reshape: votes recorded before the reshape for
    SURVIVING acceptors keep counting, exactly as if replayed onto a
    fresh new-universe board."""
    rng = random.Random(200 + seed)
    pool = list(range(24))
    old = _random_system(rng, pool)
    new = _random_system(rng, pool)
    old_spec = old.write_spec()
    new_spec = new.write_spec()

    checker = TpuQuorumChecker(old_spec, window=64)
    fresh = TpuQuorumChecker(new_spec, window=64)
    pre = [(rng.randrange(0, 48), rng.choice(old_spec.universe))
           for _ in range(40)]
    for slot, voter in pre:
        checker.record_and_check([slot], [old_spec.column_of(voter)])
    checker.reshape(new_spec)
    # Replay the pre-reshape votes of SURVIVING acceptors onto the
    # fresh new-universe board (dropped acceptors lose their columns).
    for slot, voter in pre:
        if voter in new_spec.universe:
            fresh.record_and_check([slot], [new_spec.column_of(voter)])
    post = [(rng.randrange(0, 48), rng.choice(new_spec.universe))
            for _ in range(40)]
    for slot, voter in post:
        checker.record_and_check([slot], [new_spec.column_of(voter)])
        fresh.record_and_check([slot], [new_spec.column_of(voter)])
    # Bit-identical chosen state... except slots already chosen under
    # the OLD spec stay sticky on the reshaped board (chosen is
    # slot-axis state); mask those out.
    pre_board = np.asarray(checker.board.votes)
    fresh_board = np.asarray(fresh.board.votes)
    assert pre_board.shape == fresh_board.shape
    touched = sorted({s for s, _ in pre} | {s for s, _ in post})
    for slot in touched:
        np.testing.assert_array_equal(pre_board[:, slot % 64],
                                      fresh_board[:, slot % 64])


def test_epoch_column_map_and_reshape_block():
    cmap = epoch_column_map((5, 9, 2), (2, 9, 7, 5))
    assert cmap.tolist() == [2, 1, -1, 0]
    block = np.asarray([[1, 0], [1, 1], [0, 1]], dtype=np.uint8)
    got = reshape_block(block, (5, 9, 2), (2, 9, 7, 5))
    assert got.tolist() == [[0, 1], [1, 1], [0, 0], [1, 0]]


# --- the epoch tracker ------------------------------------------------------


@pytest.mark.parametrize("seed", range(4))
def test_epoch_tracker_backends_agree(seed):
    """dict (oracle semantics) and tpu (segmented board) backends
    report the same chosen (slot, round)s, exactly once, across a
    membership change."""
    rng = random.Random(300 + seed)
    members0 = ("a0", "a1", "a2")
    members1 = ("a0", "a1", "a3")
    boundary = rng.randrange(4, 30)

    def build():
        store = EpochStore.from_members(members0, f=1)
        return store

    stores = {b: build() for b in ("dict", "tpu")}
    trackers = {b: EpochQuorumTracker(stores[b], backend=b, window=128)
                for b in ("dict", "tpu")}
    reported = {b: [] for b in trackers}

    def drain_all():
        for b, t in trackers.items():
            reported[b].extend(t.drain())

    # Watermark-bounded handover invariant: slots >= boundary receive
    # votes only once the epoch exists (the leader buffers proposals
    # through activation), so pre-switch events stay below it.
    switched = False
    for i in range(120):
        if not switched and i == 60:
            for b in trackers:
                stores[b].add(EpochConfig(
                    epoch=1, start_slot=boundary, f=1,
                    members=members1))
                trackers[b].note_epochs()
            switched = True
        slot = rng.randrange(0, 60 if switched else boundary)
        voter = rng.choice(("a0", "a1", "a2", "a3", "stranger"))
        for t in trackers.values():
            t.record(slot, 0, voter)
        if rng.random() < 0.3:
            drain_all()
    drain_all()
    # Exactly-once + equality (order may differ between backends).
    for b, got in reported.items():
        assert len(got) == len(set(got)), (b, got)
    assert set(reported["dict"]) == set(reported["tpu"])


# --- the dense epoch kernel (ISSUE 38) ---------------------------------------


class BoardModel:
    """What a vote board holds, on the host: per column its owner slot,
    round, voters and whether the owner was reported chosen."""

    def __init__(self, oracle, window):
        self.oracle, self.window = oracle, window
        self.columns: dict = {}

    def block(self, start, votes, round) -> set:
        """Apply one block's ``(offset, node)`` votes; the slots it
        newly chose."""
        touched = set()
        for offset, node in votes:
            slot = start + offset
            state = self.columns.setdefault(
                slot % self.window, [-1, -1, set(), False])
            if slot < state[0]:
                continue                       # the ring moved past it
            if slot > state[0]:
                state[:] = [slot, -1, set(), False]
            if round > state[1]:
                state[1], state[2] = round, set()
            if round == state[1]:
                state[2].add(node)
            touched.add(slot % self.window)
        newly = set()
        for column in touched:
            slot, _, voters, chosen = self.columns[column]
            if not chosen and self.oracle.chosen(slot, voters):
                self.columns[column][3] = True
                newly.add(slot)
        return newly


def _majorities(rng, pool, planes, spacing):
    """``planes`` epochs of 3 of ``pool``, ``spacing`` slots apart."""
    systems = [SimpleMajority(rng.sample(pool, 3)) for _ in range(planes)]
    return systems, [k * spacing for k in range(planes)]


def _checker_pair(systems, starts, window):
    return [EpochSegmentedChecker([s.write_spec() for s in systems],
                                  starts, window=window) for _ in range(2)]


def _same_boards(dense, sparse) -> None:
    for field, a, b in zip(dense.board._fields, dense.board, sparse.board):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), field)


def _record_both(dense, sparse, model, start, width, votes, round=0):
    """One block into the dense kernel, its votes into the scatter, and
    both against the model: ``newly`` and the boards, bit for bit."""
    block = np.zeros((dense.num_nodes, width), dtype=np.uint8)
    for offset, node in votes:
        block[dense.column_of(node), offset] = 1
    newly = dense.record_block(start, block, vote_round=round)
    rows, offsets = np.nonzero(block)
    per_vote = sparse.record_and_check(
        start + offsets, rows, np.full(rows.size, round, dtype=np.int32))
    from_scatter = np.zeros(width, dtype=bool)
    from_scatter[offsets[per_vote]] = True
    np.testing.assert_array_equal(newly, from_scatter)
    assert {start + int(o) for o in np.flatnonzero(newly)} \
        == model.block(start, votes, round)
    _same_boards(dense, sparse)
    return newly


def _spanning(boundaries_inside):
    """A 64-column block at slot 100 over epochs that start at the given
    offsets into it; a last epoch far above it, so that the universe
    holds acceptors that are members of none of the block's epochs."""
    def case(rng):
        pool = list(range(6))
        starts = [0] + [100 + b for b in boundaries_inside] + [5000]
        systems = [SimpleMajority(rng.sample(pool, 3)) for _ in starts]
        systems[-1] = SimpleMajority([3, 4, 5])
        systems[0] = SimpleMajority([0, 1, 2])
        return systems, starts, pool, [(100, 64, 0)] * 4
    return case


def _preempting(rng):
    """Round 0 half-votes, then a round-1 block over the middle of them,
    then round 0 again (stale there, live beside it)."""
    pool = list(range(6))
    systems, starts = _majorities(rng, pool, 4, 40)
    systems[0], systems[1] = (SimpleMajority([0, 1, 2]),
                              SimpleMajority([3, 4, 5]))
    return systems, starts, pool, [(64, 64, 0), (80, 32, 1), (64, 64, 0),
                                   (64, 64, 1), (64, 64, 1)]


def _reclaiming(rng):
    """The ring wraps onto columns that hold half-voted and chosen slots
    of earlier epochs; a straggler block for the old slots comes last."""
    pool = list(range(6))
    systems, starts = _majorities(rng, pool, 9, 60)
    systems[0], systems[1] = (SimpleMajority([0, 1, 2]),
                              SimpleMajority([3, 4, 5]))
    return systems, starts, pool, [(0, 64, 0), (0, 64, 0), (256 + 16, 64, 0),
                                   (256 + 16, 64, 0), (0, 64, 0),
                                   (512, 64, 0), (512, 64, 0)]


DENSE_CASES = {
    "inside-one-epoch": _spanning([]),
    "one-boundary": _spanning([31]),
    "three-boundaries": _spanning([5, 6, 40]),
    "boundary-at-first-and-last-column": _spanning([0, 63]),
    "epochs-that-govern-no-slot": _spanning([20, 20, 20, 41]),
    "newer-round-preempts-mid-block": _preempting,
    "column-reclaimed-by-the-ring": _reclaiming,
}


# The reclaiming case ends with a straggler, which the checker flags.
@pytest.mark.filterwarnings("ignore:EpochSegmentedChecker. vote for slot")
@pytest.mark.parametrize("case", DENSE_CASES)
@pytest.mark.parametrize("seed", [0, 1])
def test_dense_epoch_kernel_equals_the_oracle_and_the_scatter(case, seed):
    """``record_block`` of the epoch-segmented checker: every column is
    judged under its own slot's epoch, non-members' votes (any of the
    pool votes anywhere) complete nothing, and the board comes out as
    the scatter leaves it."""
    rng = random.Random(f"{case}-{seed}")
    systems, starts, pool, blocks = DENSE_CASES[case](rng)
    window = 256
    dense, sparse = _checker_pair(systems, starts, window)
    model = BoardModel(EpochsOracle(systems, starts), window)
    chosen = 0
    for start, width, round in blocks:
        votes = [(rng.randrange(width), rng.choice(pool))
                 for _ in range(2 * width)]
        chosen += int(_record_both(dense, sparse, model, start, width,
                                   votes, round).sum())
    assert chosen >= 16       # the case decides something


def test_dense_epoch_kernel_leaves_padding_columns_untouched():
    """A 40-column block is padded to the 64 bucket: the 24 columns
    beyond it, which hold other slots' votes, rounds and owners, come
    out as they went in."""
    rng = random.Random(38)
    pool = list(range(6))
    systems, starts = _majorities(rng, pool, 3, 50)
    systems[0], systems[1] = (SimpleMajority([0, 1, 2]),
                              SimpleMajority([3, 4, 5]))
    dense, sparse = _checker_pair(systems, starts, 256)
    model = BoardModel(EpochsOracle(systems, starts), 256)
    beyond = [(offset, rng.choice(pool)) for offset in range(24)]
    _record_both(dense, sparse, model, 80, 24, beyond, round=3)
    before = [np.asarray(plane)[..., 80:104] for plane in dense.board]
    votes = [(rng.randrange(40), rng.choice(pool)) for _ in range(80)]
    _record_both(dense, sparse, model, 40, 40, votes, round=0)
    for plane, was in zip(dense.board, before):
        np.testing.assert_array_equal(np.asarray(plane)[..., 80:104], was)


@pytest.mark.parametrize("planes", [1, 29, 128])
def test_dense_epoch_kernel_at_any_number_of_planes(planes):
    """K real planes of the 128 a stack is allocated by: one of them,
    the cell's 29, and all of them (no padding plane left)."""
    rng = random.Random(planes)
    pool = list(range(6))
    systems, starts = _majorities(rng, pool, planes, 24)
    systems[0] = SimpleMajority([0, 1, 2])
    if planes > 1:
        systems[1] = SimpleMajority([3, 4, 5])
    else:
        pool = [0, 1, 2]
    window = 4096
    dense, sparse = _checker_pair(systems, starts, window)
    assert dense._masks.shape[0] == 128
    model = BoardModel(EpochsOracle(systems, starts), window)
    chosen = 0
    for start in range(0, min(24 * planes, window - 256), 200):
        for _ in range(2):
            votes = [(rng.randrange(256), rng.choice(pool))
                     for _ in range(400)]
            chosen += int(_record_both(dense, sparse, model, start, 256,
                                       votes).sum())
    assert chosen >= 100

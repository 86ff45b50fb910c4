"""TpuQuorumChecker vs. the host oracle, including round preemption and GC."""

import itertools
import logging
import random
import re

import numpy as np
import pytest

from frankenpaxos_tpu.ops.quorum import (
    MultiConfigQuorumChecker,
    TpuQuorumChecker,
)
from frankenpaxos_tpu.quorums import Grid, SimpleMajority, UnanimousWrites
from tests.protocols.multipaxos_harness import drain_and_collect


def test_check_batch_matches_oracle():
    qs = Grid([[0, 1, 2], [3, 4, 5]])
    spec = qs.write_spec()
    subsets = [set(c) for r in range(7)
               for c in itertools.combinations(range(6), r)]
    present = np.stack([spec.present_vector(s) for s in subsets])
    checker = TpuQuorumChecker(spec, window=8)
    got = checker.check_batch(present)
    expected = spec.evaluate(present)
    np.testing.assert_array_equal(got, expected)


def test_record_and_check_simple_majority():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=16)
    # Two votes for slot 5 in round 0: second one completes the majority.
    newly = checker.record_and_check([5, 5], [0, 1], [0, 0])
    # Both batch entries see post-batch state: quorum reached.
    assert newly.any()
    # Re-voting an already-chosen slot doesn't report it again.
    newly = checker.record_and_check([5], [2], [0])
    assert not newly.any()
    # A different slot is independent.
    newly = checker.record_and_check([6], [0], [0])
    assert not newly.any()
    newly = checker.record_and_check([6], [2], [0])
    assert newly.any()


def test_round_preemption_clears_votes():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=16)
    assert not checker.record_and_check([3], [0], [0]).any()
    # A vote in a higher round wipes the round-0 vote: still no quorum.
    assert not checker.record_and_check([3], [1], [5]).any()
    # An old-round vote is discarded.
    assert not checker.record_and_check([3], [2], [0]).any()
    # Second vote in round 5 completes the quorum.
    assert checker.record_and_check([3], [0], [5]).any()


def test_release_recycles_rows():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=4)
    assert checker.record_and_check([1, 1], [0, 1], [0, 0]).any()
    checker.release([1])
    # Slot 5 maps to the same ring row; it must start clean.
    assert not checker.record_and_check([5], [0], [0]).any()
    assert checker.record_and_check([5], [1], [0]).any()


def test_randomized_against_host_oracle():
    """Random vote streams: device chosen-set == host replay."""
    rng = random.Random(1234)
    qs = Grid([[0, 1], [2, 3]])
    spec = qs.write_spec()
    window = 32
    checker = TpuQuorumChecker(spec, window=window)

    host_rounds = {}  # slot -> round
    host_votes = {}   # slot -> set of cols
    host_chosen = set()

    for _ in range(30):
        batch = max(1, rng.randrange(8))
        slots = [rng.randrange(window) for _ in range(batch)]
        cols = [rng.randrange(4) for _ in range(batch)]
        rounds = [rng.randrange(3) for _ in range(batch)]
        newly = checker.record_and_check(slots, cols, rounds)
        # Host replay with identical semantics (batch max-round first).
        batch_round = {}
        for s, r in zip(slots, rounds):
            batch_round[s] = max(batch_round.get(s, -1), r)
        for s, r in batch_round.items():
            if r > host_rounds.get(s, -1):
                host_rounds[s] = r
                host_votes[s] = set()
        for s, c, r in zip(slots, cols, rounds):
            if r == host_rounds.get(s, -1):
                host_votes.setdefault(s, set()).add(c)
        newly_host = set()
        for s in set(slots):
            if s not in host_chosen and spec.check(host_votes.get(s, set())):
                newly_host.add(s)
                host_chosen.add(s)
        got = {s for s, n in zip(slots, newly) if n}
        assert got == newly_host, (got, newly_host)


def test_padding_invalid_entries_ignored():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=8)
    newly = checker.record_and_check([2], [0], [0], pad_to=64)
    assert newly.shape == (1,)
    assert not newly.any()
    # The padded (slot 0, node 0, round 0) lanes must not have voted:
    # nodes 1 and 2 alone must be what completes the majority for slot 0.
    state = np.asarray(checker.board.votes)
    assert state[0, 0] == 0
    assert checker.record_and_check([0, 0], [1, 2], [0, 0]).any()


def test_record_block_dense_path():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=64)
    # Acceptors 0 and 1 vote for slots [8, 16); acceptor 2 silent.
    block = np.zeros((3, 8), dtype=np.uint8)
    block[0, :] = 1
    block[1, :4] = 1
    newly = checker.record_block(8, block)
    np.testing.assert_array_equal(newly, [True] * 4 + [False] * 4)
    # Acceptor 2 completes the rest; first 4 not re-reported.
    block2 = np.zeros((3, 8), dtype=np.uint8)
    block2[2, :] = 1
    newly = checker.record_block(8, block2)
    np.testing.assert_array_equal(newly, [False] * 4 + [True] * 4)


def test_record_block_round_preemption():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=64)
    block = np.zeros((3, 4), dtype=np.uint8)
    block[0, :] = 1
    assert not checker.record_block(0, block, vote_round=0).any()
    # Higher round clears acceptor 0's round-0 votes.
    block1 = np.zeros((3, 4), dtype=np.uint8)
    block1[1, :] = 1
    assert not checker.record_block(0, block1, vote_round=2).any()
    # Stale round-0 votes are ignored.
    block2 = np.zeros((3, 4), dtype=np.uint8)
    block2[2, :] = 1
    assert not checker.record_block(0, block2, vote_round=0).any()
    # Completing round 2 chooses.
    assert checker.record_block(0, block, vote_round=2).all()


def test_record_block_mixed_with_sparse():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=64)
    block = np.zeros((3, 8), dtype=np.uint8)
    block[0, :] = 1
    assert not checker.record_block(16, block).any()
    # Straggler vote via the sparse path completes slot 20 only.
    newly = checker.record_and_check([20], [1], [0])
    assert newly.all()


def test_record_block_straddle_rejected():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=16)
    with pytest.raises(ValueError):
        checker.record_block(12, np.zeros((3, 8), dtype=np.uint8))
    with pytest.raises(ValueError):
        checker.record_block(0, np.zeros((2, 8), dtype=np.uint8))


def test_multi_config_checker():
    universe = tuple(range(6))
    grid = Grid([[0, 1, 2], [3, 4, 5]])
    maj = SimpleMajority([0, 1, 2, 3, 4])
    una = UnanimousWrites([0, 1, 2])
    specs = [grid.write_spec().reindexed(universe),
             maj.write_spec().reindexed(universe),
             una.write_spec().reindexed(universe)]
    checker = MultiConfigQuorumChecker(specs)

    rng = random.Random(9)
    rows, cfgs, expected = [], [], []
    for _ in range(200):
        xs = {i for i in range(6) if rng.random() < 0.5}
        k = rng.randrange(3)
        rows.append(specs[k].present_vector(xs))
        cfgs.append(k)
        expected.append(specs[k].check(xs))
    got = checker.check_batch(np.stack(rows), np.array(cfgs))
    np.testing.assert_array_equal(got, np.array(expected))


def test_window_violation_counter():
    """ADVICE r3: a straggler vote trailing the frontier by >= window is
    silently droppable on device -- the checker must surface it."""
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=16)
    checker.record_and_check([40], [0], [0])
    assert checker.window_violations == 0
    # Slot 40 - 16 = 24 is the lowest safe slot; 20 trails by >= window.
    with pytest.warns(RuntimeWarning, match="trails the frontier"):
        checker.record_and_check([20], [1], [0])
    assert checker.window_violations == 1
    # Subsequent violations count without re-warning.
    checker.record_and_check([21], [1], [0])
    assert checker.window_violations == 2
    # In-window stragglers are fine.
    checker.record_and_check([30], [1], [0])
    assert checker.window_violations == 2


def test_window_violation_counter_dense_path():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=64)
    block = np.ones((3, 4), dtype=np.uint8)
    checker.record_block(200, block)
    with pytest.warns(RuntimeWarning):
        checker.record_block(128, block)
    assert checker.window_violations == 1


def test_window_violation_intra_batch_and_rejected_block():
    qs = SimpleMajority([0, 1, 2])
    checker = TpuQuorumChecker(qs.write_spec(), window=16)
    # Two same-batch slots >= window apart alias one column: flagged
    # even with a fresh frontier.
    with pytest.warns(RuntimeWarning):
        checker.record_and_check([36, 20], [0, 1], [0, 0])
    assert checker.window_violations == 1

    # A rejected (ring-straddling) block must NOT advance the frontier.
    checker2 = TpuQuorumChecker(qs.write_spec(), window=16)
    with pytest.raises(ValueError, match="straddles"):
        checker2.record_block(1000, np.ones((3, 10), dtype=np.uint8))
    checker2.record_and_check([990], [0], [0])
    assert checker2.window_violations == 0


# --- fused grid kernel ------------------------------------------------------
# _spec_statics tags grid specs so every kernel (record_block,
# record_and_check, check_batch) swaps the generic mask
# matmul for the boolean reshape col-OR/row-AND (write) / col-AND/row-OR
# (read) reduction. Bit-identity to the quorums/systems.py host oracle
# is the contract.


GRIDS = [
    Grid([[0, 1, 2], [3, 4, 5]]),        # non-square 2x3
    Grid([[0, 1], [2, 3], [4, 5]]),      # non-square 3x2
    Grid([[0, 2, 4], [1, 3, 5]]),        # interleaved universe (perm)
    Grid([[7, 8], [9, 10]]),             # square, offset ids
]


def test_spec_statics_detects_grids():
    from frankenpaxos_tpu.ops.quorum import _spec_statics

    for qs in GRIDS:
        for spec in (qs.write_spec(), qs.read_spec()):
            _, meta = _spec_statics(spec)
            assert meta[2] is not None, (qs, spec.combine)
    # Non-grid predicates keep the generic matmul...
    for spec in (SimpleMajority(range(5)).write_spec(),
                 UnanimousWrites(range(3)).read_spec()):
        _, meta = _spec_statics(spec)
        assert meta[2] is None
    # ...except degenerate grids: UnanimousWrites' write spec (all n of
    # one group, ANY) IS a 1xN grid-read predicate; detection keeps it
    # bit-identical, so taking the fused path is correct.
    spec = UnanimousWrites(range(3)).write_spec()
    _, meta = _spec_statics(spec)
    assert meta[2] == ("read", 1, 3, None)
    checker = TpuQuorumChecker(spec, window=64)
    blocks = np.array([[1, 1, 0], [1, 1, 1], [0, 0, 0], [1, 0, 1]],
                      dtype=np.uint8)
    np.testing.assert_array_equal(checker.check_batch(blocks),
                                  spec.evaluate(blocks))


@pytest.mark.parametrize("qs", GRIDS, ids=["2x3", "3x2", "perm", "2x2"])
def test_fused_grid_check_block_matches_oracle(qs):
    rng = np.random.default_rng(3)
    for spec in (qs.write_spec(), qs.read_spec()):
        checker = TpuQuorumChecker(spec, window=1 << 9)
        for width in (1, 7, 64, 100):
            block = (rng.random((spec.num_nodes, width)) < 0.5
                     ).astype(np.uint8)
            got = checker.check_batch(block.T)
            np.testing.assert_array_equal(got, spec.evaluate(block.T),
                                          err_msg=f"{qs} {spec.combine}")


@pytest.mark.parametrize("qs", GRIDS, ids=["2x3", "3x2", "perm", "2x2"])
def test_fused_grid_record_paths_match_oracle(qs):
    """The stateful dense + sparse paths under the fused predicate:
    accumulated votes across drains report exactly what the host oracle
    reports."""
    rng = np.random.default_rng(7)
    spec = qs.write_spec()
    checker = TpuQuorumChecker(spec, window=1 << 9)
    n = spec.num_nodes
    host = np.zeros((n, 64), dtype=np.uint8)
    chosen = np.zeros(64, dtype=bool)
    for _ in range(6):
        arrivals = (rng.random((n, 64)) < 0.3).astype(np.uint8)
        newly = checker.record_block(0, arrivals)
        host |= arrivals
        hit = spec.evaluate(host.T)
        expected_newly = hit & ~chosen
        np.testing.assert_array_equal(newly, expected_newly)
        chosen |= hit
    # Sparse stragglers on top of the same board.
    slots = rng.integers(0, 64, size=20)
    nodes = rng.integers(0, n, size=20)
    newly = checker.record_and_check(slots, nodes)
    for s, node in zip(slots, nodes):
        host[node, s] = 1
    hit = spec.evaluate(host.T)
    for i, s in enumerate(slots):
        if newly[i]:
            assert hit[s] and not chosen[s]


def test_fused_grid_pipeline_step_matches_generic():
    """bench/pipeline.steady_state_step commits identically with the
    fused grid reduction and with the generic mask matmul (the fused
    path forced off by patching detection)."""
    import jax.numpy as jnp

    import frankenpaxos_tpu.ops.quorum as quorum_ops
    from frankenpaxos_tpu.bench.pipeline import make_state, steady_state_step

    spec = Grid([[0, 1, 2], [3, 4, 5]]).write_spec()
    masks, thresholds, combine_any = spec.as_arrays()

    def run(patched):
        orig = quorum_ops.grid_layout
        if patched:
            quorum_ops.grid_layout = lambda *a, **k: None
        try:
            state = make_state(1 << 9, 6)
            for t in range(6):
                state = steady_state_step(
                    state, jnp.int32(t), block_size=1 << 7, masks=masks,
                    thresholds=thresholds, combine_any=combine_any)
        finally:
            quorum_ops.grid_layout = orig
        return state

    fused, generic = run(False), run(True)
    assert int(fused.committed) == int(generic.committed) > 0
    np.testing.assert_array_equal(np.asarray(fused.chosen),
                                  np.asarray(generic.chosen))
    np.testing.assert_array_equal(np.asarray(fused.sm_state),
                                  np.asarray(generic.sm_state))


# --- how a drain's inputs reach the device --------------------------------

#: Not a power of two and used by no other test, so the programs below
#: are compiled here and not found in the process's jit cache.
TRACKER_WINDOW = 3 * 4096


def _tracker_config(grid: bool):
    from frankenpaxos_tpu.deploy import get_protocol

    protocol = get_protocol("multipaxos")
    ports = iter(range(20000, 21000))

    def port():
        return ["127.0.0.1", next(ports)]

    raw = protocol.cluster(1, port)
    if grid:
        raw["flexible"] = True
        raw["acceptors"] = [[port() for _ in range(3)] for _ in range(2)]
    return protocol.load_config(raw)


class _TrackerAndOracle:
    """A ``TpuQuorumTracker`` fed vote for vote beside the dict oracle;
    a drain collects at once, and says how many jitted calls it made."""

    def __init__(self, grid: bool, window: int = TRACKER_WINDOW):
        from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
            DictQuorumTracker,
            TpuQuorumTracker,
        )

        config = _tracker_config(grid)
        self.tracker = TpuQuorumTracker(config, window=window)
        self.oracle = DictQuorumTracker(config)
        # A write quorum: f+1 of the one group, or one of each grid row.
        self.quorum = ((0, 0), (1, 0)) if grid else ((0, 0), (0, 1))

    def vote(self, slots, round: int = 0, voters=None) -> None:
        for slot in slots:
            for group, index in voters or self.quorum:
                self.tracker.record(slot, round, group, index)
                self.oracle.record(slot, round, group, index)

    def drain(self) -> int:
        """Drain both, hold the tracker to the oracle's chosen list, and
        return the number of launches the drain made."""
        before = (self.tracker.device_drains, self.tracker.device_launches)
        got = drain_and_collect(self.tracker)
        assert sorted(got) == sorted(self.oracle.drain())
        assert self.tracker.device_drains == before[0] + 1
        return self.tracker.device_launches - before[1]


class _CompiledNames(logging.Handler):
    """The names of the programs ``jax.log_compiles()`` reports."""

    def __init__(self):
        super().__init__()
        self.names: list = []

    def emit(self, record) -> None:
        found = re.match(r"Compiling (?:jit\()?([\w.\-]+)",
                         record.getMessage())
        if found:
            self.names.append(found.group(1))


@pytest.mark.parametrize("grid", [False, True], ids=["majority", "grid2x3"])
def test_tracker_compiles_only_its_named_programs_and_none_after_prewarm(
        grid):
    """A drain hands the device host buffers in one jitted call: no eager
    ``jnp`` operation (each a compiled ``convert_element_type`` or
    ``broadcast_in_dim`` of its own) on the way, and after the prewarm
    nothing compiles at all, whatever shape the drain has."""
    import jax

    handler = _CompiledNames()
    logger = logging.getLogger("jax")
    logger.addHandler(handler)
    try:
        with jax.log_compiles():
            both = _TrackerAndOracle(grid)
            prewarm = list(handler.names)
            del handler.names[:]
            window = both.tracker.checker.window
            # Every dense bucket: 64, 256, 1024, 4096 columns.
            for start, width in ((0, 40), (100, 200), (400, 800),
                                 (1300, 3000)):
                both.vote(range(start, start + width))
                assert both.drain() == 1
            # A ring straddle, two rounds in one drain, a sparse tail.
            both.vote(range(window - 64, window + 64))
            assert both.drain() == 2
            both.vote(range(window + 100, window + 140), round=1)
            both.vote(range(window + 200, window + 206), round=2)
            assert both.drain() == 2
            both.vote(range(window + 1000, window + 2000, 10), round=1)
            assert both.drain() == 1
    finally:
        logger.removeHandler(handler)
    # The board, four widths of record_block, two of the scatter, the
    # release: eight, and the stateless check that went is not one.
    assert sorted(prewarm) == sorted(
        ["fpx_quorum_make_board"] + 4 * ["fpx_quorum_record_block"]
        + 2 * ["fpx_quorum_record_votes"] + ["fpx_quorum_release"]), prewarm
    assert "fpx_quorum_check_block" not in prewarm
    assert handler.names == []


@pytest.mark.parametrize("grid", [False, True], ids=["majority", "grid2x3"])
def test_tracker_counts_one_launch_a_dense_drain_and_two_a_ring_straddle(
        grid):
    both = _TrackerAndOracle(grid, window=4096)
    tracker = both.tracker
    assert (tracker.device_drains, tracker.device_launches) == (0, 0)
    # Single round, inside one bucket: one block, one launch; the votes
    # below quorum stay on the board for the next drain.
    both.vote(range(0, 30))
    both.vote(range(30, 50), voters=both.quorum[:1])
    assert both.drain() == 1
    both.vote(range(30, 50), voters=both.quorum[1:])
    assert both.drain() == 1
    # The ring's end inside the block: one launch on each side of it.
    both.vote(range(4096 - 20, 4096 + 20))
    assert both.drain() == 2
    assert (tracker.device_drains, tracker.device_launches) == (3, 4)
    assert tracker.checker.window_violations == 0


@pytest.mark.parametrize("width", [1, 5, 12, 13, 64])
def test_record_block_scalars_survive_the_one_host_buffer(width):
    """The block's ring offset, slot number and round ride below its votes
    as bytes (``_stage_block``), one more row from 12 columns up and
    several below: a slot near 2^31 and a round of four different bytes
    come out of the kernel as they went in, at any width (the prewarm's
    round -1 goes the same way in every tracker test)."""
    window = 4096
    checker = TpuQuorumChecker(SimpleMajority(range(3)).write_spec(),
                               window=window)
    start_slot = 2**31 - 1 - window + 7      # column 6 of a late ring
    block = np.zeros((3, width), dtype=np.uint8)
    vote_round = 0x01020304
    block[0] = 1
    assert not checker.record_block(start_slot, block, vote_round).any()
    block[:] = 0
    block[2] = 1
    assert checker.record_block(start_slot, block, vote_round).all()
    columns = slice(start_slot % window, start_slot % window + width)
    np.testing.assert_array_equal(
        np.asarray(checker.board.owner)[columns],
        start_slot + np.arange(width))
    assert (np.asarray(checker.board.rounds)[columns] == vote_round).all()
    assert checker.window_violations == 0


# What TpuQuorumTracker did with the drains of ``_planned_drains`` at the
# commit before the plan moved into ``BoardDrainPlanner`` (824ed9c): per
# drain its launches in order, how many (slot, round)s it reported, the
# CRC-32 of that list as int64 pairs, its first three and its last.
# A block is (start slot, width, round, votes, CRC-32 of the block); a
# scatter (votes, pad_to, first slot, last slot, CRC-32 of slots, columns
# and rounds).
_PLANNED = [
    ("dense",
     [("block", 0, 1024, 0, 600, 2157296486)],
     300, 3794490453, [(0, 0), (1, 0), (2, 0)], [(299, 0)]),
    ("clustered",
     [("block", 20000, 256, 0, 200, 1375499983),
      ("block", 26000, 64, 0, 101, 3804446436)],
     150, 1745212135, [(20000, 0), (20001, 0), (20002, 0)], [(26049, 0)]),
    ("multi-round",
     [("votes", 1, 64, 26055, 26055, 1348293691),
      ("block", 26050, 1024, 1, 900, 3284542904),
      ("votes", 2, 64, 26060, 26060, 372353089)],
     450, 1215162172, [(26055, 0), (26050, 1), (26051, 1)], [(26499, 1)]),
    ("straddling",
     [("block", 32068, 256, 1, 512, 3304015762),
      ("block", 32324, 256, 1, 512, 3304015762),
      ("block", 32580, 64, 1, 128, 2086209108),
      ("block", 32644, 64, 1, 128, 2086209108),
      ("votes", 120, 256, 32708, 32767, 3757369263),
      ("block", 32768, 1024, 1, 660, 3303458141)],
     1030, 1803197219, [(32068, 1), (32069, 1), (32070, 1)], [(33097, 1)]),
    ("sparse tail",
     [("block", 40600, 256, 1, 200, 1375499983),
      ("votes", 256, 256, 34000, 36040, 4234635308),
      ("votes", 44, 64, 36048, 36392, 2027371706)],
     100, 4230709098, [(40600, 1), (40601, 1), (40602, 1)], [(40699, 1)]),
    ("re-acks",
     [("block", 40600, 256, 1, 100, 2717574356),
      ("votes", 256, 256, 34000, 36040, 1954974375),
      ("votes", 44, 64, 36048, 36392, 3423951963)],
     300, 3775700345, [(34000, 1), (34008, 1), (34016, 1)], [(36392, 1)]),
]


def _planned_drains(t, window: int):
    """Record one drain's votes on ``t``, then yield its name."""
    # Two acceptors' ranges, one round.
    t.record_range(0, 300, 0, 0, 0)
    t.record_range(0, 300, 0, 0, 1)
    yield "dense"
    # Two runs 6000 slots apart, packed arrays and single votes; and one
    # of the two round-0 votes of slot 26055.
    for acceptor in (0, 2):
        t.record_votes(np.arange(20000, 20100), np.zeros(100, np.int32),
                       0, acceptor)
    for slot in range(26000, 26050):
        t.record(slot, 0, 0, 1)
        t.record(slot, 0, 0, 2)
    t.record(26055, 0, 0, 0)
    yield "clustered"
    # Three rounds: the older round's quorum completes, the dominant
    # round's block covers its slot, a newer round's votes come last.
    t.record_range(26050, 26500, 1, 0, 0)
    t.record_range(26050, 26500, 1, 0, 1)
    t.record(26055, 0, 0, 2)
    t.record(26060, 2, 0, 0)
    t.record(26060, 2, 0, 1)
    yield "multi-round"
    # A run across the ring's end.
    for acceptor in (1, 2):
        t.record_range(2 * window - 700, 2 * window + 330, 1, 0, acceptor)
    yield "straddling"
    # A thin cluster, every eighth slot, beside a dense run.
    t.record_votes(np.arange(34000, 36400, 8), np.ones(300, np.int32), 0, 0)
    t.record_range(40600, 40700, 1, 0, 0)
    t.record_range(40600, 40700, 1, 0, 2)
    yield "sparse tail"
    # The thin cluster's second votes, and a re-ack of what is chosen.
    t.record_votes(np.arange(34000, 36400, 8), np.ones(300, np.int32), 0, 1)
    t.record_range(40600, 40700, 1, 0, 1)
    yield "re-acks"


def test_the_shared_planner_launches_and_reports_as_the_tracker_did():
    """``TpuQuorumTracker`` over ``BoardDrainPlanner`` (ISSUE 38) makes,
    for dense, clustered, multi-round, straddling and sparse drains, the
    launches the tracker made when the plan was its own, argument for
    argument, and reports the same (slot, round)s in the same order."""
    import zlib

    from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
        TpuQuorumTracker,
    )

    window = 1 << 14
    t = TpuQuorumTracker(_tracker_config(False), window=window)
    calls: list = []
    block = t.checker.record_block_async
    votes = t.checker.record_and_check_async

    def noting_block(start_slot, b, vote_round=0):
        calls.append(("block", int(start_slot), b.shape[1],
                      int(vote_round), int(np.count_nonzero(b)),
                      zlib.crc32(np.ascontiguousarray(b).tobytes())))
        return block(start_slot, b, vote_round=vote_round)

    def noting_votes(slots, cols, rounds=None, pad_to=None):
        calls.append(("votes", len(slots), pad_to, int(slots[0]),
                      int(slots[-1]),
                      zlib.crc32(np.asarray(slots, np.int64).tobytes()
                                 + np.asarray(cols, np.int32).tobytes()
                                 + np.asarray(rounds, np.int32).tobytes())))
        return votes(slots, cols, rounds, pad_to=pad_to)

    t.checker.record_block_async = noting_block
    t.checker.record_and_check_async = noting_votes
    got = []
    for name in _planned_drains(t, window):
        del calls[:]
        reported = drain_and_collect(t)
        pairs = np.asarray(reported, np.int64).reshape(-1, 2)
        got.append((name, list(calls), len(reported),
                    zlib.crc32(pairs.tobytes()), reported[:3],
                    reported[-1:]))
    assert got == _PLANNED
    assert (t.device_launches, t.device_votes, t.device_drains,
            t.checker.window_violations) == (18, 4764, 6, 0)

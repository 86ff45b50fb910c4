"""Strided votes on the device vote board: ``record_range`` with a
stride, as a Mencius leader group's runs and noop ranges arrive, held to
a plain oracle, and planned as one dense block."""

import numpy as np
import pytest

from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
    group_quorum_spec,
    TpuQuorumTracker,
)
from tests.protocols.multipaxos_harness import drain_and_collect

GROUPS = 3
ROW = 3
THRESHOLD = 2


def board(window: int) -> TpuQuorumTracker:
    return TpuQuorumTracker(
        None, window=window, row_size=ROW,
        spec=group_quorum_spec(GROUPS, ROW, THRESHOLD))


class Oracle:
    """(slot, round) -> the (group, index) pairs that voted, and each
    reported once when ``THRESHOLD`` of one group are in."""

    def __init__(self):
        self.votes: dict = {}
        self.done: set = set()
        self.newly: list = []

    def record_range(self, start, end, rnd, group, index, stride):
        for slot in range(start, end, stride):
            key = (slot, rnd)
            if key in self.done:
                continue
            voters = self.votes.setdefault(key, set())
            voters.add((group, index))
            if sum(g == group for g, _ in voters) >= THRESHOLD:
                self.done.add(key)
                self.newly.append(key)

    def drain(self) -> list:
        newly, self.newly = self.newly, []
        return newly


@pytest.mark.parametrize("stride", [1, 2, 3])
def test_strided_ranges_across_the_ring_end_match_the_oracle(stride):
    window = 256
    tracker, oracle = board(window), Oracle()
    rng = np.random.default_rng(stride)
    start = window - 40 * stride  # the ranges cross the ring's end
    for drain in range(6):
        for group in range(GROUPS):
            first = start + group
            end = first + 30 * stride
            for index in rng.permutation(ROW)[:rng.integers(1, ROW + 1)]:
                for target in (tracker, oracle):
                    target.record_range(first, end, 0, group, int(index),
                                        stride)
        got = drain_and_collect(tracker)
        assert sorted(got) == sorted(oracle.drain()), drain
        start += 25 * stride
    assert tracker.checker.window_violations == 0
    assert oracle.done


def test_a_foreign_groups_votes_choose_nothing():
    """Votes of another group's acceptors never complete a quorum, even
    f+1 of them: a slot's row is its own group's."""
    tracker = board(256)
    tracker.record_range(0, 30, 0, 0, 0, 3)
    tracker.record_range(0, 30, 0, 1, 0, 3)
    tracker.record_range(0, 30, 0, 2, 1, 3)
    assert drain_and_collect(tracker) == []
    tracker.record_range(0, 30, 0, 0, 2, 3)
    assert sorted(drain_and_collect(tracker)) == [
        (slot, 0) for slot in range(0, 30, 3)]


@pytest.mark.parametrize("stride,voters", [(3, 1), (3, 2), (5, 1)])
def test_a_strided_run_is_planned_dense(stride, voters):
    """One group's run of 300 slots at its stride, voted by ``voters``
    acceptors: one dense launch, whatever share of the span the stride
    leaves empty (a stride-5 run from one acceptor fills a fifth)."""
    tracker = board(1 << 12)
    for index in range(voters):
        tracker.record_range(0, 300 * stride, 0, 0, index, stride)
    got = drain_and_collect(tracker)
    assert tracker.device_launches == 1
    assert tracker.dense_votes == tracker.device_votes == 300 * voters
    assert len(got) == (300 if voters >= THRESHOLD else 0)


def test_interleaved_runs_of_three_groups_are_one_launch():
    """A drain that holds every group's run (and a noop range's votes)
    over one span: one dense block, every slot decided."""
    tracker = board(1 << 12)
    for group in range(GROUPS):
        for index in (0, 1):
            tracker.record_range(group, group + 3 * 200, 0, group, index, 3)
    got = drain_and_collect(tracker)
    assert tracker.device_launches == 1
    assert tracker.dense_votes == tracker.device_votes == 2 * 3 * 200
    assert sorted(got) == [(slot, 0) for slot in range(600)]

"""The Mencius cell end to end through ``benchmark/run.py`` at toy size
on the CPU: three leader groups' strided runs and noop ranges all
counted on the chip owner's board, the read-back ordered through the
log, the reference holding every slot to its own group's quorum, and
the planted ``mencius_foreign_vote`` beside it refused."""

import json
import os

from bench_util import derive, FAULTS, REPO, run_cell
import pytest

CELL = "mencius3.saturated"


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return derive(str(tmp_path_factory.mktemp("toy")), toy=True)


@pytest.fixture(scope="module")
def traced(toy):
    return run_cell(toy, CELL, trace=1)


def test_every_vote_is_on_the_board_and_lagging_groups_skip(traced):
    code, result, errors = traced
    assert code == 0, errors[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(value == 0 for value, _ in result["compared"].values())
    # What the Mencius reference adds to multipaxos_kv's numbers.
    assert {"votes_from_other_groups", "log_holes", "slots_not_chosen",
            "slot_kinds_differ", "chosen_early"} <= set(result["compared"])
    metrics = {name: reading["value"]
               for name, reading in result["metrics"].items()}
    assert 0 < metrics["mencius.noop_slot_share_pct"] < 100
    assert metrics["mencius.votes_per_drain"] > 0
    assert 1 <= metrics["mencius.launches_per_drain"] < 2
    assert metrics["mencius.dense_vote_share_pct"] > 50
    for stage in ("drain", "collect", "fan-out"):
        assert metrics[f"stage.share_pct.proxy_leader.{stage}"] > 0, stage


def test_the_records_hold_every_group_and_every_slot(traced):
    import numpy as np

    code, _, errors = traced
    assert code == 0, errors[-3000:]
    records = os.path.join(REPO, ".bench_runs", CELL, "records")
    labels = sorted(name[:-len(".json")] for name in os.listdir(records)
                    if name.endswith(".json") and "." not in name[:-5])
    assert len(labels) == 18
    with open(os.path.join(records, "proxy_leader_0_1.json")) as f:
        owner = json.load(f)
    assert [t["board_shape"] for t in owner["trackers"]] == [
        [9, 4096], [9, 4096]]
    groups = set()
    for k in range(2):
        with np.load(os.path.join(records,
                                  f"proxy_leader_0_1.tracker{k}.npz")) as t:
            votes = t["votes"]
        # Every vote is its own slot's group's.
        assert (votes[:, 4] == votes[:, 1] % 3).all()
        groups |= set(votes[:, 4].tolist())
    assert groups == {0, 1, 2}
    with np.load(os.path.join(records, "replica_0.replica.npz")) as log:
        kinds = log["slot_kinds"]
    assert (kinds >= 0).all() and (kinds == 0).any() and (kinds == 1).any()


def test_a_board_that_counts_another_groups_vote_is_refused(
        tmp_path_factory):
    """The control: ``mencius_foreign_vote.py`` in the role entry's
    place. Slots are reported chosen after one vote of their own group,
    and only ``chosen_early`` says so."""
    planted = derive(str(tmp_path_factory.mktemp("foreign")), toy=True,
                     role_entry=os.path.join(FAULTS,
                                             "mencius_foreign_vote.py"))
    code, result, errors = run_cell(planted, CELL)
    assert code == 0, errors[-3000:]
    assert result["correct"] is False
    over = {name for name, (value, limit) in result["compared"].items()
            if value > limit}
    assert over == {"chosen_early"}, result["compared"]
    assert set(result["offenders"]) == over
    assert "offender chosen_early: " + json.dumps(
        result["offenders"]["chosen_early"][0]) in errors

"""The cell whose commands are batched into slots, end to end through
``benchmark/run.py`` at toy size on the CPU: the batchers carry the
window's writes, slots hold several commands each, the reference judges
the log of those slots command by command, and the planted
``batch_lost_command`` beside it is refused."""

import json
import os

from bench_util import derive, FAULTS, REPO, run_cell
import pytest

CELL = "batched.saturated"


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return derive(str(tmp_path_factory.mktemp("toy")), toy=True)


@pytest.fixture(scope="module")
def traced(toy):
    return run_cell(toy, CELL, trace=1)


def test_the_batchers_carry_the_window_and_slots_hold_several(traced):
    code, result, errors = traced
    assert code == 0, errors[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(value == 0 for value, _ in result["compared"].values())
    metrics = {name: reading["value"]
               for name, reading in result["metrics"].items()}
    # Every write of the window reached its slot through a batcher.
    assert metrics["batcher.batched_share_pct"] > 90
    assert metrics["batcher.commands_per_batch"] > 1
    assert metrics["role.cpu_pct.batcher"] > 0
    for stage in ("batch", "flush", "loop-wait"):
        assert metrics[f"stage.share_pct.batcher.{stage}"] > 0, stage
    assert metrics["stage.mean_ms.saturated.batcher.hold-client-wait"] > 0
    # The proxy leaders count a slot's votes, not its commands.
    assert metrics["tracker.votes_per_drain"] > 0


def test_the_records_show_two_batchers_and_slots_of_several_commands(
        traced):
    code, _, errors = traced
    assert code == 0, errors[-3000:]
    records = os.path.join(REPO, ".bench_runs", CELL, "records")
    labels = sorted(name[:-len(".json")] for name in os.listdir(records)
                    if name.endswith(".json") and "." not in name[:-5])
    assert [label for label in labels if label.startswith("batcher")] == [
        "batcher_0", "batcher_1"]
    assert len(labels) == 10
    with open(os.path.join(records, "proxy_leader_0_1.json")) as f:
        owner = json.load(f)
    chosen = sum(tracker["reported"] for tracker in owner["trackers"])
    with open(os.path.join(records, "replica_0.json")) as f:
        replica = json.load(f)
    # Far fewer slots were chosen than writes were executed.
    assert 0 < chosen < replica_executed(records) / 2
    assert replica["stores"]


def replica_executed(records: str) -> int:
    import numpy as np

    with np.load(os.path.join(records, "replica_0.replica.npz")) as log:
        return len(log["keys"])


def test_a_replica_that_drops_the_last_command_of_a_batch_is_refused(
        tmp_path_factory):
    """The control: ``batch_lost_command.py`` in the role entry's
    place."""
    planted = derive(str(tmp_path_factory.mktemp("lost")), toy=True,
                     role_entry=os.path.join(FAULTS,
                                             "batch_lost_command.py"))
    code, result, errors = run_cell(planted, CELL)
    assert code == 0, errors[-3000:]
    assert result["correct"] is False
    over = {name for name, (value, limit) in result["compared"].items()
            if value > limit}
    assert "replica_writes_lost" in over, result["compared"]
    assert set(result["offenders"]) == over
    for name in over:
        assert f"offender {name}: " + json.dumps(
            result["offenders"][name][0]) in errors
    # Nothing else is broken: the fault fails its own numbers only.
    assert over <= {"replica_writes_lost", "replica_store_wrong",
                    "reads_wrong"}, result["compared"]

"""The cell whose acceptor set is replaced under load, end to end through
``benchmark/run.py`` at toy size on the CPU (a reconfiguration every half
second, so that a two-second window holds several), the planted
``stale_epoch_quorum`` beside it, and the deployment's own pieces: the
draw, the seed, and the check that refuses a program whose epoch board is
cut short."""

import json
import os
import random

from bench_util import BENCHMARK, derive, FAULTS, run_cell
from harness.manifest import load_module
import pytest

CELL = "reconfig.saturated"
deployment = load_module(os.path.join(BENCHMARK, "deployments",
                                      "multipaxos_reconfig.py"))


def every_half_second(derived: dict) -> None:
    """The toy copy of the cell's configuration with a reconfiguration
    every 0.5 s and the guarantee stated for the toy window: 3 epochs
    activated in 2 s."""
    for entry in derived["configs"]:
        with open(entry["file"]) as f:
            config = json.load(f)
        if "reconfigure" not in config:
            continue
        config["reconfigure"]["period_s"] = 0.5
        config["guarantees"]["epochs_activated_in_window_at_least"] = 3
        config["guarantees"]["window_s"] = 2.0
        with open(entry["file"], "w") as f:
            json.dump(config, f)


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return derive(str(tmp_path_factory.mktemp("toy")), toy=True,
                  change=every_half_second)


@pytest.fixture(scope="module")
def traced(toy):
    return run_cell(toy, CELL, trace=1)


def test_the_cell_runs_at_toy_size_and_its_epochs_activate(traced):
    code, result, errors = traced
    assert code == 0, errors[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "offender " not in errors
    compared = result["compared"]
    for name in ("chosen_early", "chosen_twice", "epoch_overlap",
                 "epoch_activated_without_predecessor_quorum",
                 "too_few_epochs"):
        assert compared[name] == [0, 0], name
    metrics = {name: reading["value"]
               for name, reading in result["metrics"].items()}
    assert metrics["reconfig.epochs_in_window"] >= 3
    # K grows by one a reconfiguration, those before the window included.
    assert metrics["reconfig.planes"] > metrics["reconfig.epochs_in_window"]
    assert metrics["reconfig.handover_ms"] > 0
    assert metrics["reconfig.votes_per_launch"] >= 1
    assert metrics["stage.share_pct.proxy_leader.epoch-drain"] > 0
    assert metrics["stage.mean_ms.proxy_leader.epoch-drain"] > 0


def test_the_records_hold_the_epochs_and_the_trackers_that_took_over(traced):
    code, _, errors = traced
    assert code == 0, errors[-3000:]
    records = os.path.join(os.path.dirname(BENCHMARK), ".bench_runs", CELL,
                           "records")
    with open(os.path.join(records, "proxy_leader_0_1.json")) as f:
        owner = json.load(f)
    plain = [t for t in owner["trackers"] if t.get("kind") != "epoch"]
    epoch = [t for t in owner["trackers"] if t.get("kind") == "epoch"]
    assert len(plain) == 2 and len(epoch) == 2
    assert sorted(t["predecessor"] for t in epoch) == [0, 1]
    for tracker in epoch:
        # The toy window, whole: the epoch board is as wide as the other.
        assert tracker["board_shape"][1] == plain[0]["board_shape"][1]
        assert tracker["board_shape"][0] == 8    # rows come in eights
        assert tracker["planes"] >= 4 and tracker["votes"] > 0
    events = []
    for leader in ("leader_0", "leader_1"):
        with open(os.path.join(records, leader + ".json")) as f:
            events += json.load(f)["epoch_events"]
    kinds = [event[0] for event in events]
    assert kinds.count("activated") >= 3
    assert kinds.count("define") >= kinds.count("activated")
    with open(os.path.join(records, "reconfigurer.json")) as f:
        sent = json.load(f)["sent"]
    # One a period, on the monotonic clock, none twice the same set.
    assert len(sent) >= kinds.count("define")
    due = [s["due_mono_s"] for s in sent]
    assert all(abs(b - a - 0.5) < 1e-9 for a, b in zip(due, due[1:]))
    assert all(a["pool_indices"] != b["pool_indices"]
               for a, b in zip(sent, sent[1:]))
    assert all(s["mono_s"] >= s["due_mono_s"] for s in sent)
    for label in (f"acceptor_{n}.json" for n in range(6)):
        assert os.path.exists(os.path.join(records, label))


def test_a_quorum_of_the_previous_epochs_members_is_refused(
        tmp_path_factory):
    """The control: ``stale_epoch_quorum.py`` in the role entry's place."""
    planted = derive(str(tmp_path_factory.mktemp("stale")), toy=True,
                     role_entry=os.path.join(FAULTS, "stale_epoch_quorum.py"),
                     change=every_half_second)
    code, result, errors = run_cell(planted, CELL)
    assert code == 0, errors[-3000:]
    assert result["correct"] is False
    assert result["compared"]["chosen_early"][0] > 0
    assert result["offenders"]["chosen_early"]
    assert "offender chosen_early" in errors


def test_a_draw_is_uniform_over_the_subsets_and_never_the_current_set():
    rng = random.Random(37)
    current, seen = (0, 1, 2), {}
    for _ in range(4000):
        drawn = deployment.draw(rng, 6, 3, current)
        assert drawn != current and len(set(drawn)) == 3
        assert all(0 <= place < 6 for place in drawn)
        seen[drawn] = seen.get(drawn, 0) + 1
        current = drawn
    assert len(seen) == 20               # 6 choose 3
    assert min(seen.values()) > 120 and max(seen.values()) < 290


def test_the_seed_is_the_launchers_own():
    assert deployment.seed_of(["--workload", "x", "--seed", "2147483659",
                               "--seconds", "2"]) == 2147483659
    assert deployment.seed_of(["--seed=7", "--trace", "1"]) == 7
    assert deployment.seed_of([]) == 0


def cell_config() -> dict:
    with open(os.path.join(BENCHMARK, "configs",
                           "mp_f1_majority_reconfig.json")) as f:
        return json.load(f)


def test_the_program_gives_its_epoch_tracker_the_configured_window():
    deployment.require_the_epoch_board_is_whole(cell_config())


def test_a_program_that_cuts_the_epoch_board_short_is_refused(monkeypatch):
    """What the parent of this cell's PR did: ``min(tpu_window, 1 << 14)``."""
    from frankenpaxos_tpu.protocols.multipaxos import proxy_leader

    ensure = proxy_leader.ProxyLeader._ensure_epoch_tracker

    def capped(self):
        import dataclasses

        self.options = dataclasses.replace(
            self.options, tpu_window=min(self.options.tpu_window, 1 << 14))
        ensure(self)

    monkeypatch.setattr(proxy_leader.ProxyLeader, "_ensure_epoch_tracker",
                        capped)
    with pytest.raises(SystemExit, match="16384.*1048576"):
        deployment.require_the_epoch_board_is_whole(cell_config())
    # A configuration whose window the cut does not reach passes.
    small = cell_config()
    small["options"]["tpu_window"] = "4096"
    deployment.require_the_epoch_board_is_whole(small)

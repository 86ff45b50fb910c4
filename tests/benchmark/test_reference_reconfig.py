"""The reconfigured deployment's plain reference on records made by hand:
a sound run compares clean, and each number it adds (and each of
``multipaxos_kv``'s that it adds to) fires alone on the one record that
breaks it."""

import json
import os

from bench_util import BENCHMARK
from harness import role_entry, role_entry_reconfig
from harness.manifest import load_module
import numpy as np
import pytest

CONFIG = os.path.join(BENCHMARK, "configs", "mp_f1_majority_reconfig.json")
reference = load_module(os.path.join(BENCHMARK, "reference",
                                     "multipaxos_reconfig.py"))
POOL = [["127.0.0.1", 7000 + n] for n in range(6)]
PROXY = ["127.0.0.1", 7100]
#: Members of epochs 0-3, as places in the pool: between them all six.
MEMBERS = [(0, 1, 2), (1, 2, 3), (2, 3, 4), (0, 4, 5)]
EPOCHS = {"epoch_overlap", "epoch_activated_without_predecessor_quorum",
          "too_few_epochs"}


def wid(generator: int, loop: int, count: int) -> int:
    return generator << 56 | loop << 40 | count


def sound_run(edit=None):
    """The records of one sound run, made by hand. Two generators of four
    loops write one shared key for two seconds; the system orders each
    write at an instant between its issue and its answer, a slot a write
    after the launcher's probe in slot 0. The acceptor set is replaced
    three times, at a quarter, a half and three quarters of the log; two
    members of a slot's epoch vote for it. The single-epoch tracker counts
    the slots below the first boundary, but for the last of them, whose
    second vote reaches the epoch tracker that took over: that one reports
    it, and every slot after. ``edit(records)`` may break it."""
    with open(CONFIG) as f:
        config = json.load(f)
    rng = np.random.default_rng(7)
    ops = []
    for generator in range(2):
        for loop in range(4):
            at, count = 1000.0 + rng.uniform(0, 0.01), 0
            while at < 1002.0:
                answered = at + rng.uniform(0.002, 0.010)
                ops.append([generator, at, answered, 0, 0,
                            wid(generator, loop, count),
                            rng.uniform(at, answered)])
                at, count = answered + 1e-4, count + 1
    closed = max(op[2] for op in ops) + 0.01
    log = [op[5] for op in sorted(ops, key=lambda op: op[6])]
    ops += [[generator, closed, closed + 0.02, 1, 0, log[-1], closed + 0.01]
            for generator in range(2)]
    generators = []
    for generator in range(2):
        mine = [op for op in ops if op[0] == generator]
        generators.append({
            "info": {"index": generator, "keys": ["0"],
                     "end_mono_s": 1002.0, "gave_up": 0,
                     "wall_minus_mono_s": [1.7e9, 1.7e9]},
            "ops": {"issue_mono_s": np.array([op[1] for op in mine]),
                    "latency_s": np.array([op[2] - op[1] for op in mine]),
                    "kind": np.array([op[3] for op in mine], dtype=np.int8),
                    "key": np.array([op[4] for op in mine], dtype=np.int32),
                    "value": np.array([op[5] for op in mine],
                                      dtype=np.int64)}})
    end = len(log) + 1
    values = ["0"] + [f"{value:016x}" for value in log]

    def replica() -> dict:
        return {"record": {"claimed": False, "trackers": [],
                           "key_names": ["0", "probe"],
                           "stores": [{"probe": "0", "0": values[-1]}]},
                "replica": {"keys": np.array([1] + [0] * len(log),
                                             dtype=np.int32),
                            "values": np.array(values, dtype="S16")},
                "trackers": []}

    starts = [0, end // 4, end // 2, 3 * end // 4]

    def voters_of(slot: int) -> tuple:
        members = MEMBERS[sum(slot >= start for start in starts) - 1]
        return members[slot % 3], members[(slot + 1) % 3]

    plain, later, voter_ids = [], [], {}

    def by_address(slot: int, place: int) -> tuple:
        voter = voter_ids.setdefault(place, len(voter_ids))
        return (slot, slot + 1, 0, voter)

    for slot in range(end):
        first, second = voters_of(slot)
        if slot < starts[1] - 1:
            plain += [(slot, slot + 1, 0, 0, first),
                      (slot, slot + 1, 0, 0, second),
                      np.asarray([(slot, 0)])]
        elif slot == starts[1] - 1:      # one vote each side of the switch
            plain.append((slot, slot + 1, 0, 0, first))
            later += [by_address(slot, second), np.asarray([(slot, 0)])]
        else:
            later += [by_address(slot, first), by_address(slot, second),
                      np.asarray([(slot, 0)])]
    votes, reports = role_entry.expand(np, plain)
    epoch_votes, epoch_reports = role_entry_reconfig.expand(np, later)
    window = config["board"]["window"]
    cluster = [POOL[:3]]
    owner = {
        "record": {"claimed": True, "cluster_acceptors": cluster,
                   "epoch_events": [], "trackers": [
                       {"window_violations": 0, "board_shape": [3, window]},
                       {"kind": "epoch", "predecessor": 0,
                        "addresses": [POOL[place] for place in voter_ids],
                        "board_shape": [8, window],
                        "window_violations": 0}]},
        "replica": None,
        "trackers": [{"votes": votes, "reports": reports},
                     {"votes": epoch_votes, "reports": epoch_reports}]}
    events = []
    for epoch in (1, 2, 3):
        at = 1000.0 + 0.5 * epoch
        before = [POOL[place] for place in MEMBERS[epoch - 1]]
        events += [
            ["define", epoch, starts[epoch], 0,
             [POOL[place] for place in MEMBERS[epoch]], False, at],
            ["ack", epoch, 0, PROXY, at + 0.0005],
            ["ack", epoch, 0, before[0], at + 0.001],
            ["ack", epoch, 0, before[1], at + 0.002],
            ["proposed", starts[epoch], 5, at + 0.002],
            ["activated", epoch, at + 0.002],
            ["ack", epoch, 0, before[2], at + 0.003]]
    leader = {"record": {"claimed": False, "trackers": [],
                         "cluster_acceptors": cluster,
                         "epoch_events": events},
              "replica": None, "trackers": []}
    idle = {"record": {"claimed": False, "trackers": [],
                       "cluster_acceptors": cluster, "epoch_events": []},
            "replica": None, "trackers": []}
    records = {"proxy_leader_0_1": owner, "leader_0": leader,
               "leader_1": idle, "replica_0": replica(),
               "replica_1": replica()}
    if edit is not None:
        edit(records)
    return config, generators, records


def over(compared: dict) -> set:
    return {name for name, (value, limit) in compared.items()
            if value > limit}


def test_a_sound_run_compares_clean_and_keeps_every_number_of_the_plain_one():
    evidence: dict = {}
    config, generators, records = sound_run()
    compared = reference.compare(np, config, generators, records, evidence)
    assert over(compared) == set() and evidence == {}, compared
    assert all(limit == 0 for _, limit in compared.values())
    plain = reference.kv.compare(
        np, dict(config, board={"nodes": 3,
                                "window": config["board"]["window"]}),
        generators, {label: dict(r, record=dict(r["record"], trackers=r[
            "record"]["trackers"][:1]), trackers=r["trackers"][:1])
            for label, r in records.items()})
    assert set(compared) == set(plain) | EPOCHS


def events_of(records) -> list:
    return records["leader_0"]["record"]["epoch_events"]


def epoch_arrays(records) -> dict:
    return records["proxy_leader_0_1"]["trackers"][1]


def a_non_members_vote_completes_a_quorum(records):
    """A slot of epoch 2 is reported after one member's vote and the vote
    of an acceptor that epoch 1 had and epoch 2 has not; the second member
    votes later."""
    arrays, entry = (epoch_arrays(records),
                     records["proxy_leader_0_1"]["record"]["trackers"][1])
    votes, reports = arrays["votes"], arrays["reports"]
    slot = int(reports[len(reports) // 2 + 3, 1])
    mine = np.flatnonzero(votes[:, 1] == slot)
    report = int(reports[reports[:, 1] == slot][0, 0])
    outsider = entry["addresses"].index(POOL[1])
    assert POOL[1] not in [POOL[p] for p in MEMBERS[2]] and len(mine) == 2
    late = votes[mine[1]].copy()
    votes[mine[1], 4] = outsider
    late[0] = report + 1             # after the report, before what follows
    arrays["votes"] = np.concatenate([votes, late[None]])
    arrays["votes"] = arrays["votes"][np.argsort(arrays["votes"][:, 0],
                                                 kind="stable")]


def a_slot_reported_twice(records):
    arrays = epoch_arrays(records)
    arrays["reports"] = np.concatenate([arrays["reports"],
                                        arrays["reports"][-1:]])


def a_quorum_never_reported(records):
    arrays = epoch_arrays(records)
    arrays["reports"] = arrays["reports"][:-1]


def the_straddling_slot_is_dropped_at_the_switch(records):
    """What a switch that leaves the old board behind does: the slot with
    a vote on each side is reported by neither tracker."""
    arrays = epoch_arrays(records)
    arrays["reports"] = arrays["reports"][1:]


def a_board_one_row_short(records):
    records["proxy_leader_0_1"]["record"]["trackers"][1]["board_shape"][0] = 5


def a_board_cut_to_a_shorter_window(records):
    records["proxy_leader_0_1"]["record"]["trackers"][1]["board_shape"][
        1] = 1 << 14


def an_epoch_defined_twice_in_one_round(records):
    events = events_of(records)
    at = next(n for n, e in enumerate(events)
              if e[0] == "define" and e[1] == 2)
    other = list(events[at])
    other[2] += 1
    events.insert(at, other)


def an_epoch_that_starts_below_its_predecessor(records):
    """A fourth change, defined and never proposed into, that claims a
    slot of the second (its members are the third's, so that no slot is
    judged differently for it)."""
    events = events_of(records)
    third = next(e for e in events if e[0] == "define" and e[1] == 3)
    events.append(["define", 4, third[2] - 1, 0,
                   [POOL[place] for place in MEMBERS[3]], False, 1001.9])


def proposed_into_after_one_acknowledgement(records):
    events = events_of(records)
    at = next(n for n, e in enumerate(events)
              if e[0] == "proposed" and e[1] == next(
                  d[2] for d in events if d[0] == "define" and d[1] == 2))
    events.insert(at - 1, events.pop(at))  # before the second member's ack


def acknowledged_by_the_new_members_only(records):
    """f + 1 acknowledgements, none of them a predecessor's."""
    events = events_of(records)
    new = [POOL[place] for place in MEMBERS[3] if place not in MEMBERS[2]]
    assert len(new) == 2
    for event in events:
        if event[0] == "ack" and event[1] == 3 and event[3] != PROXY:
            event[3] = new[0] if event[3] == POOL[MEMBERS[2][0]] else new[1]


def no_epoch_activated_while_the_generators_ran(records):
    for event in events_of(records):
        if event[0] == "activated":
            event[2] -= 100.0


CASES = [
    (a_non_members_vote_completes_a_quorum, "chosen_early"),
    (a_slot_reported_twice, "chosen_twice"),
    (a_quorum_never_reported, "chosen_missing"),
    (the_straddling_slot_is_dropped_at_the_switch, "chosen_missing"),
    (a_board_one_row_short, "board_shape_wrong"),
    (a_board_cut_to_a_shorter_window, "board_shape_wrong"),
    (an_epoch_defined_twice_in_one_round, "epoch_overlap"),
    (an_epoch_that_starts_below_its_predecessor, "epoch_overlap"),
    (proposed_into_after_one_acknowledgement,
     "epoch_activated_without_predecessor_quorum"),
    (acknowledged_by_the_new_members_only,
     "epoch_activated_without_predecessor_quorum"),
    (no_epoch_activated_while_the_generators_ran, "too_few_epochs"),
]


@pytest.mark.parametrize("edit,number", CASES,
                         ids=[edit.__name__ for edit, _ in CASES])
def test_each_number_fires_alone(edit, number):
    evidence: dict = {}
    compared = reference.compare(np, *sound_run(edit), evidence)
    assert over(compared) == {number}, compared
    assert compared[number][0] == 1
    # The evidence names what was counted, in plain numbers and strings.
    assert set(evidence) == {number} and len(evidence[number]) == 1
    json.dumps(evidence)


def test_the_guarantee_of_epochs_scales_with_how_long_the_generators_ran():
    """18 of 20 seconds is 1 of the two seconds these generators ran, and
    all three activations lie inside them."""
    config, generators, records = sound_run()
    assert config["guarantees"]["epochs_activated_in_window_at_least"] == 18
    assert config["guarantees"]["window_s"] == 20.0
    config["guarantees"]["epochs_activated_in_window_at_least"] = 50
    compared = reference.compare(np, config, generators, records)
    assert compared["too_few_epochs"] == (1, 0)  # int(50 * 1.99 / 20) - 3


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        text = f.read()
    assert "frankenpaxos" not in text.replace("frankenpaxos_tpu.reconfig",
                                              "").replace("ops/", "")
    assert "import frankenpaxos" not in text and "from frankenpaxos" not in text
    assert "EpochStore" not in text

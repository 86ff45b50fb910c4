"""The plain reference of a compartmentalized Mencius key-value
deployment, and the comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made but
its answers: what clients were told, what each replica executed and what
its log held, what the chip owner's trackers were fed and what they
reported. ``multipaxos_kv.py``, the benchmark's own file beside this one,
judges the clients and the replicas' executed writes (``PlainRegisters``:
every acknowledged write executed exactly once in every replica, one
order in all, a client's writes in its issue order, reads no older than
the newest write acknowledged before them, every key read back); here
its ``compare`` is given the records without the trackers, and the
trackers are judged by the Mencius rule instead:

  PlainGroupQuorums  slot ``s`` belongs to leader group ``s mod G``, and
                     only that group's acceptors may vote for it: for
                     every (slot, round) the set of the own group's
                     acceptors that voted, a quorum once ``threshold`` of
                     them did. A vote from any other group's acceptor is
                     counted apart, and never towards a quorum.

Every comparison is exact, so every limit is 0. What each number counts,
beside ``multipaxos_kv``'s (``ops_unanswered``, ``keys_not_read_back``,
``reads_wrong``, ``replicas_missing``, the ``replica_*`` numbers,
``replica_logs_differ`` and ``chip_owners_wrong``):

  chosen_early            (slot, round)s a tracker reported before
                          ``threshold`` acceptors of the slot's own group
                          had voted for it in that round
  chosen_extra            reported, and never at quorum by the end
  chosen_twice            reported more than once by one tracker
  chosen_missing          at quorum by the end, and never reported
  votes_from_other_groups votes fed for a slot by an acceptor of another
                          leader group
  window_violations       votes a device board dropped as outside its ring
  board_shape_wrong       trackers whose device board is not [nodes, window]
  log_holes               slots below a replica's executed watermark that
                          its log holds no chosen value for
  slots_not_chosen        slots below a replica's executed watermark that
                          no tracker reported chosen
  slot_kinds_differ       slots below both watermarks that one replica
                          holds as a noop and another as a command batch,
                          or as batches of different sizes
"""

from __future__ import annotations

import importlib.util
import os

#: Numbers of ``multipaxos_kv.compare`` that come from the trackers; this
#: file counts them by the Mencius rule.
TRACKER_NUMBERS = ("chosen_early", "chosen_extra", "chosen_twice",
                   "chosen_missing", "window_violations",
                   "board_shape_wrong")


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("bench_reference_" + name,
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


kv = _beside("multipaxos_kv")


class PlainGroupQuorums:
    """``groups`` leader groups, each of ``acceptor_groups`` acceptor
    groups of ``row_size``; a voter is (board group, index) with board
    group ``leader_group * acceptor_groups + acceptor_group``, and slot
    ``s``'s is ``(s mod groups) * acceptor_groups + (s div groups) mod
    acceptor_groups``."""

    def __init__(self, groups: int, acceptor_groups: int, threshold: int):
        self.groups = groups
        self.acceptor_groups = acceptor_groups
        self.threshold = threshold
        self.voted: dict = {}
        self.complete: set = set()
        self.foreign = []

    def owner(self, slot: int) -> int:
        return ((slot % self.groups) * self.acceptor_groups
                + (slot // self.groups) % self.acceptor_groups)

    def vote(self, slot: int, round: int, group: int, index: int) -> None:
        if group != self.owner(slot):
            self.foreign.append((slot, round, group, index))
            return
        key = (slot, round)
        if key in self.complete:
            return
        voters = self.voted.setdefault(key, set())
        voters.add(index)
        if len(voters) >= self.threshold:
            self.complete.add(key)
            del self.voted[key]


def replay_tracker(votes, reports, plain: PlainGroupQuorums,
                   evidence=None, tracker: str = "") -> dict:
    """One tracker's record against ``plain``: ``votes`` rows (sequence,
    first slot, end slot, round, group, index), ``reports`` rows
    (sequence, slot, round), both in arrival order."""
    reported: set = set()
    early: dict = {}
    twice = []
    votes = votes.tolist()
    at = 0
    for seq, slot, round in reports.tolist():
        while at < len(votes) and votes[at][0] < seq:
            _, first, end, vote_round, group, index = votes[at]
            for voted_slot in range(first, end):
                plain.vote(voted_slot, vote_round, group, index)
            at += 1
        key = (slot, round)
        if key in reported:
            twice.append((slot, round, seq))
        elif key not in plain.complete:
            early[key] = seq
        reported.add(key)
    for _, first, end, vote_round, group, index in votes[at:]:
        for voted_slot in range(first, end):
            plain.vote(voted_slot, vote_round, group, index)
    extra = reported - plain.complete
    missing = plain.complete - reported

    def chosen(keys):
        return ({"tracker": tracker, "slot": slot, "round": round}
                for slot, round in sorted(keys))

    kv.keep(evidence, "chosen_early", (
        {"tracker": tracker, "slot": slot, "round": round,
         "owner_group": plain.owner(slot),
         "reported_at_event": early[slot, round]}
        for slot, round in sorted(set(early) - extra)))
    kv.keep(evidence, "chosen_extra", chosen(extra))
    kv.keep(evidence, "chosen_twice", (
        {"tracker": tracker, "slot": slot, "round": round,
         "reported_again_at_event": seq} for slot, round, seq in twice))
    kv.keep(evidence, "chosen_missing", chosen(missing))
    kv.keep(evidence, "votes_from_other_groups", (
        {"tracker": tracker, "slot": slot, "round": round,
         "owner_group": plain.owner(slot), "voter": [group, index]}
        for slot, round, group, index in plain.foreign))
    return {"chosen_early": len(set(early) - extra),
            "chosen_extra": len(extra),
            "chosen_twice": len(twice),
            "chosen_missing": len(missing),
            "votes_from_other_groups": len(plain.foreign)}, reported


def check_slots(np, replicas: dict, chosen, evidence=None) -> dict:
    """Every replica's log below its executed watermark against the
    trackers' reports (``chosen``: the reported slots, sorted and
    unique) and against the other replicas."""
    numbers = {"log_holes": 0, "slots_not_chosen": 0,
               "slot_kinds_differ": 0}
    logs = []
    for label, replica in replicas.items():
        kinds = replica["replica"]["slot_kinds"]
        commands = replica["replica"]["slot_commands"]
        holes = np.flatnonzero(kinds < 0)
        numbers["log_holes"] += len(holes)
        kv.keep(evidence, "log_holes", (
            {"replica": label, "slot": slot,
             "executed_watermark": len(kinds)}
            for slot in holes.tolist()))
        slots = np.arange(len(kinds))
        known = np.zeros(len(kinds), dtype=bool)
        if len(chosen):
            at = np.minimum(np.searchsorted(chosen, slots), len(chosen) - 1)
            known = chosen[at] == slots
        numbers["slots_not_chosen"] += int((~known).sum())
        kv.keep(evidence, "slots_not_chosen", (
            {"replica": label, "slot": slot,
             "holds": "noop" if kinds[slot] == 0 else "commands"}
            for slot in np.flatnonzero(~known).tolist()))
        logs.append((label, kinds, commands))
    for label, kinds, commands in logs[1:]:
        first, first_kinds, first_commands = logs[0]
        common = min(len(kinds), len(first_kinds))
        differ = np.flatnonzero(
            (kinds[:common] != first_kinds[:common])
            | (commands[:common] != first_commands[:common]))
        numbers["slot_kinds_differ"] += len(differ)
        kv.keep(evidence, "slot_kinds_differ", (
            {"replicas": [first, label], "slot": slot,
             "kinds": [int(first_kinds[slot]), int(kinds[slot])],
             "commands": [int(first_commands[slot]), int(commands[slot])]}
            for slot in differ.tolist()))
    return numbers


def compare(np, config: dict, generators: list, records: dict,
            evidence=None) -> dict:
    # The clients and the replicas' executed writes, as multipaxos_kv
    # holds them; the trackers are held back and judged below.
    without_trackers = {
        label: dict(r, record=dict(r["record"], trackers=[]), trackers=[])
        for label, r in records.items()}
    found: dict = {}
    numbers = {name: value for name, (value, _) in kv.compare(
        np, config, generators, without_trackers, found).items()
        if name not in TRACKER_NUMBERS}
    if evidence is not None:
        for name, rows in found.items():
            if name not in TRACKER_NUMBERS:
                evidence.setdefault(name, []).extend(rows)

    counts = dict.fromkeys(TRACKER_NUMBERS + ("votes_from_other_groups",),
                           0)
    board = [config["board"]["nodes"], config["board"]["window"]]
    reported = [np.empty(0, dtype=np.int64)]
    trackers = 0
    for label, record in records.items():
        for k, (counters, arrays) in enumerate(zip(
                record["record"]["trackers"], record["trackers"])):
            trackers += 1
            tracker = f"{label}.tracker{k}"
            plain = PlainGroupQuorums(
                config["leader_groups"],
                config["acceptor_groups"] // config["leader_groups"],
                config["quorum"]["threshold"])
            replayed, _ = replay_tracker(arrays["votes"], arrays["reports"],
                                         plain, evidence, tracker)
            for name, count in replayed.items():
                counts[name] += count
            reported.append(arrays["reports"][:, 1])
            counts["window_violations"] += counters["window_violations"]
            if counters["window_violations"]:
                kv.keep(evidence, "window_violations", [
                    {"tracker": tracker,
                     "votes_dropped": counters["window_violations"]}])
            if counters["board_shape"] != board:
                counts["board_shape_wrong"] += 1
                kv.keep(evidence, "board_shape_wrong", [
                    {"tracker": tracker, "board": counters["board_shape"],
                     "configuration": board}])
    if not trackers:
        # No tracker recorded anything: nothing was compared.
        counts["chosen_missing"] += 1
        kv.keep(evidence, "chosen_missing", [{"trackers_recorded": 0}])
    numbers.update(counts)
    replicas = {label: r for label, r in records.items()
                if r["replica"] is not None}
    numbers.update(check_slots(np, replicas,
                               np.unique(np.concatenate(reported)),
                               evidence))
    return {name: (int(value), 0) for name, value in numbers.items()}

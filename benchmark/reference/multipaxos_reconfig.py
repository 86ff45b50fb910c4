"""The plain reference of a MultiPaxos key-value deployment whose acceptor
set is replaced under load: what ``multipaxos_kv.py`` holds a run to, and
the epochs.

It imports nothing of the program; ``multipaxos_kv.py``, the benchmark's
own file beside it, is loaded by path and CALLED for everything it
compares (the clients' registers, the replicas' logs, the single-epoch
trackers against ``PlainQuorums``). What is added is computed here from
records alone, with Python sets:

  PlainEpochs   the epoch map as the leaders' records give it: for every
                epoch its id, its start slot and its members BY ADDRESS
                (epoch 0: the first acceptor group of the cluster file,
                from slot 0). A slot's epoch is the last one that starts
                at or below it. A vote counts for its slot only if the
                voter's address is a member of that slot's epoch; a slot
                is chosen in a round once f + 1 such voters have voted for
                it in that round.

Every tracker pair of the chip owner is replayed against it in arrival
order: the votes the single-epoch tracker was fed up to the switch (by
(group, index), turned into addresses through the cluster file), then the
votes the epoch tracker was fed (by address), with both trackers' reports.
A slot whose first vote came before the switch and whose second came
after it is therefore chosen here, and has to be reported by one of the
two.

What each number counts, beside ``multipaxos_kv``'s (into whose
``chosen_early``, ``chosen_twice``, ``chosen_missing`` and
``board_shape_wrong`` the pairs' counts are added):

  chosen_early    (slot, round)s a pair reported before f + 1 members of
                  the slot's epoch had voted for it in that round among
                  the votes fed by then (never, too: a report that no
                  quorum ever stood behind is early)
  chosen_twice    reported more than once by a pair
  chosen_missing  chosen by the end, and reported by neither
  board_shape_wrong  epoch trackers whose device board has not a row for
                  every address that was ever a member (it may hold spare
                  rows), or is not the configuration's window wide
  epoch_overlap   epochs whose id does not follow its predecessor's, whose
                  start slot is below its predecessor's, or that one round
                  defined twice in two ways (an epoch defined while
                  nothing was proposed starts where its predecessor did,
                  which then governs no slot: a slot still has one epoch)
  epoch_activated_without_predecessor_quorum
                  epochs into which a leader proposed before f + 1 members
                  of the predecessor had acknowledged the commit to it
  too_few_epochs  the guarantee's count of epochs activated in the
                  ``window_s`` seconds before the window closed, less
                  those that were, floored at 0 (generators that ran for
                  less are held to the same rate over what they ran)
"""

from __future__ import annotations

import bisect
import importlib.util
import os

EPOCH = "epoch"


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("bench_reference_" + name,
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


kv = _beside("multipaxos_kv")


def address(raw) -> tuple:
    return (raw[0], int(raw[1]))


class PlainEpochs:
    """``epochs``: ``[(start slot, members)]`` in epoch order, members a
    set of addresses."""

    def __init__(self, epochs: list, f: int):
        self.starts = [start for start, _ in epochs]
        self.members = [frozenset(members) for _, members in epochs]
        self.need = f + 1
        self.voted: dict = {}
        self.complete: set = set()

    def members_of(self, slot: int) -> frozenset:
        return self.members[max(0, bisect.bisect_right(self.starts, slot)
                                - 1)]

    def vote(self, slot: int, round: int, voter: tuple) -> None:
        key = (slot, round)
        if key in self.complete or voter not in self.members_of(slot):
            return
        voters = self.voted.setdefault(key, set())
        voters.add(voter)
        if len(voters) >= self.need:
            self.complete.add(key)
            del self.voted[key]


def epochs_of(records: dict, evidence=None) -> tuple:
    """The epoch map of the leaders' records, and how many of its entries
    break the partition of slot space. Returns ``(epochs, overlap)``,
    ``epochs`` as ``PlainEpochs`` takes them."""
    initial = None
    defined: dict = {}               # id -> (round, start, members)
    overlap = 0
    for label, record in sorted(records.items()):
        events = record["record"].get("epoch_events") or ()
        if not events:
            continue
        if initial is None:
            initial = frozenset(
                address(a) for a in record["record"]["cluster_acceptors"][0])
        for event in events:
            if event[0] != "define":
                continue
            _, epoch, start, round, members = event[:5]
            entry = (round, start,
                     frozenset(address(a) for a in members))
            known = defined.get(epoch)
            if known is not None and known[0] == round and known != entry:
                overlap += 1
                kv.keep(evidence, "epoch_overlap", [
                    {"epoch": epoch, "round": round, "leader": label,
                     "defined_twice": [known[1], start]}])
            if known is None or round >= known[0]:
                defined[epoch] = entry
    epochs = [(0, initial or frozenset())]
    for place, epoch in enumerate(sorted(defined), start=1):
        _, start, members = defined[epoch]
        if epoch != place or start < epochs[-1][0]:
            overlap += 1
            kv.keep(evidence, "epoch_overlap", [
                {"epoch": epoch, "expected_id": place, "start_slot": start,
                 "predecessor_start_slot": epochs[-1][0]}])
        # Kept in order whatever was defined: a slot's epoch stays the
        # last that starts at or below it.
        epochs.append((max(start, epochs[-1][0]), members))
    return epochs, overlap


def unproven_activations(records: dict, epochs: list, f: int,
                         evidence=None) -> int:
    """For every epoch a leader defined and then proposed into: the
    acknowledgements of that commit (its epoch, its round) that had reached
    the leader before its first proposal after the definition have to come
    from f + 1 members of the predecessor."""
    unproven = 0
    for label, record in sorted(records.items()):
        events = record["record"].get("epoch_events") or ()
        open_define = None           # (epoch, round) awaiting a proposal
        acked: set = set()
        for event in events:
            if event[0] == "define":
                open_define, acked = (event[1], event[3]), set()
            elif event[0] == "ack" and open_define == (event[1], event[2]):
                acked.add(address(event[3]))
            elif event[0] == "proposed" and open_define is not None:
                epoch = open_define[0]
                before = (epochs[epoch - 1][1]
                          if 0 < epoch <= len(epochs) else frozenset())
                if len(acked & before) < f + 1:
                    unproven += 1
                    kv.keep(evidence,
                            "epoch_activated_without_predecessor_quorum", [
                                {"leader": label, "epoch": epoch,
                                 "first_proposed_slot": event[1],
                                 "acknowledged_by": sorted(
                                     map(list, acked)),
                                 "predecessor": sorted(map(list, before))}])
                open_define = None
    return unproven


def activated_in(records: dict, first: float, last: float) -> int:
    return sum(1 for record in records.values()
               for event in record["record"].get("epoch_events") or ()
               if event[0] == "activated" and first <= event[2] <= last)


def replay_pair(plain_votes, plain_reports, plain_addresses: list,
                epoch_votes, epoch_reports, epoch_addresses: list,
                epochs: list, f: int, evidence=None, tracker: str = "") -> dict:
    """One single-epoch tracker's record (may be empty) followed by the
    epoch tracker's that took over from it, against ``PlainEpochs``."""
    plain = PlainEpochs(epochs, f)
    reported: set = set()
    early: dict = {}
    twice = []

    def replay(votes: list, reports: list, voter_of) -> None:
        at = 0
        for seq, slot, round in reports:
            while at < len(votes) and votes[at][0] < seq:
                _, first, end, vote_round = votes[at][:4]
                voter = voter_of(votes[at])
                for voted_slot in range(first, end):
                    plain.vote(voted_slot, vote_round, voter)
                at += 1
            key = (slot, round)
            if key in reported:
                twice.append((slot, round, seq))
            elif key not in plain.complete:
                early[key] = seq
            reported.add(key)
        for row in votes[at:]:
            _, first, end, vote_round = row[:4]
            voter = voter_of(row)
            for voted_slot in range(first, end):
                plain.vote(voted_slot, vote_round, voter)

    replay(plain_votes.tolist(), plain_reports.tolist(),
           lambda row: plain_addresses[row[4]][row[5]])
    replay(epoch_votes.tolist(), epoch_reports.tolist(),
           lambda row: epoch_addresses[row[4]])
    missing = plain.complete - reported
    kv.keep(evidence, "chosen_early", (
        {"tracker": tracker, "slot": slot, "round": round,
         "reported_at_event": seq,
         "members_of_its_epoch": sorted(map(list, plain.members_of(slot))),
         "had_voted": sorted(map(list, plain.voted.get((slot, round), ())))}
        for (slot, round), seq in sorted(early.items())))
    kv.keep(evidence, "chosen_twice", (
        {"tracker": tracker, "slot": slot, "round": round,
         "reported_again_at_event": seq} for slot, round, seq in twice))
    kv.keep(evidence, "chosen_missing", (
        {"tracker": tracker, "slot": slot, "round": round}
        for slot, round in sorted(missing)))
    return {"chosen_early": len(early), "chosen_twice": len(twice),
            "chosen_missing": len(missing)}


def compare(np, config: dict, generators: list, records: dict,
            evidence=None) -> dict:
    f = config["f"]
    window = config["board"]["window"]
    # ``multipaxos_kv`` on what it knows: the single-epoch trackers, whose
    # board is over the members of epoch 0.
    single = {label: dict(r, record=dict(r["record"], trackers=[
        t for t in r["record"]["trackers"] if t.get("kind") != EPOCH]),
        trackers=[a for t, a in zip(r["record"]["trackers"], r["trackers"])
                  if t.get("kind") != EPOCH])
        for label, r in records.items()}
    numbers = {name: value for name, (value, _) in kv.compare(
        np, dict(config, board={"nodes": config["members"],
                                "window": window}),
        generators, single, evidence).items()}

    epochs, numbers["epoch_overlap"] = epochs_of(records, evidence)
    numbers["epoch_activated_without_predecessor_quorum"] = (
        unproven_activations(records, epochs, f, evidence))
    # The guarantee's count is for a window of ``window_s``; a run whose
    # generators ran for less is held to the same rate over what they ran.
    closed = max(g["info"]["end_mono_s"] for g in generators)
    ran_s = closed - min(float(g["ops"]["issue_mono_s"].min())
                         for g in generators if len(g["ops"]["issue_mono_s"]))
    span_s = min(config["guarantees"]["window_s"], ran_s)
    wanted = int(config["guarantees"]["epochs_activated_in_window_at_least"]
                 * span_s / config["guarantees"]["window_s"])
    found = activated_in(records, closed - span_s, closed)
    numbers["too_few_epochs"] = max(0, wanted - found)
    if found < wanted:
        kv.keep(evidence, "too_few_epochs", [
            {"wanted": wanted, "in_the_last_s": span_s,
             "activated_in_them": found,
             "epochs_defined": len(epochs) - 1}])

    ever = set().union(*(members for _, members in epochs))
    empty = np.empty((0, 6), dtype=np.int64)
    none = np.empty((0, 3), dtype=np.int64)
    for label, r in records.items():
        entries = r["record"]["trackers"]
        for k, (entry, arrays) in enumerate(zip(entries, r["trackers"])):
            if entry.get("kind") != EPOCH:
                continue
            tracker = f"{label}.tracker{k}"
            before = entry["predecessor"]
            plain = r["trackers"][before] if before is not None else None
            found = replay_pair(
                plain["votes"] if plain else empty,
                plain["reports"] if plain else none,
                [[address(a) for a in group]
                 for group in r["record"]["cluster_acceptors"]],
                arrays["votes"], arrays["reports"],
                [address(a) for a in entry["addresses"]],
                epochs, f, evidence, tracker)
            for name, count in found.items():
                numbers[name] += count
            # A row for every address that was ever a member (a board
            # may hold spare rows), and the window as configured.
            shape = entry["board_shape"]
            if (shape is None or shape[0] < len(ever)
                    or shape[1] != window):
                numbers["board_shape_wrong"] += 1
                kv.keep(evidence, "board_shape_wrong", [
                    {"tracker": tracker, "board": shape,
                     "members_ever": len(ever), "window": window}])
    return {name: (int(value), 0) for name, value in numbers.items()}

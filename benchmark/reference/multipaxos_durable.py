"""The plain reference of a durable MultiPaxos key-value deployment: what
``multipaxos_kv.py`` holds a run to, on the first life of every role, and
durability on what came back after the whole storage tier (every acceptor
and every replica) was killed at once with everything after each one's
last fsync discarded.

It imports nothing of the program; ``multipaxos_kv.py``, the benchmark's
own file beside it, is loaded by its path, and judges the first life: a
replica's first-life log, and the trackers' records up to the kill. It reads what
``harness/role_entry_durable.py`` left in the records: for a replica the
first life's executed writes with their slots, the second life's executed
writes with theirs (the log's replay first), its state as rebuilt from
the log and at exit; for an acceptor the votes it held at the dump and the
votes it rebuilt from its log; and what ``deployments/
multipaxos_durable.py`` left of the kill and the recovery.

Every number below is an exact count with limit 0, and leaves its first
rows under ``evidence``, but ``unsynced_bytes_discarded``, which is a
reading: its limit is ``NO_LIMIT``, so that it stands in the line beside
the others and decides nothing (it says whether the discard had anything
to do).

  acked_write_lost        over the replicas, writes acknowledged to a
                          generator that the replica, recovered and caught
                          up, has not executed: not below the watermark of
                          the snapshot it recovered from, not among the
                          writes its second life executed, or at a slot at
                          or above its final watermark. A replica that
                          never came back has executed none
  acked_write_not_durable_at_quorum
                          acknowledged writes whose slot fewer than f+1
                          acceptors hold a vote for, in the round the
                          tracker reported it chosen in or a later one, in
                          their state as rebuilt from the log, before any
                          message. This program's acceptors forget no vote
                          (a compaction re-logs every one), so no slot is
                          excused for lying below a chosen watermark: every
                          acknowledged slot has to be held by votes
  recovered_state_wrong   over the replicas, keys whose value in the store
                          rebuilt from the log is not that of the last
                          write to the key in the first life's order below
                          the recovered watermark; the same for the store
                          at exit below the final watermark; and, where the
                          recovered watermark is the first life's, client
                          table entries that differ from the first life's
  recovered_order_differs over the replicas, slots from the recovered
                          snapshot's watermark up, and below the first
                          life's watermark, at which what the second life
                          executed is not what the first executed
  storage_not_killed_at_once
                          storage roles not killed within KILL_SPREAD_S of
                          the first (all of them where no kill is on
                          record)
  roles_not_recovered     storage roles with no second life
  recovery_probe_failed   1 if the write and the linearizable read of it
                          through the recovered cluster did not both
                          complete inside the deployment's deadline
  unsynced_bytes_discarded
                          bytes past the last recorded fsync that were cut
                          from the logs before the relaunch, all roles
"""

from __future__ import annotations

import importlib.util
import os

KILL_SPREAD_S = 1.0
NO_LIMIT = 2 ** 63 - 1
STORAGE_KINDS = ("acceptor", "replica")


def _load_kv():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "multipaxos_kv.py")
    spec = importlib.util.spec_from_file_location(
        "bench_reference_multipaxos_kv", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


kv = _load_kv()
keep = kv.keep


def kind_of(label: str, record: dict) -> str:
    return record["record"].get("kind") or label.split("_")[0]


def held_rounds(np, runs, slots, size: int):
    """For every slot below ``size`` the highest round an acceptor holds
    a vote in (-1: none), from its run records (first slot, end slot,
    round) and its single votes (slot, round)."""
    best = np.full(size, -1, dtype=np.int64)
    for first, end, round in runs.tolist():
        part = best[first:min(end, size)]
        np.maximum(part, round, out=part)
    if len(slots):
        inside = slots[:, 0] < size
        np.maximum.at(best, slots[inside, 0], slots[inside, 1])
    return best


def chosen_rounds(np, records: dict, size: int):
    """For every slot below ``size`` the lowest round a tracker reported
    it chosen in (-1: none reported)."""
    never = np.iinfo(np.int64).max
    rounds = np.full(size, never, dtype=np.int64)
    for record in records.values():
        for arrays in record["trackers"]:
            reports = arrays["reports"]
            reports = reports[reports[:, 1] < size]
            np.minimum.at(rounds, reports[:, 1], reports[:, 2])
    rounds[rounds == never] = -1
    return rounds


def before_the_kill(record: dict) -> dict:
    """``record`` with each tracker's votes and reports cut to the
    events its recorder held when the storage tier was killed (the chip
    owner's ``first_life_events``): the first life's, which
    ``multipaxos_kv`` judges. What the trackers saw of the recovery, a
    leader change among it where a role lost its tail, is not held to
    "every quorum reported once"."""
    marks = record["record"].get("first_life_events")
    if not marks:
        return record
    return {**record, "trackers": [
        {"votes": arrays["votes"][arrays["votes"][:, 0] < mark],
         "reports": arrays["reports"][arrays["reports"][:, 0] < mark]}
        for arrays, mark in zip(record["trackers"], marks)]}


def slots_of_writes(np, plain, values, slots):
    """Each client write's slot in a replica's first life (-1: not
    executed there)."""
    rows = plain.find(kv.ids_of(np, values))
    found = np.full(len(plain.write_ids), -1, dtype=np.int64)
    known = rows >= 0
    # The first place a write was executed at: later ones are repeats.
    found[rows[known][::-1]] = slots[known][::-1]
    return found


def last_values(np, keys, values, names: list, below) -> dict:
    """{key name: value} of the last of ``keys``/``values`` (a log in
    execution order) at each key among the entries ``below`` marks."""
    keys, values = keys[below], values[below]
    last_at = np.full(len(names), -1, dtype=np.int64)
    if len(keys):
        np.maximum.at(last_at, keys, np.arange(len(keys)))
    return {names[k]: values[at].decode()
            for k, at in enumerate(last_at.tolist()) if at >= 0}


def store_wrong(store: dict, expected: dict) -> list:
    return [name for name in sorted(
        (set(store) | set(expected)) - {kv.PROBE_KEY})
        if store.get(name) != expected.get(name)]


def entries(np, slots, keys, values, names: list, place: dict, width: int):
    """A log as one array of (slot, key, value) rows that two lives'
    logs can be compared by; ``place`` gives a key name its number."""
    out = np.empty(len(slots), dtype=[("slot", np.int64), ("key", np.int64),
                                      ("value", f"S{width}")])
    out["slot"] = slots
    mapped = np.array([place.setdefault(name, len(place))
                       for name in names] + [0], dtype=np.int64)
    out["key"] = mapped[keys] if len(keys) else keys
    out["value"] = values
    return out


def first_life_slots(np, arrays: dict):
    """The slot of each write a replica's first life executed; a record
    without them (another role entry's) reads as a slot a write."""
    return (arrays["slots"] if "slots" in arrays
            else np.arange(len(arrays["keys"])))


def check_replica(np, plain, label: str, record: dict, acked, write_slot,
                  evidence) -> dict:
    """One replica's second life against its first; ``write_slot`` is
    each client write's slot in that first life."""
    numbers = {"acked_write_lost": 0, "recovered_state_wrong": 0,
               "recovered_order_differs": 0}
    arrays, info = record["replica"], record["record"]
    first_slots = first_life_slots(np, arrays)
    if info.get("lives", 1) < 2 or "recovered_keys" not in arrays:
        # It holds nothing: it never came back.
        numbers["acked_write_lost"] = int(acked.sum())
        keep(evidence, "acked_write_lost", (
            {"replica": label, "came_back": False,
             "write": plain.write_row(row)}
            for row in np.flatnonzero(acked).tolist()))
        return numbers
    life1, life2 = info["life1"], info["life2"]
    recovered, final = life2["recovered"], life2["final"]
    first_watermark = life1["executed_watermark"]
    snapshot = recovered["snapshot_watermark"]
    superseded = recovered.get("superseded_writes", 0)
    second_keys = arrays["recovered_keys"][superseded:]
    second_values = arrays["recovered_values"][superseded:]
    second_slots = arrays["recovered_slots"][superseded:]

    # Lost: not under the snapshot, not executed again, or past the end.
    again = np.isin(plain.write_ids, kv.ids_of(np, second_values))
    lost = acked & ~again & ~((write_slot >= 0) & (write_slot < snapshot))
    lost |= acked & (write_slot >= final["executed_watermark"])
    numbers["acked_write_lost"] = int(lost.sum())
    keep(evidence, "acked_write_lost", (
        {"replica": label, "write": plain.write_row(row),
         "slot": int(write_slot[row]), "snapshot_watermark": snapshot,
         "recovered_watermark": recovered["executed_watermark"],
         "final_watermark": final["executed_watermark"]}
        for row in np.flatnonzero(lost).tolist()))

    # The state: as rebuilt from the log, and at exit.
    names = info.get("key_names", [])
    wrong = []
    for when, state in (("recovered", recovered), ("final", final)):
        watermark = min(state["executed_watermark"], first_watermark)
        expected = last_values(np, arrays["keys"], arrays["values"], names,
                               first_slots < watermark)
        wrong += [{"replica": label, "when": when, "key": name,
                   "store": state["store"].get(name),
                   "last_write_below_watermark": expected.get(name),
                   "watermark": watermark}
                  for name in store_wrong(state["store"], expected)]
    if recovered["executed_watermark"] == first_watermark:
        first_table = life1["client_table"]
        table = recovered["client_table"]
        wrong += [{"replica": label, "when": "recovered", "client": client,
                   "client_table": table.get(client),
                   "first_life": first_table.get(client)}
                  for client in sorted(set(first_table) | set(table))
                  if first_table.get(client) != table.get(client)]
    numbers["recovered_state_wrong"] = len(wrong)
    keep(evidence, "recovered_state_wrong", wrong)

    # The order: what both lives executed at the slots both executed.
    end = min(first_watermark, final["executed_watermark"])
    width = max(arrays["values"].dtype.itemsize,
                second_values.dtype.itemsize, 1)
    place: dict = {}
    first = entries(np, first_slots, arrays["keys"], arrays["values"],
                    names, place, width)
    second = entries(np, second_slots, second_keys, second_values,
                     life2.get("key_names", []), place, width)
    first = first[(first["slot"] >= snapshot) & (first["slot"] < end)]
    second = second[(second["slot"] >= snapshot) & (second["slot"] < end)]
    if len(first) != len(second) or (first != second).any():
        differ = np.unique(np.setxor1d(first, second)["slot"])
        numbers["recovered_order_differs"] = len(differ)
        name_of = {number: name for name, number in place.items()}

        def at(log, slot):
            return [[name_of[int(row["key"])],
                     row["value"].decode(errors="replace")]
                    for row in log[log["slot"] == slot]]

        keep(evidence, "recovered_order_differs", (
            {"replica": label, "slot": int(slot),
             "first_life": at(first, slot), "second_life": at(second, slot)}
            for slot in differ.tolist()))
    return numbers


def compare(np, config: dict, generators: list, records: dict,
            evidence=None) -> dict:
    storage = {label: r for label, r in records.items()
               if kind_of(label, r) in STORAGE_KINDS}
    acceptors = {label: r for label, r in storage.items()
                 if kind_of(label, r) == "acceptor"}
    replicas = {label: r for label, r in storage.items()
                if kind_of(label, r) == "replica"
                and r["replica"] is not None and "keys" in r["replica"]}
    # The first life, by the reference of the deployment without a log:
    # an acceptor's arrays are no replica's log.
    first_life = {label: ({**r, "replica": None} if label in acceptors
                          else before_the_kill(r))
                  for label, r in records.items()}
    compared = kv.compare(np, config, generators, first_life, evidence)

    plain = kv.PlainRegisters(np, generators)
    acked = np.isfinite(plain.write_acked)
    numbers = {"acked_write_lost": 0, "acked_write_not_durable_at_quorum": 0,
               "recovered_state_wrong": 0, "recovered_order_differs": 0}
    write_slots = {
        label: slots_of_writes(np, plain, replica["replica"]["values"],
                               first_life_slots(np, replica["replica"]))
        for label, replica in replicas.items()}
    for label, replica in replicas.items():
        for name, count in check_replica(np, plain, label, replica, acked,
                                         write_slots[label],
                                         evidence).items():
            numbers[name] += count

    # Votes: every acknowledged write's slot (the first replica's: the
    # logs are one, or ``replica_logs_differ`` says so), held by f+1
    # acceptors as they rebuilt themselves from their logs.
    if replicas:
        write_slot = next(iter(write_slots.values()))
        size = int(write_slot.max(initial=-1)) + 1
        chosen = chosen_rounds(np, records, size)
        holders = np.zeros(size, dtype=np.int64)
        held_by = {}
        for name, acceptor in acceptors.items():
            votes = acceptor["replica"] or {}
            if acceptor["record"].get("lives", 1) < 2 \
                    or "recovered_voted_runs" not in votes:
                continue
            held_by[name] = held_rounds(
                np, votes["recovered_voted_runs"],
                votes["recovered_voted_slots"], size)
            holders += held_by[name] >= np.maximum(chosen, 0)
        slotted = acked & (write_slot >= 0)
        short = slotted.copy()
        short[slotted] = holders[write_slot[slotted]] < config["f"] + 1
        numbers["acked_write_not_durable_at_quorum"] = int(short.sum())
        keep(evidence, "acked_write_not_durable_at_quorum", (
            {"write": plain.write_row(row), "slot": int(write_slot[row]),
             "chosen_in_round": int(chosen[write_slot[row]]),
             "recovered_vote_rounds": {
                 name: int(held[write_slot[row]])
                 for name, held in sorted(held_by.items())},
             "acceptors_recovered": sorted(held_by)}
            for row in np.flatnonzero(short).tolist()))

    # The kill and the recovery themselves.
    recovery = next((r["record"]["recovery"] for r in storage.values()
                     if "recovery" in r["record"]), {})
    killed = recovery.get("killed_mono_s") or {}
    late = [label for label in sorted(storage)
            if label not in killed
            or killed[label] - min(killed.values()) > KILL_SPREAD_S]
    numbers["storage_not_killed_at_once"] = len(late)
    keep(evidence, "storage_not_killed_at_once", (
        {"role": label, "killed_mono_s": killed.get(label),
         "first_killed_mono_s": min(killed.values(), default=None)}
        for label in late))
    gone = [label for label in sorted(storage)
            if storage[label]["record"].get("lives", 1) < 2]
    numbers["roles_not_recovered"] = len(gone)
    keep(evidence, "roles_not_recovered", (
        {"role": label, "lives": storage[label]["record"].get("lives", 1),
         "never_listened_again": label in (recovery.get("not_recovered")
                                           or ())} for label in gone))
    numbers["recovery_probe_failed"] = int(
        recovery.get("recovery_probe_failed", 1))
    if numbers["recovery_probe_failed"]:
        keep(evidence, "recovery_probe_failed", [
            {key: recovery.get(key) for key in (
                "not_dumped", "not_recovered", "probe_committed_mono_s",
                "probe_read_mono_s", "error")}])
    compared.update({name: (int(value), 0)
                     for name, value in numbers.items()})
    compared["unsynced_bytes_discarded"] = (
        int(sum(int(r["record"].get("unsynced_bytes_discarded", 0) or 0)
                for r in storage.values())), NO_LIMIT)
    return compared

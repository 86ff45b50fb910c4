"""The plain reference of a MultiPaxos key-value deployment, and the
comparison that decides ``correct``.

It imports nothing of the program and takes nothing the program made
but its answers: what clients were told, what each replica executed,
what the chip owner's quorum tracker was fed and what it reported.

  PlainRegisters  every key is a register that all clients share, and a
                  replica's executed log is one order of all writes.
                  Every write carries a value of its own (the
                  generator's ``write_id``), so each is found in the log
                  by it. The log is held to what clients saw: every
                  acknowledged write once, a loop's writes in the order
                  it issued them, no write before one that was
                  acknowledged before it was issued, and every read
                  (in the run, and the read-back after it) no older than
                  the newest write acknowledged before the read was
                  issued. A bound check: with shared keys no single
                  value is "the" expected one, and the order of
                  concurrent writes is the system's to choose.
  PlainQuorums    for every (slot, round) the set of acceptors that
                  voted, and the configuration's write quorum rule
                  evaluated on that set after every vote: no ring, no
                  kernels, no buffering.

``compare`` returns ``{name: (number, limit)}``; a run is correct when no
number is above its limit. Every comparison is exact, so every limit is
0. Given an ``evidence`` dictionary it also leaves there, for every number
above its limit, the first ``EVIDENCE_ROWS`` rows the count was made from,
each a small dictionary of plain numbers and strings: a refusal then
carries what to reason from, not a count alone.

One clock. Every instant compared here (``issue_mono_s``, the answer's
instant ``issue_mono_s + latency_s``, ``end_mono_s``) is a read of
``time.monotonic()`` in a generator: ``CLOCK_MONOTONIC``, one clock for
all processes of a Linux host and never stepped. No wall-clock instant
enters: a step of ``CLOCK_REALTIME`` inside a run (NTP, a VM put right
after a pause) would read as writes out of real-time order.

What each number counts:

  ops_unanswered          operations issued and never answered, or given up
  keys_not_read_back      keys with an acknowledged write and no answered
                          read issued after the window closed
  reads_wrong             linearizable reads that returned a value nobody
                          wrote or no replica executed, one written to
                          another key, one older than a write to the key
                          that was acknowledged before the read was issued,
                          or one issued only after the read was answered
  replicas_missing        replicas short of the number the configuration's
                          guarantees say hold every acknowledged write
  replica_writes_lost     over those replicas, acknowledged writes that the
                          replica did not execute
  replica_writes_repeated writes a replica executed more than once
  replica_writes_unknown  executed writes that no client issued, or that
                          landed on another key than the client's
  replica_order_wrong     executed writes of one loop that come before an
                          earlier write of that loop
  replica_realtime_wrong  executed writes placed before a write that had
                          been acknowledged before they were issued
  replica_store_wrong     keys whose final value in the replica's store is
                          not that of the last write the replica executed
  replica_logs_differ     places at which two replicas' executed sequences
                          differ
  chosen_early            (slot, round)s the tracker reported before
                          PlainQuorums saw their write quorum among the
                          votes fed by then
  chosen_extra            reported, and never at quorum by the end
  chosen_twice            reported more than once
  chosen_missing          at quorum by the end, and never reported
  window_violations       votes the device board dropped as outside its ring
  board_shape_wrong       trackers whose device board is not [nodes, window]
                          as the configuration states
  chip_owners_wrong       |processes that claimed a device - 1|
"""

from __future__ import annotations

import itertools

#: The launcher's one write before the generators start.
PROBE_KEY = "probe"
WRITE, READ = 0, 1
ID_DIGITS = 16
#: Two instants of one host's monotonic clock read in two processes: the
#: slack within which "acknowledged before issued" is not held against a
#: log.
CLOCK_SLACK_S = 1e-3
#: How many offenders of a number above its limit are kept as evidence.
EVIDENCE_ROWS = 20


def id_parts(write_id: int) -> dict:
    """A write's id as the generator wrote it (``closed_kv.write_id``)."""
    write_id = int(write_id)
    if write_id < 0:
        return {"id": write_id}
    return {"generator": write_id >> 56, "loop": write_id >> 40 & 0xFFFF,
            "sequence": write_id & (1 << 40) - 1}


def ids_of(np, values):
    """The write ids that executed values carry (their first 16
    hexadecimal digits), -1 where a value carries none."""
    if not len(values):
        return np.empty(0, dtype=np.int64)
    width = values.dtype.itemsize
    chars = np.frombuffer(values.tobytes(), dtype=np.uint8).reshape(
        len(values), width)
    if width < ID_DIGITS:
        return np.full(len(values), -1, dtype=np.int64)
    chars = chars[:, :ID_DIGITS].astype(np.int64)
    digit = np.where((chars >= 48) & (chars <= 57), chars - 48,
                     np.where((chars >= 97) & (chars <= 102), chars - 87,
                              -1))
    valid = (digit >= 0).all(axis=1) & (digit[:, 0] < 8)
    shifts = 4 * np.arange(ID_DIGITS - 1, -1, -1, dtype=np.int64)
    return np.where(valid, (np.where(digit < 0, 0, digit) << shifts).sum(
        axis=1), -1)


def keep(evidence, name: str, rows) -> None:
    """The first ``EVIDENCE_ROWS`` of ``rows`` (made only as far as they
    are kept) under ``evidence[name]``, over all calls."""
    if evidence is None:
        return
    found = list(itertools.islice(
        rows, EVIDENCE_ROWS - len(evidence.get(name, ()))))
    if found:
        evidence.setdefault(name, []).extend(found)


class PlainRegisters:
    """What the clients saw, as arrays over all generators' operations."""

    def __init__(self, np, generators: list):
        self.np = np
        ops = {name: np.concatenate([g["ops"][name] for g in generators])
               for name in generators[0]["ops"]}
        generator = np.concatenate([
            np.full(len(g["ops"]["kind"]), g["info"].get("index", n))
            for n, g in enumerate(generators)])
        self.key_names = generators[0]["info"]["keys"]
        self.closed_at = max(g["info"]["end_mono_s"] for g in generators)
        answered = ops["latency_s"] >= 0
        self.gave_up = {g["info"].get("index", n): g["info"]["gave_up"]
                        for n, g in enumerate(generators)}
        self.unanswered = int((~answered).sum()) + sum(self.gave_up.values())
        acked_at = np.where(answered, ops["issue_mono_s"] + ops["latency_s"],
                            np.inf)
        self.unanswered_rows = [
            {"generator": int(generator[row]),
             "kind": "read" if ops["kind"][row] == READ else "write",
             "key": self.key_names[ops["key"][row]],
             "issued": float(ops["issue_mono_s"][row]),
             **({} if ops["kind"][row] == READ
                else id_parts(ops["value"][row]))}
            for row in np.flatnonzero(~answered)[:EVIDENCE_ROWS].tolist()]
        writes = ops["kind"] == WRITE
        order = np.argsort(ops["value"][writes], kind="stable")
        self.write_ids = ops["value"][writes][order]
        self.write_keys = ops["key"][writes][order]
        self.write_issued = ops["issue_mono_s"][writes][order]
        self.write_acked = acked_at[writes][order]
        reads = (ops["kind"] == READ) & answered
        self.read_generator = generator[reads]
        self.read_keys = ops["key"][reads]
        self.read_values = ops["value"][reads]
        self.read_issued = ops["issue_mono_s"][reads]
        self.read_answered = acked_at[reads]

    def write_row(self, row: int, place=None) -> dict:
        """One client write, as evidence: who issued it, when it was
        issued and answered (monotonic seconds; None: never), and where
        a replica's log has it."""
        acked = float(self.write_acked[row])
        out = {**id_parts(self.write_ids[row]),
               "key": self.key_names[self.write_keys[row]],
               "issued": float(self.write_issued[row]),
               "answered": acked if acked != float("inf") else None}
        if place is not None:
            out["place"] = int(place)
        return out

    def find(self, ids):
        """For each id its row among the clients' writes, -1 for none."""
        np = self.np
        if not len(self.write_ids):
            return np.full(len(ids), -1, dtype=np.int64)
        rows = np.minimum(np.searchsorted(self.write_ids, ids),
                          len(self.write_ids) - 1)
        return np.where(self.write_ids[rows] == ids, rows, -1)

    def check_log(self, log_keys, log_values, key_names: list,
                  final: dict, evidence=None, replica: str = "") -> tuple:
        """One replica's executed writes against what the clients saw.
        Returns the numbers, and each client write's first place in the
        log (-1: not executed). ``place`` counts the clients' writes the
        replica executed, the launcher's probe left out."""
        np = self.np
        probe = np.array([name == PROBE_KEY for name in key_names],
                         dtype=bool)
        mine = ~probe[log_keys] if len(log_keys) else np.empty(0, bool)
        log_keys, log_values = log_keys[mine], log_values[mine]
        rows = self.find(ids_of(np, log_values))
        known = rows >= 0
        log_names = np.array(key_names, dtype=object)[log_keys]
        client_names = np.array(self.key_names, dtype=object)[
            self.write_keys[rows]]
        unknown = ~known | (log_names != client_names)
        keep(evidence, "replica_writes_unknown", (
            {"replica": replica, "log_entry": at, "key": log_names[at],
             "value": log_values[at].decode(errors="replace")}
            for at in np.flatnonzero(unknown).tolist()))
        rows = rows[known]
        times = np.bincount(rows, minlength=len(self.write_ids))
        place = np.full(len(self.write_ids), len(rows), dtype=np.int64)
        np.minimum.at(place, rows, np.arange(len(rows)))
        place[times == 0] = -1
        # A loop issues its writes one after another, so in the log a
        # loop's ids rise: sorted by loop, stable, each id is above the
        # one before it.
        ids = self.write_ids[rows]
        by_loop = np.argsort(ids >> 40, kind="stable")
        ids = ids[by_loop]
        order_wrong = ((ids[1:] >> 40 == ids[:-1] >> 40)
                       & (ids[1:] <= ids[:-1]))
        keep(evidence, "replica_order_wrong", (
            {"replica": replica,
             "write": self.write_row(rows[by_loop[at + 1]], by_loop[at + 1]),
             "placed_after": self.write_row(rows[by_loop[at]], by_loop[at])}
            for at in np.flatnonzero(order_wrong).tolist()))
        # Real time: nothing later in the log may have been acknowledged
        # before this write was issued.
        later_acked = np.minimum.accumulate(
            np.r_[self.write_acked[rows], np.inf][::-1])[::-1][1:]
        realtime_wrong = (later_acked + CLOCK_SLACK_S
                          < self.write_issued[rows])
        keep(evidence, "replica_realtime_wrong", (
            self.realtime_offender(rows, at, replica)
            for at in np.flatnonzero(realtime_wrong).tolist()))
        # The store against the replica's own log: each key holds the
        # last value executed for it.
        last_at = np.full(len(key_names), -1, dtype=np.int64)
        np.maximum.at(last_at, log_keys, np.arange(len(log_keys)))
        last = {key_names[k]: log_values[at].decode()
                for k, at in enumerate(last_at.tolist()) if at >= 0}
        store_wrong = [name for name in sorted(
            (set(last) | set(final)) - {PROBE_KEY})
            if final.get(name) != last.get(name)]
        keep(evidence, "replica_store_wrong", (
            {"replica": replica, "key": name, "store": final.get(name),
             "last_executed": last.get(name)} for name in store_wrong))
        acked = np.isfinite(self.write_acked)
        lost = acked & (times == 0)
        keep(evidence, "replica_writes_lost", (
            {"replica": replica, "write": self.write_row(row)}
            for row in np.flatnonzero(lost).tolist()))
        keep(evidence, "replica_writes_repeated", (
            {"replica": replica, "times": int(times[row]),
             "write": self.write_row(row, place[row])}
            for row in np.flatnonzero(times > 1).tolist()))
        return {
            "replica_writes_lost": int(lost.sum()),
            "replica_writes_repeated": int((times > 1).sum()),
            "replica_writes_unknown": int(unknown.sum()),
            "replica_order_wrong": int(order_wrong.sum()),
            "replica_realtime_wrong": int(realtime_wrong.sum()),
            "replica_store_wrong": len(store_wrong),
        }, place

    def realtime_offender(self, rows, at: int, replica: str) -> dict:
        """The write at place ``at`` of a replica's log and the one later
        in the log that was answered soonest: answered more than the
        slack before the first was issued."""
        later = at + 1 + int(self.np.argmin(self.write_acked[rows[at + 1:]]))
        write = self.write_row(rows[at], at)
        before = self.write_row(rows[later], later)
        return {"replica": replica, "write": write,
                "placed_before": before,
                "answered_before_issue_by_ms":
                    1e3 * (write["issued"] - before["answered"])}

    def check_reads(self, place, evidence=None) -> tuple:
        """Every answered read against the writes acknowledged before it
        was issued, by their places in one replica's log. Returns the
        wrong reads and the keys never read back."""
        np = self.np
        acked = np.flatnonzero(np.isfinite(self.write_acked))
        # Acknowledged writes by key, then by the instant of the
        # acknowledgement; reads by key.
        acked = acked[np.lexsort((self.write_acked[acked],
                                  self.write_keys[acked]))]
        by_key = np.argsort(self.read_keys, kind="stable")
        read_rows = self.find(self.read_values)
        wrong = 0
        for key in np.unique(self.read_keys).tolist():
            reads = by_key[slice(*np.searchsorted(
                self.read_keys[by_key], [key, key + 1]))]
            writes = acked[slice(*np.searchsorted(
                self.write_keys[acked], [key, key + 1]))]
            # The newest place in the log among the writes acknowledged
            # before each read was issued: no read may return an older.
            newest = np.r_[-1, np.maximum.accumulate(place[writes])]
            least = newest[np.searchsorted(
                self.write_acked[writes],
                self.read_issued[reads] - CLOCK_SLACK_S)]
            rows = read_rows[reads]
            absent = (self.read_values[reads] == -1) & (least < 0)
            sound = ((rows >= 0) & (self.write_keys[rows] == key)
                     & (place[rows] >= 0) & (place[rows] >= least)
                     & (self.write_issued[rows]
                        <= self.read_answered[reads] + CLOCK_SLACK_S))
            wrong += int((~absent & ~sound).sum())
            keep(evidence, "reads_wrong", (
                {"generator": int(self.read_generator[reads[at]]),
                 "key": self.key_names[key],
                 "issued": float(self.read_issued[reads[at]]),
                 "answered": float(self.read_answered[reads[at]]),
                 "returned": (self.write_row(rows[at], place[rows[at]])
                              if rows[at] >= 0
                              else id_parts(self.read_values[reads[at]])),
                 "newest_place_acknowledged_before": int(least[at])}
                for at in np.flatnonzero(~absent & ~sound).tolist()))
        read_back = set(self.read_keys[
            self.read_issued >= self.closed_at].tolist())
        written = set(self.write_keys[acked].tolist())
        keep(evidence, "keys_not_read_back", (
            {"key": self.key_names[key], "window_closed": self.closed_at}
            for key in sorted(written - read_back)))
        return wrong, len(written - read_back)


class PlainQuorums:
    """``quorum`` is the configuration's: ``rows`` of node numbers
    (group * row size + index) and either ``threshold`` (that many of one
    row) or ``one_per_row``."""

    def __init__(self, quorum: dict):
        self.row_size = len(quorum["rows"][0])
        self.row_masks = [sum(1 << node for node in row)
                          for row in quorum["rows"]]
        self.kind = quorum["kind"]
        self.threshold = quorum.get("threshold")
        if self.kind not in ("threshold", "one_per_row"):
            raise ValueError(f"unknown quorum kind {self.kind!r}")
        self.voted: dict = {}
        self.complete: set = set()

    def is_write_quorum(self, mask: int) -> bool:
        if self.kind == "threshold":
            return any(bin(mask & row).count("1") >= self.threshold
                       for row in self.row_masks)
        return all(mask & row for row in self.row_masks)

    def vote(self, slot: int, round: int, group: int, index: int) -> None:
        key = (slot, round)
        if key in self.complete:
            return
        mask = self.voted.get(key, 0) | 1 << (group * self.row_size + index)
        if self.is_write_quorum(mask):
            self.complete.add(key)
            self.voted.pop(key, None)
        else:
            self.voted[key] = mask


def replay_tracker(votes, reports, quorum: dict, evidence=None,
                   tracker: str = "") -> dict:
    """One tracker's record against PlainQuorums. ``votes`` rows are
    (sequence, first slot, end slot, round, group, index), ``reports``
    rows (sequence, slot, round), both in arrival order."""
    plain = PlainQuorums(quorum)
    reported: set = set()
    early: dict = {}                 # (slot, round) -> report's sequence
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

    keep(evidence, "chosen_early", (
        {"tracker": tracker, "slot": slot, "round": round,
         "reported_at_event": early[slot, round]}
        for slot, round in sorted(set(early) - extra)))
    keep(evidence, "chosen_extra", chosen(extra))
    keep(evidence, "chosen_twice", (
        {"tracker": tracker, "slot": slot, "round": round,
         "reported_again_at_event": seq} for slot, round, seq in twice))
    keep(evidence, "chosen_missing", chosen(missing))
    return {"chosen_early": len(set(early) - extra),
            "chosen_extra": len(extra),
            "chosen_twice": len(twice),
            "chosen_missing": len(missing)}


def compare(np, config: dict, generators: list, records: dict,
            evidence=None) -> dict:
    plain = PlainRegisters(np, generators)
    numbers = {"ops_unanswered": plain.unanswered}
    keep(evidence, "ops_unanswered", itertools.chain(
        plain.unanswered_rows,
        ({"generator": index, "gave_up": count}
         for index, count in plain.gave_up.items() if count)))

    replicas = {label: r for label, r in records.items()
                if r["replica"] is not None}
    wanted = config["guarantees"][
        "replicas_holding_every_acknowledged_write"]
    numbers["replicas_missing"] = max(0, wanted - len(replicas))
    if numbers["replicas_missing"]:
        keep(evidence, "replicas_missing", [
            {"wanted": wanted, "wrote_a_log": sorted(replicas)}])
    logs = []
    place = np.full(len(plain.write_ids), -1, dtype=np.int64)
    for label, replica in replicas.items():
        names = replica["record"]["key_names"]
        keys, values = replica["replica"]["keys"], replica["replica"]["values"]
        for final in replica["record"]["stores"]:
            found, place = plain.check_log(keys, values, names, final,
                                           evidence, label)
            for name, count in found.items():
                numbers[name] = numbers.get(name, 0) + count
        logs.append((label, names, keys, values.astype("S64")))
    numbers["replica_logs_differ"] = 0
    for label, names, keys, values in logs[1:]:
        first, first_names, first_keys, first_values = logs[0]
        common = min(len(keys), len(first_keys))
        differ = ((keys[:common] != first_keys[:common])
                  | (values[:common] != first_values[:common]))
        numbers["replica_logs_differ"] += (
            abs(len(keys) - len(first_keys))
            + int(names != first_names)
            + int(np.count_nonzero(differ)))
        if len(keys) != len(first_keys) or names != first_names:
            keep(evidence, "replica_logs_differ", [
                {"replicas": [first, label],
                 "entries": [len(first_keys), len(keys)],
                 "key_names_equal": names == first_names}])
        keep(evidence, "replica_logs_differ", (
            {"replicas": [first, label], "log_entry": at,
             "keys": [first_names[first_keys[at]], names[keys[at]]],
             "values": [first_values[at].decode(errors="replace"),
                        values[at].decode(errors="replace")]}
            for at in np.flatnonzero(differ).tolist()))
    numbers["reads_wrong"], numbers["keys_not_read_back"] = (
        plain.check_reads(place, evidence))

    chosen = {"chosen_early": 0, "chosen_extra": 0, "chosen_twice": 0,
              "chosen_missing": 0}
    violations = 0
    board_wrong = 0
    board = [config["board"]["nodes"], config["board"]["window"]]
    for label, record in records.items():
        for k, (counters, arrays) in enumerate(zip(
                record["record"]["trackers"], record["trackers"])):
            tracker = f"{label}.tracker{k}"
            for name, count in replay_tracker(
                    arrays["votes"], arrays["reports"], config["quorum"],
                    evidence, tracker).items():
                chosen[name] += count
            violations += counters["window_violations"]
            if counters["window_violations"]:
                keep(evidence, "window_violations", [
                    {"tracker": tracker,
                     "votes_dropped": counters["window_violations"]}])
            board_wrong += counters["board_shape"] != board
            if counters["board_shape"] != board:
                keep(evidence, "board_shape_wrong", [
                    {"tracker": tracker, "board": counters["board_shape"],
                     "configuration": board}])
    numbers.update(chosen)
    numbers["window_violations"] = violations
    numbers["board_shape_wrong"] = board_wrong
    claimed = sorted(label for label, r in records.items()
                     if r["record"]["claimed"])
    numbers["chip_owners_wrong"] = abs(len(claimed) - 1)
    if len(claimed) != 1:
        keep(evidence, "chip_owners_wrong", [{"claimed_a_device": claimed}])
    if not any(r["record"]["trackers"] for r in records.values()):
        # No tracker recorded anything: nothing was compared.
        numbers["chosen_missing"] += 1
        keep(evidence, "chosen_missing", [{"trackers_recorded": 0}])
    return {name: (int(value), 0) for name, value in numbers.items()}

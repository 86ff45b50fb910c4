"""The plain reference of a loaded, read-mostly key-value table on
MultiPaxos (a YCSB-style deployment), and the comparison that decides
``correct``.

It imports nothing of the program. From ``multipaxos_kv.py`` beside it
come the plain shared registers (a replica's log held to what clients
saw) and the plain vote sets (a tracker's record replayed); the numbers
those give keep their names and their limit 0. What differs here:

  reads are the bulk of the rows, over a hundred thousand keys, so every
  read is held to the writes acknowledged before it in one pass over
  arrays sorted by (key, instant), with no loop over keys
  (``PlainTable.check_reads``; the rule is ``multipaxos_kv``'s, instants
  rounded to the nanosecond);

  the table was loaded before the window: the generators' first
  ``load_rows`` rows are its inserts, one row a record. They are writes
  like any other (found in the logs, ordered, bounding reads: a record
  that was loaded never reads as absent), but they are read back from the
  replicas' stores and not by the clients;

  the replicas' records are ``role_entry_ids.py``'s: for every executed
  write its key, its id and its value's length, and the store's final
  contents in the same columns.

The deployment's own numbers, each with limit 0:

  table_records_missing  over the replicas, records "0" .. ``records``-1
                         that the store does not hold after the run
  record_width_wrong     executed writes, stored values and values
                         returned to reads whose length is not
                         ``fields`` x ``field_bytes``
  mix_off                1 if the share of reads among the operations
                         issued in the window is outside six standard
                         deviations of the configuration's ``read_share``
                         for that many operations
  skew_off               of the ten keys most often drawn in the window,
                         how many have a share of the operations outside
                         six standard deviations of the share their rank
                         has under ``P(r) ~ r**-zipfian_constant`` over
                         ``records`` ranks (computed here, from the
                         configuration alone). Which record has which
                         rank is the generator's to choose.

The six-sigma bands are binomial, for the run's own count of operations,
so a toy run and a run at the cell's size are held to one rule: a closed
loop draws each operation afresh, and "issued before the window's end"
is a stopping rule, so the counts are unbiased (Wald) however unlike the
operations' durations are.
"""

from __future__ import annotations

import importlib.util
import itertools
import os


def _beside(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        name + ".py")
    spec = importlib.util.spec_from_file_location("bench_reference_" + name,
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


kv = _beside("multipaxos_kv")
keep = kv.keep

ABSENT = -1
#: Instants are packed beside their key into one sortable number:
#: nanoseconds since the run's first instant, in this many bits (4.9 h).
TIME_BITS = 44
SIGMAS = 6.0
HOTTEST = 10


def zipfian_shares(np, ranks: int, constant: float, first: int):
    """The probability of each of the ``first`` hottest ranks under
    ``P(r) ~ r**-constant`` over ``ranks`` ranks."""
    weights = np.arange(1, ranks + 1, dtype=np.float64) ** -constant
    return weights[:first] / weights.sum()


def band(np, share, count: int):
    """Six standard deviations of a binomial share over ``count``
    draws."""
    return SIGMAS * np.sqrt(share * (1.0 - share) / max(count, 1))


class PlainTable(kv.PlainRegisters):
    """``PlainRegisters`` over a loaded table: which writes are the
    load's, what length each read returned, and the reads checked without
    a loop over keys."""

    def __init__(self, np, generators: list):
        super().__init__(np, generators)
        ops = {name: np.concatenate([g["ops"][name] for g in generators])
               for name in ("kind", "value", "latency_s", "length",
                            "issue_mono_s", "key")}
        load = np.concatenate([
            np.arange(len(g["ops"]["kind"])) < g["info"]["load_rows"]
            for g in generators])
        writes = ops["kind"] == kv.WRITE
        order = np.argsort(ops["value"][writes], kind="stable")
        self.write_is_load = load[writes][order]
        reads = (ops["kind"] == kv.READ) & (ops["latency_s"] >= 0)
        self.read_lengths = ops["length"][reads]
        opened_at = min(g["info"]["start_mono_s"] for g in generators)
        issued = ops["issue_mono_s"]
        in_window = (issued >= opened_at) & (issued < self.closed_at)
        self.window_kinds = ops["kind"][in_window]
        self.window_keys = ops["key"][in_window]
        self.first_instant = float(issued.min()) - 1.0 if len(issued) else 0.0

    def stamp(self, keys, seconds):
        """(key, instant) as one number that sorts by key, then by
        instant."""
        np = self.np
        return (keys.astype(np.int64) << TIME_BITS) | np.round(
            (seconds - self.first_instant) * 1e9).astype(np.int64)

    def check_reads(self, place, evidence=None) -> tuple:
        """Every answered read against the writes acknowledged before it
        was issued, by their places in one replica's log. Returns the
        wrong reads and the keys updated after the load and never read
        back."""
        np = self.np
        acked = np.flatnonzero(np.isfinite(self.write_acked))
        stamps = self.stamp(self.write_keys[acked], self.write_acked[acked])
        by_stamp = np.argsort(stamps, kind="stable")
        acked, stamps = acked[by_stamp], stamps[by_stamp]
        # The newest place in the log among a key's writes acknowledged so
        # far: a running maximum that starts anew with every key, each
        # key's places lifted above all those of the keys before it.
        span = int(place.max(initial=-1)) + 2
        lift = self.write_keys[acked].astype(np.int64) * span
        newest = np.maximum.accumulate(lift + place[acked] + 1) - lift - 1
        keys = self.read_keys.astype(np.int64)
        first = np.searchsorted(stamps, keys << TIME_BITS)
        upto = np.searchsorted(stamps, self.stamp(
            self.read_keys, self.read_issued - kv.CLOCK_SLACK_S))
        least = np.where(upto > first,
                         newest[np.maximum(upto, 1) - 1]
                         if len(newest) else -1, -1)
        rows = self.find(self.read_values)
        absent = (self.read_values == ABSENT) & (least < 0)
        sound = ((rows >= 0) & (self.write_keys[rows] == self.read_keys)
                 & (place[rows] >= 0) & (place[rows] >= least)
                 & (self.write_issued[rows]
                    <= self.read_answered + kv.CLOCK_SLACK_S))
        wrong = ~absent & ~sound
        keep(evidence, "reads_wrong", (
            {"generator": int(self.read_generator[at]),
             "key": self.key_names[self.read_keys[at]],
             "issued": float(self.read_issued[at]),
             "answered": float(self.read_answered[at]),
             "returned": (self.write_row(rows[at], place[rows[at]])
                          if rows[at] >= 0
                          else kv.id_parts(self.read_values[at])),
             "newest_place_acknowledged_before": int(least[at])}
            for at in np.flatnonzero(wrong).tolist()))
        updated = np.unique(self.write_keys[acked[
            ~self.write_is_load[acked]]])
        read_back = self.read_keys[self.read_issued >= self.closed_at]
        never = np.setdiff1d(updated, read_back)
        keep(evidence, "keys_not_read_back", (
            {"key": self.key_names[key], "window_closed": self.closed_at}
            for key in never.tolist()))
        return int(wrong.sum()), len(never)

    def check_shape(self, workload: dict, records: int,
                    evidence=None) -> dict:
        """The traffic's shape: the mix and the skew of what was issued
        in the window."""
        np = self.np
        count = len(self.window_kinds)
        wanted = workload["read_share"]
        share = float((self.window_kinds == kv.READ).mean()) if count else 0.0
        mix_off = int(count == 0 or abs(share - wanted)
                      > band(np, wanted, count))
        if mix_off:
            keep(evidence, "mix_off", [
                {"operations_in_window": count, "read_share": share,
                 "configuration": wanted,
                 "band": float(band(np, wanted, count))}])
        drawn = np.sort(np.bincount(self.window_keys, minlength=records)
                        )[::-1][:HOTTEST] / max(count, 1)
        law = zipfian_shares(np, records, workload["zipfian_constant"],
                             HOTTEST)
        off = (np.abs(drawn - law) > band(np, law, count)) | (count == 0)
        keep(evidence, "skew_off", (
            {"operations_in_window": count, "rank": rank + 1,
             "share_drawn": float(drawn[rank]), "share_of_the_law":
             float(law[rank]), "band": float(band(np, law[rank], count))}
            for rank in np.flatnonzero(off).tolist()))
        return {"mix_off": mix_off, "skew_off": int(off.sum())}


def check_table(np, replica: dict, records: int, width: int, label: str,
                evidence=None) -> dict:
    """One replica's store and log against the table's shape: every
    record there, every value ``width`` long."""
    names = replica["key_names"]
    probe = names == kv.PROBE_KEY
    held = names[replica["store_keys"]]
    wanted = np.arange(records).astype("U")
    missing = wanted[~np.isin(wanted, held)]
    keep(evidence, "table_records_missing", (
        {"replica": label, "record": name} for name in missing.tolist()))
    stored_off = (~probe[replica["store_keys"]]
                  & (replica["store_lengths"] != width))
    keep(evidence, "record_width_wrong", (
        {"replica": label, "stored": str(held[at]),
         "length": int(replica["store_lengths"][at]), "width": width}
        for at in np.flatnonzero(stored_off).tolist()))
    executed_off = (~probe[replica["keys"]] & (replica["lengths"] != width)
                    if len(replica["keys"]) else np.zeros(0, bool))
    keep(evidence, "record_width_wrong", (
        {"replica": label, "log_entry": at,
         "key": str(names[replica["keys"][at]]),
         "length": int(replica["lengths"][at]), "width": width}
        for at in np.flatnonzero(executed_off).tolist()))
    return {"table_records_missing": len(missing),
            "record_width_wrong": int(stored_off.sum())
            + int(executed_off.sum())}


def compare(np, config: dict, generators: list, records: dict,
            evidence=None) -> dict:
    plain = PlainTable(np, generators)
    width = config["fields"] * config["field_bytes"]
    numbers = {"ops_unanswered": plain.unanswered}
    keep(evidence, "ops_unanswered", itertools.chain(
        plain.unanswered_rows,
        ({"generator": index, "gave_up": count}
         for index, count in plain.gave_up.items() if count)))

    replicas = {label: r["replica"] for label, r in records.items()
                if r["replica"] is not None}
    wanted = config["guarantees"][
        "replicas_holding_every_acknowledged_write"]
    numbers["replicas_missing"] = max(0, wanted - len(replicas))
    if numbers["replicas_missing"]:
        keep(evidence, "replicas_missing", [
            {"wanted": wanted, "wrote_a_log": sorted(replicas)}])
    numbers.update(table_records_missing=0, record_width_wrong=0)
    logs = []
    place = np.full(len(plain.write_ids), -1, dtype=np.int64)
    for label, replica in replicas.items():
        names = replica["key_names"].tolist()
        final = {names[key]: value.decode() for key, value in zip(
            replica["store_keys"].tolist(), replica["store_values"].tolist())}
        found, place = plain.check_log(replica["keys"], replica["values"],
                                       names, final, evidence, label)
        found.update(check_table(np, replica, config["records"], width,
                                 label, evidence))
        for name, count in found.items():
            numbers[name] = numbers.get(name, 0) + count
        logs.append((label, names, replica["keys"], replica["values"]))
    numbers["replica_logs_differ"] = 0
    for label, names, keys, values in logs[1:]:
        first, first_names, first_keys, first_values = logs[0]
        common = min(len(keys), len(first_keys))
        differ = ((keys[:common] != first_keys[:common])
                  | (values[:common] != first_values[:common]))
        numbers["replica_logs_differ"] += (
            abs(len(keys) - len(first_keys)) + int(names != first_names)
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
    returned_off = ((plain.read_values != ABSENT)
                    & (plain.read_lengths != width))
    keep(evidence, "record_width_wrong", (
        {"generator": int(plain.read_generator[at]),
         "key": plain.key_names[plain.read_keys[at]],
         "returned_length": int(plain.read_lengths[at]), "width": width}
        for at in np.flatnonzero(returned_off).tolist()))
    numbers["record_width_wrong"] += int(returned_off.sum())

    chosen = {"chosen_early": 0, "chosen_extra": 0, "chosen_twice": 0,
              "chosen_missing": 0}
    violations = 0
    board_wrong = 0
    board = [config["board"]["nodes"], config["board"]["window"]]
    for label, record in records.items():
        for k, (counters, arrays) in enumerate(zip(
                record["record"]["trackers"], record["trackers"])):
            tracker = f"{label}.tracker{k}"
            for name, count in kv.replay_tracker(
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
    numbers.update(plain.check_shape(config["workload"], config["records"],
                                     evidence))
    return {name: (int(value), 0) for name, value in numbers.items()}

"""The durable deployment's own files: the sidecar that records what an
fsync covered and the cut of what it did not, the reference on records
made by hand, a recovery that never comes back, and the planted
``ack_before_sync`` end to end at toy size (whose records also show the
dump on a signal, the second life that carries both, and the storage
line)."""

import json
import os
import signal
import sys
import time

from bench_util import BENCHMARK, FAULTS, REPO, run_cell, toy_manifest
from harness import role_entry_durable
from harness.manifest import load_module
from harness.role_entry import expand
import numpy as np
import pytest

CELL = "durable.saturated"
CONFIG = os.path.join(BENCHMARK, "configs", "mp_f1_majority_durable.json")
reference = load_module(os.path.join(BENCHMARK, "reference",
                                     "multipaxos_durable.py"))
deployment = load_module(os.path.join(BENCHMARK, "deployments",
                                      "multipaxos_durable.py"))
DURABILITY = {"acked_write_lost", "acked_write_not_durable_at_quorum",
              "recovered_state_wrong", "recovered_order_differs",
              "storage_not_killed_at_once", "roles_not_recovered",
              "recovery_probe_failed", "unsynced_bytes_discarded"}


# --- the sidecar and the cut ------------------------------------------------

def storage_under(root):
    from frankenpaxos_tpu.wal import FileStorage

    return role_entry_durable.synced_length_storage(FileStorage)(str(root))


def test_the_sidecar_holds_what_the_last_fsync_covered(tmp_path):
    from frankenpaxos_tpu.wal import Wal, WalPromise

    storage = storage_under(tmp_path)
    assert role_entry_durable.read_synced(str(tmp_path)) == (0, 0)
    log = Wal(storage)
    for round in range(3):
        log.append(WalPromise(round=round))
        log.sync()
        assert role_entry_durable.read_synced(str(tmp_path)) == (
            0, os.path.getsize(tmp_path / "seg-00000000.wal"))
    # The word is in the file itself: it outlives the process.
    with open(tmp_path / role_entry_durable.SIDECAR, "rb") as f:
        assert int.from_bytes(f.read(8), "little") == storage.size(
            "seg-00000000.wal")
    # A compaction moves the log on to a new segment.
    from frankenpaxos_tpu.wal import WalSnapshot

    log.compact(WalSnapshot(payload=b""), [WalPromise(round=2)])
    segment, length = role_entry_durable.read_synced(str(tmp_path))
    assert segment == 1 and length == os.path.getsize(
        tmp_path / "seg-00000001.wal")


def test_bytes_appended_and_not_synced_are_cut_and_synced_ones_are_not(
        tmp_path):
    storage = storage_under(tmp_path)
    name = "seg-00000003.wal"
    storage.append("seg-00000002.wal", b"whole" * 10)
    storage.sync("seg-00000002.wal")
    storage.append(name, b"s" * 100)
    storage.sync(name)
    storage.append(name, b"u" * 50)
    storage._handles[name].flush()       # written, and never fsynced
    storage.append("seg-00000004.wal", b"n" * 7)
    storage._handles["seg-00000004.wal"].flush()
    assert os.path.getsize(tmp_path / name) == 150
    assert role_entry_durable.cut_unsynced(str(tmp_path)) == 57
    assert os.path.getsize(tmp_path / name) == 100
    assert os.path.getsize(tmp_path / "seg-00000002.wal") == 50
    assert os.path.getsize(tmp_path / "seg-00000004.wal") == 0
    with open(tmp_path / name, "rb") as f:
        assert f.read() == b"s" * 100
    # Nothing more to cut, and a directory that is not there cuts nothing.
    assert role_entry_durable.cut_unsynced(str(tmp_path)) == 0
    assert role_entry_durable.cut_unsynced(str(tmp_path / "none")) == 0


def test_the_storage_line_names_the_file_system_under_a_path(tmp_path):
    line = role_entry_durable.storage_of(str(tmp_path))
    assert set(line) == {"path", "mount", "device", "fstype"}
    assert line["path"] == os.path.realpath(tmp_path)
    assert line["fstype"] and line["path"].startswith(line["mount"])
    with open("/proc/mounts") as f:
        assert any(fields.split()[1:3] == [line["mount"], line["fstype"]]
                   for fields in f)


# --- the reference on records made by hand ----------------------------------

def wid(generator: int, loop: int, count: int) -> int:
    return generator << 56 | loop << 40 | count


def sound_run(edit=None):
    """The records of one sound run with its recovery, made by hand. Two
    generators of four loops write one shared key for two seconds; the
    system orders each write at an instant between its issue and its
    answer, a slot a write after the launcher's probe in slot 0; two of
    the three acceptors vote for each slot. Both replicas execute the
    log, are killed at its end, come back from a snapshot at half of it
    plus the replay of the rest, and then execute the recovery's probe.
    ``edit(records)`` may break it."""
    with open(CONFIG) as f:
        config = json.load(f)
    rng = np.random.default_rng(5)
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

    end = len(log) + 1                       # the first life's watermark
    half = end // 2                          # the snapshot's
    values = ["0"] + [f"{value:016x}" for value in log]
    recovery = {"recovery_probe_failed": 0, "not_recovered": [],
                "killed_mono_s": {}, "recover_s": 4.0}
    table = {f"('127.0.0.1', {9000 + g})/{loop}": len(log)
             for g in range(2) for loop in range(4)}

    def replica() -> dict:
        store = {"probe": "0", "0": values[-1]}
        return {
            "record": {
                "claimed": False, "trackers": [], "kind": "replica",
                "key_names": ["0", "probe"], "stores": [dict(store)],
                "lives": 2, "unsynced_bytes_discarded": 0,
                "recovery": recovery,
                "life1": {"executed_watermark": end, "store": dict(store),
                          "client_table": dict(table)},
                "life2": {
                    "key_names": ["0", "probe"],
                    "recovered": {
                        "executed_watermark": end, "snapshot_watermark": half,
                        "superseded_writes": 0, "replayed_writes": end - half,
                        "store": dict(store), "client_table": dict(table)},
                    "final": {"executed_watermark": end + 1,
                              "store": {**store, "probe": "recovered-1"},
                              "client_table": dict(table)}}},
            "replica": {
                "keys": np.array([1] + [0] * len(log), dtype=np.int32),
                "values": np.array(values, dtype="S16"),
                "slots": np.arange(end, dtype=np.int64),
                "recovered_keys": np.array([0] * (end - half) + [1],
                                           dtype=np.int32),
                "recovered_values": np.array(
                    values[half:] + ["recovered-1"], dtype="S16"),
                "recovered_slots": np.arange(half, end + 1,
                                             dtype=np.int64)},
            "trackers": []}

    def acceptor(index: int) -> dict:
        mine = [slot for slot in range(end + 1)
                if index in (slot % 3, (slot + 1) % 3)]
        runs = np.array([(slot, slot + 1, 0) for slot in mine],
                        dtype=np.int64)
        none = np.empty((0, 2), dtype=np.int64)
        return {"record": {"claimed": False, "trackers": [],
                           "kind": "acceptor", "lives": 2, "stores": [],
                           "unsynced_bytes_discarded": 0,
                           "recovery": recovery,
                           "life1": {"round": 0}, "life2": {
                               "recovered": {"round": 0},
                               "final": {"round": 0}}},
                "replica": {"voted_runs": runs[runs[:, 0] < end],
                            "voted_slots": none,
                            "recovered_voted_runs": runs[runs[:, 0] < end],
                            "recovered_voted_slots": none},
                "trackers": []}

    # The tracker: every slot's two votes and its report; after the kill
    # the probe's, and a quorum in a later round that is never reported
    # (what a leader change leaves): the first life ends at the mark.
    events = [event for slot in range(end) for event in (
        (slot, slot + 1, 0, 0, slot % 3),
        (slot, slot + 1, 0, 0, (slot + 1) % 3), np.asarray([(slot, 0)]))]
    mark = len(events)
    events += [(end, end + 1, 0, 0, 0), (end, end + 1, 0, 0, 1),
               np.asarray([(end, 0)]),
               (end, end + 1, 2, 0, 0), (end, end + 1, 2, 0, 1)]
    votes, reports = expand(np, events)
    owner = {"record": {"claimed": True, "first_life_events": [mark],
                        "trackers": [{
                            "window_violations": 0,
                            "board_shape": [config["board"]["nodes"],
                                            config["board"]["window"]]}]},
             "replica": None,
             "trackers": [{"votes": votes, "reports": reports}]}
    records = {"proxy_leader_0_1": owner,
               "replica_0": replica(), "replica_1": replica(),
               **{f"acceptor_{n}": acceptor(n) for n in range(3)}}
    for label in records:
        if label != "proxy_leader_0_1":
            recovery["killed_mono_s"][label] = 1010.0 + len(
                recovery["killed_mono_s"]) * 1e-4
    if edit is not None:
        edit(records)
    return config, generators, records


def over(compared: dict) -> set:
    return {name for name, (value, limit) in compared.items()
            if value > limit}


def test_a_sound_run_and_its_recovery_compare_clean():
    evidence: dict = {}
    compared = reference.compare(np, *sound_run(), evidence)
    assert over(compared) == set() and evidence == {}, compared
    assert DURABILITY <= set(compared) and len(compared) == 18 + len(
        DURABILITY)
    assert compared["unsynced_bytes_discarded"] == (0, reference.NO_LIMIT)
    assert all(limit == 0 for name, (_, limit) in compared.items()
               if name != "unsynced_bytes_discarded")


def a_lost_acknowledged_write(records):
    """replica_1 comes back one slot short, and never gets the probe."""
    record, arrays = (records["replica_1"]["record"],
                      records["replica_1"]["replica"])
    end = record["life1"]["executed_watermark"]
    older = arrays["values"][end - 2].decode()
    for state in (record["life2"]["recovered"], record["life2"]["final"]):
        state["executed_watermark"] = end - 1
        state["store"] = {"probe": "0", "0": older}
    for name in ("recovered_keys", "recovered_values", "recovered_slots"):
        arrays[name] = arrays[name][:-2]


def a_vote_held_by_one_acceptor_only(records):
    """acceptor_0's log gives back all its votes but the one for slot 9."""
    arrays = records["acceptor_0"]["replica"]
    runs = arrays["recovered_voted_runs"]
    assert (runs[:, 0] == 9).any()
    arrays["recovered_voted_runs"] = runs[runs[:, 0] != 9]


def a_recovered_value_that_is_an_older_writes(records):
    record, arrays = (records["replica_0"]["record"],
                      records["replica_0"]["replica"])
    record["life2"]["recovered"]["store"]["0"] = arrays["values"][
        -3].decode()


def a_log_that_differs_at_two_slots(records):
    """replica_0 replays two neighbouring writes the other way round:
    nothing is lost, and the last write is the last still."""
    arrays = records["replica_0"]["replica"]
    values = arrays["recovered_values"].copy()
    values[[39, 40]] = values[[40, 39]]
    arrays["recovered_values"] = values


@pytest.mark.parametrize("edit, number, count", [
    (a_lost_acknowledged_write, "acked_write_lost", 1),
    (a_vote_held_by_one_acceptor_only,
     "acked_write_not_durable_at_quorum", 1),
    (a_recovered_value_that_is_an_older_writes, "recovered_state_wrong", 1),
    (a_log_that_differs_at_two_slots, "recovered_order_differs", 2),
], ids=lambda case: getattr(case, "__name__", None))
def test_each_fault_fires_its_own_number_and_no_other(edit, number, count):
    evidence: dict = {}
    compared = reference.compare(np, *sound_run(edit), evidence)
    assert over(compared) == {number}, compared
    assert compared[number] == (count, 0)
    assert set(evidence) == {number} and len(evidence[number]) == count
    json.dumps(evidence)                     # plain numbers and strings


def test_a_write_executed_again_after_the_snapshot_is_not_lost():
    """What the slot rule cannot place (a tail fetched again after the
    recovery) is found among the writes the second life executed."""
    def a_tail_fetched_again(records):
        record = records["replica_1"]["record"]
        record["life2"]["recovered"]["executed_watermark"] -= 3
        older = records["replica_1"]["replica"]["values"][-4].decode()
        record["life2"]["recovered"]["store"]["0"] = older

    compared = reference.compare(np, *sound_run(a_tail_fetched_again), {})
    assert over(compared) == set(), compared


def test_without_the_mark_the_recovery_reads_as_the_first_lifes():
    def no_mark(records):
        del records["proxy_leader_0_1"]["record"]["first_life_events"]

    compared = reference.compare(np, *sound_run(no_mark), {})
    assert over(compared) == {"chosen_missing"}, compared


def test_storage_killed_late_or_never_is_counted():
    def late(records):
        killed = records["replica_0"]["record"]["recovery"]["killed_mono_s"]
        killed["acceptor_2"] += 1.5
        del killed["replica_1"]

    compared = reference.compare(np, *sound_run(late), {})
    assert over(compared) == {"storage_not_killed_at_once"}
    assert compared["storage_not_killed_at_once"] == (2, 0)


# --- a recovery that never comes back ---------------------------------------

ROLE = """
import json, os, signal, sys, time
record_dir, label = sys.argv[1:3]
def dump(*_):
    with open(os.path.join(record_dir, label + ".life1.json"), "w") as f:
        json.dump({"label": label, "claimed": False, "trackers": [],
                   "stores": [], "gc_pause_s": [0, 0, 0], "lives": 1,
                   "kind": label.split("_")[0]}, f)
signal.signal(signal.SIGUSR2, dump)
print("listening", flush=True)
while True:
    time.sleep(0.05)
"""


def test_a_relaunch_that_never_comes_back_is_a_number_inside_the_deadline(
        tmp_path, monkeypatch):
    from frankenpaxos_tpu.bench.harness import (
        BenchmarkDirectory,
        free_port,
        LocalHost,
    )
    from frankenpaxos_tpu.deploy import get_protocol

    for name, seconds in (("GRACE_S", 6.0), ("DUMP_S", 3.0),
                          ("REAP_S", 2.0), ("AGREE_S", 0.5)):
        monkeypatch.setattr(deployment, name, seconds)
    bench = BenchmarkDirectory(str(tmp_path / "run"))
    record_dir = str(tmp_path / "run" / "records")
    os.makedirs(record_dir)
    labels = ["acceptor_0", "acceptor_1", "acceptor_2", "replica_0",
              "replica_1"]
    try:
        for label in labels:
            bench.popen(LocalHost(), label, [
                sys.executable, "-c", ROLE, record_dir, label])
            # Its next life sleeps, and never says it listens.
            bench.role_commands[label] = ([
                sys.executable, "-c", "import time; time.sleep(120)"], None)
        assert deployment.wait_for(
            lambda: all(deployment.is_listening(bench, label)
                        for label in labels), time.monotonic() + 20)
        config = {"f": 1, "flexible": False, "batchers": 0,
                  "read_batchers": 0, "leaders": 2, "proxy_leaders": 2,
                  "acceptor_groups": 1, "acceptors_per_group": 3,
                  "replicas": 2, "proxy_replicas": 0}
        protocol = get_protocol("multipaxos")
        bench.chip_owner = None
        bench.durable = {
            "record_dir": record_dir, "wal_dir": str(tmp_path / "run/wal"),
            "protocol": protocol,
            "loaded": protocol.load_config(deployment.cluster_of(config))}
        assert free_port() > 0
        started = time.monotonic()
        deployment.settle(bench)
        took = time.monotonic() - started
    finally:
        bench.cleanup()
    assert took < deployment.GRACE_S + 3, took
    with open(os.path.join(record_dir, "recovery.json")) as f:
        recovery = json.load(f)
    assert recovery["recovery_probe_failed"] == 1
    assert recovery["not_recovered"] == labels
    assert sorted(recovery["killed_mono_s"]) == labels
    spread = (max(recovery["killed_mono_s"].values())
              - min(recovery["killed_mono_s"].values()))
    assert 0 <= spread < 1.0
    assert "recover_s" not in recovery
    # Every role still has a record, and the comparison still gives its
    # numbers: the probe's failure and the roles that are gone.
    config, generators, records = sound_run()
    for label in labels:
        with open(os.path.join(record_dir, f"{label}.json")) as f:
            record = json.load(f)
        assert record["lives"] == 1 and record["recovery"] == recovery
        records[label] = {"record": {**records[label]["record"], **record},
                          "replica": records[label]["replica"],
                          "trackers": []}
    compared = reference.compare(np, config, generators, records, {})
    assert compared["recovery_probe_failed"] == (1, 0)
    assert compared["roles_not_recovered"] == (5, 0)
    assert compared["acked_write_lost"][0] > 0
    assert compared["storage_not_killed_at_once"] == (0, 0)


# --- what the deployment needs of the program, checked before a launch ------

def test_a_transport_that_reaches_a_next_life_passes_the_precondition():
    deployment.require_a_next_life_is_reached()


def test_a_transport_that_loses_the_first_message_fails_at_once(monkeypatch):
    """The parent's transport: a dead peer is found by the write that is
    lost. The launcher then exits before it has launched anything."""
    from frankenpaxos_tpu.runtime.tcp_transport import TcpTransport

    async def watches_nothing(self, conn, reader, writer):
        return None

    monkeypatch.setattr(TcpTransport, "_watch_peer", watches_nothing)
    started = time.monotonic()
    with pytest.raises(SystemExit, match="cannot run"):
        deployment.require_a_next_life_is_reached()
    assert time.monotonic() - started < 10


# --- the planted fault, end to end ------------------------------------------

def test_acknowledging_before_the_sync_prints_correct_false(
        tmp_path_factory):
    """The real cell at toy size with a storage that returns from
    ``sync()`` one drain early: the last drain before the kill was
    acknowledged and is on no disk."""
    broken = toy_manifest(tmp_path_factory, role_entry=os.path.join(
        FAULTS, "ack_before_sync.py"))
    code, result, errors = run_cell(broken, CELL)
    assert code == 0, errors[-3000:]
    assert result["correct"] is False
    fired = over(result["compared"])
    assert fired & {"acked_write_lost",
                    "acked_write_not_durable_at_quorum"}, result["compared"]
    # By durability alone: every number of the deployment without a log
    # still reads 0.
    assert fired <= DURABILITY, result["compared"]
    assert set(result["offenders"]) == fired
    for name in fired:
        assert f"offender {name}: " + json.dumps(
            result["offenders"][name][0]) in errors
    assert result["failed"] == 0
    assert result["compared"]["storage_not_killed_at_once"] == [0, 0]
    assert result["compared"]["roles_not_recovered"] == [0, 0]

    # What the run left: every storage role dumped on the signal, was
    # killed, and its second life carries both lives and the storage
    # line; the chip owner marked where the first life ended.
    records = os.path.join(REPO, ".bench_runs", CELL, "records")
    with open(os.path.join(records, "recovery.json")) as f:
        recovery = json.load(f)
    assert recovery["not_recovered"] == []
    assert set(recovery["unsynced_bytes_discarded"]) == set(
        recovery["killed_mono_s"]) and len(recovery["killed_mono_s"]) == 5
    for label in recovery["killed_mono_s"]:
        with open(os.path.join(records, f"{label}.json")) as f:
            record = json.load(f)
        assert record["lives"] == 2 and record["kind"] == label.split("_")[0]
        assert record["storage"]["fstype"]
        assert record["storage"]["path"].endswith(os.path.join("wal", label))
        assert {"life1", "life2", "recovery",
                "unsynced_bytes_discarded"} <= set(record)
        assert os.path.exists(os.path.join(records, f"{label}.life1.json"))
        with np.load(os.path.join(records, f"{label}.replica.npz")) as both:
            if record["kind"] == "replica":
                assert {"keys", "values", "slots", "recovered_keys",
                        "recovered_values", "recovered_slots"} <= set(both)
                assert len(both["slots"]) == len(both["keys"])
                # It lost its last drain: it came back short.
                assert (record["life2"]["recovered"]["executed_watermark"]
                        < record["life1"]["executed_watermark"])
            else:
                assert {"voted_runs", "recovered_voted_runs"} <= set(both)
    with open(os.path.join(records, "proxy_leader_0_1.json")) as f:
        assert len(json.load(f)["first_life_events"]) == 2
    assert signal.SIGUSR2 == role_entry_durable.DUMP_SIGNAL

"""The reduction from a trace to numbers, the plain reference on records
made by hand, and whole runs whose timed path is broken underneath the
recorders: those have to print ``correct: false``."""

import json
import os

from bench_util import BENCHMARK, FAULTS, run_cell, toy_manifest
from harness import trace_reduce
from harness.manifest import load_module
from harness.role_entry import COLLECT_SPAN, DRAIN_SPAN, expand, TRACED_SPAN
import numpy as np
import pytest

reference = load_module(os.path.join(BENCHMARK, "reference",
                                     "multipaxos_kv.py"))
KIND = "TPU v5 lite"
MS = 1_000_000  # ns


def planes(ops, host, span=(0, 100 * MS), second_chip=None) -> dict:
    out = {
        "/host:CPU": {"python3": [(TRACED_SPAN, *span)] + host},
        "/device:TPU:0": {"XLA Ops": ops, "XLA Modules": [
            ("jit_whole_program", 0, 1000 * MS)]},
    }
    if second_chip is not None:
        out["/device:TPU:1"] = {"XLA Ops": second_chip}
    return out


def test_busy_is_the_union_of_the_device_operations_inside_the_span():
    reduced = trace_reduce.reduce_planes(planes(
        ops=[("fusion", 10 * MS, 20 * MS),
             ("nested", 12 * MS, 15 * MS),         # inside the first
             ("overlap", 18 * MS, 30 * MS),        # runs on past it
             ("early", -5 * MS, 2 * MS),           # cut at the span's start
             ("outside", 150 * MS, 160 * MS)],     # after the span
        host=[]), KIND, chips=1)
    assert reduced["window_s"] == pytest.approx(0.100)
    assert reduced["busy_s"] == pytest.approx(0.020 + 0.002)
    assert [name for name, _ in reduced["device_ops"]] == [
        "overlap", "fusion", "nested", "early"]
    # Lines other than the table's operation lines are not operations.
    assert all(name != "jit_whole_program"
               for name, _ in reduced["device_ops"])


def test_idle_gaps_are_named_by_what_the_host_was_doing():
    reduced = trace_reduce.reduce_planes(planes(
        ops=[("a", 10 * MS, 20 * MS), ("b", 50 * MS, 60 * MS)],
        host=[(DRAIN_SPAN, 0, 9 * MS),
              ("PjitFunction(record)", 1 * MS, 8 * MS),   # inside the drain
              (COLLECT_SPAN, 22 * MS, 49 * MS),
              (DRAIN_SPAN, 61 * MS, 70 * MS)]), KIND, chips=1)
    gaps = reduced["idle_gaps"]
    # Longest first: 60..100 (the host mostly outside any event), 20..50
    # (the collect), 0..10 (the drain, which covers more of it than the
    # call inside it).
    assert [name for name, _ in gaps] == [
        trace_reduce.UNTRACED, COLLECT_SPAN, DRAIN_SPAN]
    assert [seconds for _, seconds in gaps] == pytest.approx(
        [0.040, 0.030, 0.010])
    assert reduced["busy_s"] + sum(s for _, s in gaps) == pytest.approx(
        reduced["window_s"])
    assert reduced["host_spans"] == {
        DRAIN_SPAN: {"count": 2, "total_s": pytest.approx(0.018)},
        COLLECT_SPAN: {"count": 1, "total_s": pytest.approx(0.027)}}


def test_busy_is_averaged_over_the_chips_used():
    reduced = trace_reduce.reduce_planes(planes(
        ops=[("a", 0, 10 * MS)], host=[],
        second_chip=[("a", 0, 30 * MS)]), KIND, chips=2)
    assert reduced["busy_s"] == pytest.approx(0.020)


def test_at_most_ten_of_each():
    ops = [(f"op{i}", i * 4 * MS, i * 4 * MS + MS * (i % 3 + 1))
           for i in range(25)]
    reduced = trace_reduce.reduce_planes(planes(ops=ops, host=[]), KIND, 1)
    assert len(reduced["device_ops"]) == 10
    assert len(reduced["idle_gaps"]) == 10


def test_a_trace_without_the_marked_span_is_refused():
    broken = planes(ops=[("a", 0, MS)], host=[])
    broken["/host:CPU"]["python3"] = []
    with pytest.raises(ValueError, match=TRACED_SPAN):
        trace_reduce.reduce_planes(broken, KIND, 1)


def test_an_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="no default"):
        trace_reduce.peaks_of("TPU v9 imaginary")
    with pytest.raises(KeyError):
        trace_reduce.reduce_planes(planes(ops=[], host=[]), "TPU v9", 1)
    v5e = trace_reduce.peaks_of(KIND)
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flop_per_s"] == 197e12
    assert v5e["source"]
    assert trace_reduce.peaks_of("cpu")["hbm_bytes_per_s"] is None


def test_least_bytes_counts_votes_decisions_and_the_boards_shape():
    assert trace_reduce.least_bytes(0, 0, 3) == 0
    assert trace_reduce.least_bytes(1000, 0, 3) == 2000
    assert trace_reduce.least_bytes(1000, 500, 3) == 2000 + 4 * 500
    assert trace_reduce.least_bytes(1000, 500, 6) == 2000 + 7 * 500


def test_the_recorded_trace():
    """A trace recorded on one TPU v5 lite chip: four board updates under
    the role entry's annotations (benchmark/harness/testdata)."""
    path = os.path.join(BENCHMARK, "harness", "testdata",
                        "tiny_tpu.xplane.pb")
    reduced = trace_reduce.reduce_planes(trace_reduce.read_planes(path),
                                         KIND, chips=1)
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["host_spans"][DRAIN_SPAN]["count"] == 4
    assert reduced["host_spans"][COLLECT_SPAN]["count"] == 4
    assert reduced["device_ops"] and reduced["idle_gaps"]
    idle = sum(seconds for _, seconds in reduced["idle_gaps"])
    assert reduced["busy_s"] + idle <= reduced["window_s"] * (1 + 1e-9)


# --- the plain reference on records made by hand ---------------------------

MAJORITY = {"kind": "threshold", "rows": [[0, 1, 2]], "threshold": 2}
GRID = {"kind": "one_per_row", "rows": [[0, 1, 2], [3, 4, 5]]}


def record(events) -> tuple:
    return expand(np, [np.asarray(e) if isinstance(e, list) else e
                       for e in events])


def test_plain_quorums_know_both_quorum_rules():
    majority = reference.PlainQuorums(MAJORITY)
    majority.vote(7, 0, 0, 1)
    assert (7, 0) not in majority.complete
    majority.vote(7, 0, 0, 1)          # the same acceptor again
    assert (7, 0) not in majority.complete
    majority.vote(7, 1, 0, 2)          # another round
    assert (7, 0) not in majority.complete
    majority.vote(7, 0, 0, 2)
    assert (7, 0) in majority.complete
    grid = reference.PlainQuorums(GRID)
    grid.vote(3, 0, 0, 0)
    grid.vote(3, 0, 0, 2)              # two of one row are no quorum
    assert (3, 0) not in grid.complete
    grid.vote(3, 0, 1, 1)
    assert (3, 0) in grid.complete


def test_a_sound_tracker_record_compares_clean():
    votes, reports = record([
        (0, 4, 0, 0, 0),                       # slots 0..3 from acceptor 0
        (np.array([0, 1]), np.array([0, 0]), 0, 1),
        [(0, 0), (1, 0)],
        (2, 4, 0, 0, 2),
        [(2, 0), (3, 0)]])
    assert reference.replay_tracker(votes, reports, MAJORITY) == {
        "chosen_early": 0, "chosen_extra": 0, "chosen_twice": 0,
        "chosen_missing": 0}


def test_each_way_a_tracker_can_be_wrong_has_its_number():
    votes, reports = record([
        (0, 4, 0, 0, 0),
        [(0, 0)],                              # before its second vote
        (0, 3, 0, 0, 1),
        [(1, 0), (1, 0)],                      # twice
        [(9, 0)]])                             # never voted for
    # Slot 2 reached its quorum and was never reported; slot 3 has one
    # vote and is rightly silent.
    assert reference.replay_tracker(votes, reports, MAJORITY) == {
        "chosen_early": 1, "chosen_extra": 1, "chosen_twice": 1,
        "chosen_missing": 1}


def wid(loop: int, count: int, generator: int = 0) -> int:
    return generator << 56 | loop << 40 | count


def clients(ops, end=100.0) -> "reference.PlainRegisters":
    """``ops`` rows: (issued, acknowledged or None, kind, key, value)."""
    arrays = {
        "issue_mono_s": np.array([o[0] for o in ops], dtype=np.float64),
        "latency_s": np.array([-1.0 if o[1] is None else o[1] - o[0]
                               for o in ops]),
        "kind": np.array([o[2] for o in ops], dtype=np.int8),
        "key": np.array([o[3] for o in ops], dtype=np.int32),
        "value": np.array([o[4] for o in ops], dtype=np.int64)}
    info = {"keys": ["0", "1"], "end_mono_s": end, "gave_up": 0}
    return reference.PlainRegisters(np, [{"info": info, "ops": arrays}])


def log_of(writes, names=("0", "1", "probe")) -> tuple:
    """``writes`` rows (key name, write id or a value as given)."""
    keys = np.array([names.index(k) for k, _ in writes], dtype=np.int32)
    values = np.array([v if isinstance(v, str) else f"{v:016x}"
                       for _, v in writes], dtype="S")
    return keys, values, list(names)


W, R = 0, 1
# Two loops on key "0", one on key "1". Loop 1's first write and loop 0's
# second overlap in time; everything else is one after another.
OPS = [(1.0, 2.0, W, 0, wid(0, 0)), (1.5, 3.5, W, 0, wid(1, 0)),
       (2.5, 3.0, W, 0, wid(0, 1)), (4.0, 5.0, W, 1, wid(2, 0)),
       (6.0, 7.0, W, 0, wid(1, 1)),
       (101.0, 102.0, R, 0, wid(1, 1)), (101.0, 102.0, R, 1, wid(2, 0))]
LOG = [("probe", "0"), ("0", wid(0, 0)), ("0", wid(0, 1)), ("0", wid(1, 0)),
       ("1", wid(2, 0)), ("0", wid(1, 1))]
FINAL = {"probe": "0", "0": f"{wid(1, 1):016x}", "1": f"{wid(2, 0):016x}"}
CLEAN = {"replica_writes_lost": 0, "replica_writes_repeated": 0,
         "replica_writes_unknown": 0, "replica_order_wrong": 0,
         "replica_realtime_wrong": 0, "replica_store_wrong": 0}


def log_numbers(log, final=FINAL, ops=OPS) -> dict:
    return clients(ops).check_log(*log_of(log), final)[0]


def test_ids_are_read_from_the_values_first_sixteen_digits():
    values = np.array([f"{wid(3, 7, 5):016x}xxxx", "0", "zz" * 8,
                       f"{wid(0, 0):016x}"], dtype="S")
    assert reference.ids_of(np, values).tolist() == [wid(3, 7, 5), -1, -1, 0]


def test_a_sound_log_is_clean_and_concurrent_writes_may_take_either_order():
    assert log_numbers(LOG) == CLEAN
    swapped = [LOG[0], LOG[1], LOG[3], LOG[2], LOG[4], LOG[5]]
    assert log_numbers(swapped) == CLEAN
    # An unanswered write may have executed or not.
    unanswered = OPS[:4] + [(6.0, None, W, 0, wid(1, 1))] + OPS[5:]
    assert log_numbers(LOG, ops=unanswered) == CLEAN
    assert log_numbers(LOG[:-1], {**FINAL, "0": f"{wid(1, 0):016x}"},
                       ops=unanswered) == CLEAN


@pytest.mark.parametrize("log, final, number", [
    # An acknowledged write that the replica never executed.
    (LOG[:4] + LOG[5:], {k: v for k, v in FINAL.items() if k != "1"},
     "replica_writes_lost"),
    (LOG + [LOG[4]], FINAL, "replica_writes_repeated"),
    # A write nobody issued, and one executed on another key.
    (LOG + [("1", wid(9, 9))], {**FINAL, "1": f"{wid(9, 9):016x}"},
     "replica_writes_unknown"),
    (LOG[:4] + [("0", wid(2, 0))] + LOG[5:], {k: v for k, v in FINAL.items()
                                             if k != "1"},
     "replica_writes_unknown"),
    # Loop 0's second write before its first.
    ([LOG[0], LOG[2], LOG[1]] + LOG[3:], FINAL, "replica_order_wrong"),
    # Loop 1's second write, issued at 6, before one acknowledged at 5.
    (LOG[:4] + [LOG[5], LOG[4]], {**FINAL, "0": f"{wid(1, 1):016x}"},
     "replica_realtime_wrong"),
    # The store does not hold the last value executed.
    (LOG, {**FINAL, "0": f"{wid(1, 0):016x}"}, "replica_store_wrong"),
])
def test_each_way_a_replicas_log_can_be_wrong_has_its_number(log, final,
                                                              number):
    found = log_numbers(log, final)
    assert found[number] >= 1, found
    assert {k for k, v in found.items() if v} <= {
        number, "replica_realtime_wrong", "replica_order_wrong"}, found


def reads_numbers(ops, log=LOG) -> tuple:
    plain = clients(ops)
    return plain.check_reads(plain.check_log(*log_of(log), FINAL)[1])


def test_reads_are_held_to_the_writes_acknowledged_before_them():
    assert reads_numbers(OPS) == (0, 0)
    # While loop 1's write is outstanding (1.5 to 3.5) a read may see it
    # or the write before it; after 3.5 it may not go back.
    early = (2.1, 2.2, R, 0, wid(0, 0))
    assert reads_numbers(OPS + [early]) == (0, 0)
    assert reads_numbers(OPS + [(2.6, 2.7, R, 0, wid(1, 0))]) == (0, 0)
    assert reads_numbers(OPS + [(3.6, 3.7, R, 0, wid(0, 1))]) == (1, 0)
    # A key nobody has written reads as absent, and only then.
    assert reads_numbers(OPS + [(0.1, 0.2, R, 1, -1)]) == (0, 0)
    assert reads_numbers(OPS + [(5.5, 5.6, R, 1, -1)]) == (1, 0)
    # A value from the future, another key's, or nobody's.
    assert reads_numbers(OPS + [(3.6, 3.7, R, 0, wid(1, 1))]) == (1, 0)
    assert reads_numbers(OPS + [(5.5, 5.6, R, 1, wid(0, 0))]) == (1, 0)
    assert reads_numbers(OPS + [(5.5, 5.6, R, 1, wid(7, 7))]) == (1, 0)
    # The read-back: every written key, after the window closed.
    assert reads_numbers(OPS[:-1]) == (0, 1)
    assert reads_numbers(OPS[:-2] + [(101.0, 102.0, R, 0, wid(0, 1)),
                                     OPS[-1]]) == (1, 0)


# --- one clock: a whole run's records, and a wall clock that steps -------

ALL_EIGHTEEN = {
    "ops_unanswered", "keys_not_read_back", "reads_wrong",
    "replicas_missing", "replica_writes_lost", "replica_writes_repeated",
    "replica_writes_unknown", "replica_order_wrong",
    "replica_realtime_wrong", "replica_store_wrong", "replica_logs_differ",
    "chosen_early", "chosen_extra", "chosen_twice", "chosen_missing",
    "window_violations", "board_shape_wrong", "chip_owners_wrong"}
STEP_AT = 1001.0      # time.monotonic() seconds


def sound_run(wall_step_s: float = 0.0, extra_ops=(), log_edit=None):
    """The records of one sound run, made by hand: two generators of
    sixteen closed loops write one shared key for two seconds (50-150 ms
    a write, so 32 are in flight at any instant), the system orders
    each write somewhere between its issue and its answer, both replicas
    execute that order, the tracker hears two acceptors a slot and
    reports it, and each generator reads the key back after the window.

    Returns ``(config, generators, records, wall)``: every instant in
    ``generators`` is monotonic; ``wall`` is what ``time.time()`` would
    have read at each operation's issue, on a host whose wall clock is
    stepped by ``wall_step_s`` at ``STEP_AT``."""
    with open(os.path.join(BENCHMARK, "configs",
                           "mp_f1_majority.json")) as f:
        config = json.load(f)
    rng = np.random.default_rng(7)
    start, end = 1000.0, 1002.0
    ops = []          # (generator, issued, answered, kind, value, ordered)
    for generator in range(2):
        for loop in range(16):
            at, count = start + rng.uniform(0, 0.05), 0
            while at < end:
                answered = at + rng.uniform(0.05, 0.15)
                ops.append((generator, at, answered, W,
                            wid(loop, count, generator),
                            rng.uniform(at, answered)))
                at, count = answered + 1e-4, count + 1
    ops.extend(extra_ops)
    log = [op[4] for op in sorted(ops, key=lambda op: op[5])]
    if log_edit is not None:
        log = log_edit(log)
    closed = max(op[2] for op in ops) + 0.01
    ops += [(generator, closed, closed + 0.02, R, log[-1], None)
            for generator in range(2)]

    def wall_of(mono: float) -> float:
        return mono + 1.7e9 + (wall_step_s if mono >= STEP_AT else 0.0)

    generators, wall = [], []
    for generator in range(2):
        mine = [op for op in ops if op[0] == generator]
        generators.append({
            "info": {"index": generator, "keys": ["0"], "end_mono_s": end,
                     "gave_up": 0, "wall_minus_mono_s": [1.7e9, 1.7e9]},
            "ops": {
                "issue_mono_s": np.array([op[1] for op in mine]),
                "latency_s": np.array([op[2] - op[1] for op in mine]),
                "kind": np.array([op[3] for op in mine], dtype=np.int8),
                "key": np.zeros(len(mine), dtype=np.int32),
                "value": np.array([op[4] for op in mine], dtype=np.int64)}})
        wall.append({"issue": np.array([wall_of(op[1]) for op in mine]),
                     "end": wall_of(end)})
    keys, values, names = log_of([("probe", "0")] + [("0", v) for v in log])
    replica = {"record": {"claimed": False, "trackers": [],
                          "key_names": names,
                          "stores": [{"probe": "0",
                                      "0": f"{log[-1]:016x}"}]},
               "replica": {"keys": keys, "values": values}, "trackers": []}
    votes, reports = record(
        [event for slot in range(len(log) + 1)
         for event in ((slot, slot + 1, 0, 0, 0), (slot, slot + 1, 0, 0, 2),
                       [(slot, 0)])])
    owner = {"record": {"claimed": True, "trackers": [
                 {"window_violations": 0,
                  "board_shape": [config["board"]["nodes"],
                                  config["board"]["window"]]}]},
             "replica": None,
             "trackers": [{"votes": votes, "reports": reports}]}
    records = {"proxy_leader_0_1": owner, "replica_0": replica,
               "replica_1": dict(replica)}
    return config, generators, records, wall


def on_the_wall_clock(generators, wall) -> list:
    """The same records as the parent's generators wrote them: the issue
    instant from ``time.time()``, the latency from a clock that does not
    step, the answer's instant their sum."""
    return [{"info": {**g["info"], "end_mono_s": w["end"]},
             "ops": {**g["ops"], "issue_mono_s": w["issue"]}}
            for g, w in zip(generators, wall)]


@pytest.mark.parametrize("wall_step_ms", [-250, 250, 0])
def test_a_step_of_the_wall_clock_inside_the_run_counts_nothing(
        wall_step_ms):
    config, generators, records, wall = sound_run(wall_step_ms / 1e3)
    evidence = {}
    compared = reference.compare(np, config, generators, records, evidence)
    assert set(compared) == ALL_EIGHTEEN
    assert all(value == 0 and limit == 0
               for value, limit in compared.values()), compared
    assert evidence == {}
    # The yardstick before this clock rule: the same run, its instants
    # as time.time() read them. A step reads as writes out of real-time
    # order, and as nothing else.
    stepped = reference.compare(np, config,
                                on_the_wall_clock(generators, wall), records)
    over = {name for name, (value, _) in stepped.items() if value}
    assert over == ({"replica_realtime_wrong"} if wall_step_ms else set())
    if wall_step_ms:
        # In both replicas, among the 32 writes in flight across it. (A
        # step back counts more: every write issued inside the step's
        # length less a latency before it. A step forward counts only
        # writes the system ordered after a later-issued one.)
        assert stepped["replica_realtime_wrong"][0] >= 2 * 8


def test_a_true_inversion_is_counted_and_its_offenders_are_printed(capsys):
    """Loop 101's write is issued 5 ms after loop 100's was answered, and
    the replicas' logs have it first."""
    first, second = wid(100, 0, 1), wid(101, 0, 0)
    extra = [(1, 1003.000, 1003.050, W, first, 1003.025),
             (0, 1003.055, 1003.100, W, second, 1003.080)]

    def swap(log: list) -> list:
        a, b = log.index(first), log.index(second)
        log[a], log[b] = second, first
        return log

    config, generators, records, _ = sound_run(extra_ops=extra,
                                               log_edit=swap)
    evidence = {}
    compared = reference.compare(np, config, generators, records, evidence)
    over = {name for name, (value, _) in compared.items() if value}
    assert over == {"replica_realtime_wrong"}, compared
    offenders = evidence["replica_realtime_wrong"]
    assert len(offenders) == min(compared["replica_realtime_wrong"][0],
                                 reference.EVIDENCE_ROWS)
    planted = [o for o in offenders if o["replica"] == "replica_0"
               and o["write"]["loop"] == 101
               and o["placed_before"]["loop"] == 100]
    assert planted, offenders
    write, before = planted[0]["write"], planted[0]["placed_before"]
    assert (write["generator"], write["sequence"]) == (0, 0)
    assert (before["generator"], before["sequence"]) == (1, 0)
    assert (write["issued"], write["answered"]) == (1003.055, 1003.100)
    assert (before["issued"], before["answered"]) == (1003.000, 1003.050)
    assert write["place"] < before["place"]
    assert planted[0]["answered_before_issue_by_ms"] == pytest.approx(5.0)
    # As run.py prints them: the offenders, the clock, and last the
    # numbers compared.
    launcher = load_module(os.path.join(BENCHMARK, "run.py"))
    launcher.print_compared(compared, evidence, {"wall_step_ms": 0.0})
    lines = capsys.readouterr().err.splitlines()
    assert lines[0] == ("offender replica_realtime_wrong: "
                        + json.dumps(offenders[0]))
    assert lines[len(offenders)] == "clock wall_step_ms: 0.0"
    assert lines[-len(compared):] == [
        f"compared {name}: {value} (limit {limit})"
        for name, (value, limit) in compared.items()]


def test_every_number_above_its_limit_leaves_its_rows():
    """The other seventeen: each fault of the tests above, through
    ``compare``, leaves the rows its count was made from."""
    config, generators, records, _ = sound_run()
    log = records["replica_0"]["replica"]
    broken = dict(records)
    # A replica that lost its last write, executed its first twice and
    # holds another value than it last executed; a tracker that reported
    # slot 1 before its second vote, slot 2 twice, slot 900 never voted.
    keys = np.r_[log["keys"][:-1], log["keys"][1]]
    values = np.r_[log["values"][:-1], log["values"][1]]
    broken["replica_1"] = {**records["replica_1"],
                           "replica": {"keys": keys, "values": values}}
    votes, reports = record([(1, 2, 0, 0, 0), [(1, 0)], (1, 3, 0, 0, 1),
                             (2, 3, 0, 0, 2), [(2, 0), (2, 0)], [(900, 0)],
                             (3, 4, 0, 0, 0), (3, 4, 0, 0, 1)])
    broken["proxy_leader_0_1"] = {
        "record": {"claimed": False, "trackers": [
            {"window_violations": 3, "board_shape": [3, 64]}]},
        "replica": None, "trackers": [{"votes": votes, "reports": reports}]}
    generators[0]["ops"]["latency_s"][0] = -1.0
    evidence = {}
    compared = reference.compare(np, config, generators, broken, evidence)
    over = {name for name, (value, _) in compared.items() if value}
    assert over == set(evidence)
    assert over >= {
        "ops_unanswered", "replica_writes_lost", "replica_writes_repeated",
        "replica_store_wrong", "replica_logs_differ", "chosen_early",
        "chosen_extra", "chosen_twice", "chosen_missing",
        "window_violations", "board_shape_wrong", "chip_owners_wrong"}
    for name, rows in evidence.items():
        assert 0 < len(rows) <= reference.EVIDENCE_ROWS
        assert len(rows) == min(compared[name][0], reference.EVIDENCE_ROWS) \
            or name in ("window_violations", "replica_logs_differ")
        json.dumps(rows)           # plain numbers and strings throughout
    assert evidence["chosen_early"][0]["slot"] == 1
    assert evidence["chosen_extra"][0]["slot"] == 900
    assert evidence["replica_writes_lost"][0]["replica"] == "replica_1"
    assert evidence["window_violations"] == [
        {"tracker": "proxy_leader_0_1.tracker0", "votes_dropped": 3}]


# --- whole runs with the timed path broken underneath ----------------------


@pytest.mark.parametrize("cell, fault, numbers", [
    # The control: one vote taken for a write quorum.
    ("majority.saturated", "quorum_of_one", {"chosen_early"}),
    pytest.param("grid2x3.saturated", "quorum_of_one", {"chosen_early"},
                 marks=pytest.mark.slow),
    # An answer altered where it is produced.
    ("majority.saturated", "extra_slot", {"chosen_extra"}),
    # An acknowledged write that no replica's store is given.
    ("majority.saturated", "dropped_write", {"replica_writes_lost"}),
])
def test_a_broken_run_prints_correct_false(tmp_path_factory, cell, fault,
                                           numbers):
    """The real cell at toy size, with the fault's role entry in the
    benchmark's place."""
    broken = toy_manifest(tmp_path_factory,
                          role_entry=os.path.join(FAULTS, fault + ".py"))
    code, result, errors = run_cell(broken, cell)
    assert code == 0, errors[-3000:]
    assert result["correct"] is False
    over = {name for name, (value, limit) in result["compared"].items()
            if value > limit}
    assert numbers <= over, result["compared"]
    # Each number above its limit leaves the rows it was counted from.
    assert set(result["offenders"]) == over
    for name in over:
        assert f"offender {name}: " + json.dumps(
            result["offenders"][name][0]) in errors
    # Nothing else is broken: the fault fails its own numbers only.
    allowed = numbers | {"chosen_extra", "chosen_early",
                         "replica_store_wrong", "reads_wrong"}
    assert over <= allowed, result["compared"]


@pytest.mark.parametrize("fault, step_ms", [("wall_step", 250),
                                            ("wall_step_back", -250)])
def test_a_stepped_wall_clock_is_reported_and_decides_nothing(
        tmp_path_factory, fault, step_ms):
    """The real cell at toy size, with generators whose ``time.time()``
    jumps halfway through the window: the host's fault, not the
    system's, so the run is correct, and says that the clock moved."""
    stepped = toy_manifest(tmp_path_factory,
                           generator=os.path.join(FAULTS, fault + ".py"))
    code, result, errors = run_cell(stepped, "majority.saturated")
    assert code == 0, errors[-3000:]
    assert result["correct"] is True, result["compared"]
    assert set(result["compared"]) == ALL_EIGHTEEN
    assert all(value == 0 for value, _ in result["compared"].values())
    assert "offenders" not in result and "offender " not in errors
    assert result["attempted"] > 0 and result["failed"] == 0
    # Every generator saw the step and the launcher did not (measured
    # against the launcher, so that a step of this host's own clock
    # meanwhile does not fail the test).
    moved = result["clock"]["by_process_ms"]
    for name in ("generator_0", "generator_1"):
        assert moved[name] - moved["launcher"] == pytest.approx(step_ms,
                                                                abs=5)
    assert result["clock"]["wall_step_ms"] == max(moved.values(), key=abs)
    assert f"clock wall_step_ms: {result['clock']['wall_step_ms']}" in errors

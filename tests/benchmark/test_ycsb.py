"""The YCSB-style cell's own pieces: the generator's draws against the
law computed here, the reference on records made by hand (each fault
fires its own number), and a whole run with a planted stale read, which
has to print ``correct: false``."""

import json
import os

from bench_util import BENCHMARK, FAULTS, manifest, run_cell, toy_manifest
from harness.manifest import load_module
from harness.role_entry import expand
import numpy as np
import pytest

generator = load_module(os.path.join(BENCHMARK, "generators", "ycsb_kv.py"))
reference = load_module(os.path.join(BENCHMARK, "reference",
                                     "multipaxos_ycsb.py"))
CONFIG = os.path.join(BENCHMARK, "configs", "mp_f1_majority_ycsb.json")
CELL = next(cell["name"] for cell in manifest()["workloads"]
            if cell["config"] == "mp_f1_majority_ycsb")
W, R = 0, 1
WIDTH = 1000


def law(ranks: int, constant: float) -> np.ndarray:
    """The Zipfian law, written out again here: P(r) = r^-c / H."""
    weights = np.array([1.0 / r ** constant for r in range(1, ranks + 1)])
    return weights / weights.sum()


# --- the generator's draws -------------------------------------------------

with open(os.path.join(BENCHMARK, "traffic", "ycsb_b_closed4096.json")) as f:
    TRAFFIC = json.load(f)


def test_the_mix_file_is_workload_b():
    assert TRAFFIC["read_share"] == 0.95
    assert TRAFFIC["key_distribution"] == "zipfian"
    assert TRAFFIC["zipfian_constant"] == 0.99 and TRAFFIC["scrambled"]
    assert TRAFFIC["keys"] == 100000 and TRAFFIC["value_bytes"] == WIDTH
    assert TRAFFIC["client_procs"] * TRAFFIC["loops_per_proc"] == 4096
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["records"] == TRAFFIC["keys"]
    assert config["fields"] * config["field_bytes"] == WIDTH
    assert config["workload"]["read_share"] == TRAFFIC["read_share"]
    assert (config["workload"]["zipfian_constant"]
            == TRAFFIC["zipfian_constant"])
    assert config["read_batchers"] == 0
    assert not any("unsafe" in key for key in config["client_options"])


def test_draws_follow_the_law_and_the_read_share():
    draws = generator.Draws(np, 2_147_483_659, 1, TRAFFIC)
    count = 1 << 20
    keys, reads = draws.block(count)
    wanted = law(TRAFFIC["keys"], TRAFFIC["zipfian_constant"])
    assert 0.078 < wanted[0] < 0.083 and 0.22 < wanted[:10].sum() < 0.24
    # By rank: undo the seed's permutation, which every process shares.
    rank_of = np.empty(TRAFFIC["keys"], dtype=np.int64)
    rank_of[draws.record_of] = np.arange(TRAFFIC["keys"])
    drawn = np.bincount(rank_of[keys], minlength=TRAFFIC["keys"]) / count
    sigma = np.sqrt(wanted * (1 - wanted) / count)
    assert (np.abs(drawn - wanted)[:1000] < 5 * sigma[:1000]).all()
    assert abs(drawn[:10].sum() - wanted[:10].sum()) < 5 * np.sqrt(
        0.23 * 0.77 / count)
    # The tail is there too: most records are drawn at some time (the last
    # rank is expected 0.9 times in these draws).
    assert (drawn > 0).sum() > 0.8 * TRAFFIC["keys"]
    assert abs(reads.mean() - 0.95) < 5 * np.sqrt(0.95 * 0.05 / count)
    # The hot records are scattered over the table, not its first rows.
    assert sorted(draws.record_of[:10].tolist()) != list(range(10))
    other = generator.Draws(np, 2_147_483_659, 0, TRAFFIC)
    assert (other.record_of == draws.record_of).all()
    assert (generator.Draws(np, 7, 1, TRAFFIC).record_of
            != draws.record_of).any()


def test_the_same_seed_gives_the_same_operations():
    def first(seed: int, index: int, count: int = 70000) -> list:
        draws = generator.Draws(np, seed, index, TRAFFIC)
        return [draws.next() for _ in range(count)]  # crosses a block

    assert first(12345, 2) == first(12345, 2)
    assert first(12345, 2) != first(12345, 3)
    assert first(12345, 2) != first(12346, 2)
    assert {type(v) for pair in first(1, 0, 10) for v in pair} == {int, bool}


# --- the reference, on records made by hand --------------------------------

RECORDS = 2000


def wid(loop: int, count: int, generator: int = 0) -> int:
    return generator << 56 | loop << 40 | count


def sound_run(read_share: float = 0.95, uniform: bool = False,
              edit=None):
    """The records of one sound run, made by hand: two generators load
    2000 records between them (batches of 50, one instant a batch), then
    sixteen closed loops each draw keys from the law and read or update
    for four seconds (2-10 ms an operation). The system orders every
    operation at an instant between its issue and its answer; a read
    returns the key's newest value at its instant. Both replicas execute
    the writes in that order. ``edit(ops, log, store)`` may break it:
    ``ops`` rows are [generator, issued, answered, kind, key, value,
    length], ``log`` rows [key, value, length], ``store`` {key: [value,
    length]}."""
    with open(CONFIG) as f:
        config = json.load(f)
    config["records"] = RECORDS
    rng = np.random.default_rng(11)
    chances = (np.full(RECORDS, 1 / RECORDS) if uniform
               else law(RECORDS, config["workload"]["zipfian_constant"]))
    record_of = rng.permutation(RECORDS)
    start, end = 1000.0, 1004.0
    ops, counts = [], {}

    def next_id(generator, loop):
        counts[generator, loop] = counts.get((generator, loop), -1) + 1
        return wid(loop, counts[generator, loop], generator)

    for generator in range(2):
        mine = list(range(generator, RECORDS, 2))
        for batch in range(0, len(mine), 50):
            issued = 990.0 + batch / 1000 + generator / 10000
            for key in mine[batch:batch + 50]:
                ops.append([generator, issued, issued + 0.05, W, key,
                            next_id(generator, 0), WIDTH, issued + 0.02])
    for generator in range(2):
        for loop in range(16):
            at = start - 0.3 + rng.uniform(0, 0.01)
            while at < end:
                answered = at + rng.uniform(0.002, 0.010)
                key = int(record_of[rng.choice(RECORDS, p=chances)])
                read = rng.random() < read_share
                ops.append([generator, at, answered, R if read else W, key,
                            None if read else next_id(generator, loop),
                            WIDTH, rng.uniform(at, answered)])
                at = answered + 1e-4
    closed = max(op[2] for op in ops) + 0.01
    updated = {(op[0], op[4]) for op in ops[RECORDS:] if op[3] == W}
    ops += [[generator, closed, closed + 0.02, R, key, None, WIDTH,
             closed + 0.01] for generator, key in sorted(updated)]
    holds, log = {}, []
    for op in sorted(ops, key=lambda op: op[7]):
        if op[3] == W:
            holds[op[4]] = op[5]
            log.append([op[4], op[5], WIDTH])
        else:
            op[5] = holds[op[4]]
    store = {key: [value, WIDTH] for key, value in holds.items()}
    if edit is not None:
        edit(ops, log, store)

    generators = []
    for generator in range(2):
        mine = [op for op in ops if op[0] == generator]
        generators.append({
            "info": {"index": generator,
                     "keys": [str(k) for k in range(RECORDS)],
                     "load_rows": sum(op[1] < 995.0 for op in mine),
                     "start_mono_s": start, "end_mono_s": end,
                     "gave_up": 0, "wall_minus_mono_s": [1.7e9, 1.7e9]},
            "ops": {
                "issue_mono_s": np.array([op[1] for op in mine]),
                "latency_s": np.array([op[2] - op[1] for op in mine]),
                "kind": np.array([op[3] for op in mine], dtype=np.int8),
                "key": np.array([op[4] for op in mine], dtype=np.int32),
                "value": np.array([op[5] for op in mine], dtype=np.int64),
                "length": np.array([op[6] for op in mine],
                                   dtype=np.int32)}})
    names = sorted({str(key) for key, _, _ in log} | {"probe"})
    place = {name: n for n, name in enumerate(names)}
    replica = {
        "key_names": np.array(names, dtype="U"),
        "keys": np.array([place["probe"]]
                         + [place[str(k)] for k, _, _ in log],
                         dtype=np.int32),
        "values": np.array(["0"] + [f"{v:016x}" for _, v, _ in log],
                           dtype="S16"),
        "lengths": np.array([1] + [n for _, _, n in log], dtype=np.int32),
        "store_keys": np.array([place["probe"]]
                               + [place[str(k)] for k in store],
                               dtype=np.int32),
        "store_values": np.array(
            ["0"] + [f"{v:016x}" for v, _ in store.values()], dtype="S16"),
        "store_lengths": np.array([1] + [n for _, n in store.values()],
                                  dtype=np.int32)}
    votes, reports = expand(np, [
        event for slot in range(len(log) + 1)
        for event in ((slot, slot + 1, 0, 0, 0), (slot, slot + 1, 0, 0, 2),
                      np.asarray([(slot, 0)]))])
    owner = {"record": {"claimed": True, "trackers": [
                 {"window_violations": 0,
                  "board_shape": [config["board"]["nodes"],
                                  config["board"]["window"]]}]},
             "replica": None,
             "trackers": [{"votes": votes, "reports": reports}]}
    plain = {"record": {"claimed": False, "trackers": []},
             "replica": replica, "trackers": []}
    return config, generators, {"proxy_leader_0_1": owner,
                                "replica_0": plain, "replica_1": dict(plain)}


def over(compared: dict) -> set:
    return {name for name, (value, limit) in compared.items()
            if value > limit}


def test_a_sound_run_compares_clean_on_all_twenty_two_numbers():
    evidence: dict = {}
    compared = reference.compare(np, *sound_run(), evidence)
    assert over(compared) == set() and evidence == {}, compared
    assert len(compared) == 22
    assert {"table_records_missing", "record_width_wrong", "mix_off",
            "skew_off", "reads_wrong", "keys_not_read_back"} <= set(compared)


def window_reads(ops):
    return [op for op in ops if op[3] == R and 1000.0 <= op[1] < 1004.0]


def a_stale_read(ops, log, store):
    """A read answered with the value its key held two writes ago, both
    acknowledged long before the read was issued."""
    for read in reversed(window_reads(ops)):
        before = [op for op in ops if op[3] == W and op[4] == read[4]
                  and op[2] < read[1] - 0.1]
        if len(before) >= 2:
            read[5] = sorted(before, key=lambda op: op[7])[-2][5]
            return
    raise AssertionError("no key was written twice before a read")


def a_read_from_the_future(ops, log, store):
    """A read answered with a value that was written only after the
    read had been answered."""
    for read in window_reads(ops):
        later = [op for op in ops if op[3] == W and op[4] == read[4]
                 and op[1] > read[2] + 0.1]
        if later:
            read[5] = later[0][5]
            return
    raise AssertionError("no key was written after a read of it")


def a_missing_record(ops, log, store):
    """A record the load left out: no insert, so no log entry and no
    value in the stores (one that no operation of the run touches; a
    record dropped from a store alone is also a store that disagrees
    with its log)."""
    touched = {op[4] for op in ops[RECORDS:]}
    key = next(key for key in range(RECORDS) if key not in touched)
    ops[:] = [op for op in ops if op[4] != key]
    log[:] = [entry for entry in log if entry[0] != key]
    del store[key]


def a_short_value(ops, log, store):
    """One update stored and executed 10 bytes short."""
    entry = log[RECORDS + 3]
    entry[2] = WIDTH - 10
    if store[entry[0]][0] == entry[1]:
        store[entry[0]][1] = WIDTH - 10


def a_short_answer(ops, log, store):
    window_reads(ops)[5][6] = 16


@pytest.mark.parametrize("edit, kwargs, number", [
    (a_stale_read, {}, "reads_wrong"),
    (a_read_from_the_future, {}, "reads_wrong"),
    (a_missing_record, {}, "table_records_missing"),
    (a_short_value, {}, "record_width_wrong"),
    (a_short_answer, {}, "record_width_wrong"),
    (None, {"uniform": True}, "skew_off"),
    (None, {"read_share": 0.5}, "mix_off"),
], ids=["stale_read", "future_read", "missing_record", "short_value",
        "short_answer", "uniform_keys", "half_reads"])
def test_each_fault_fires_its_own_number_and_nothing_else(edit, kwargs,
                                                          number, capsys):
    evidence: dict = {}
    compared = reference.compare(np, *sound_run(edit=edit, **kwargs),
                                 evidence)
    assert over(compared) == {number}, compared
    assert set(evidence) == {number}
    assert 1 <= len(evidence[number]) <= 20
    for row in evidence[number]:
        print(f"offender {number}: {json.dumps(row)}")
    assert f"offender {number}: " in capsys.readouterr().out
    if number == "skew_off":
        assert compared[number][0] >= 5    # most of the ten hottest
        assert evidence[number][0]["rank"] == 1
    if number == "reads_wrong":
        assert compared[number] == (1, 0)
        assert evidence[number][0]["returned"]["key"] == evidence[
            number][0]["key"]


def test_the_reads_rule_is_the_shared_registers_rule():
    """Vectorised over keys here, looped over keys there: the same
    verdict on every read of a run with a hundred reads altered."""
    def scramble(ops, log, store):
        rng = np.random.default_rng(3)
        reads = window_reads(ops)
        writes = [op for op in ops if op[3] == W]
        for at in rng.choice(len(reads), 100, replace=False):
            reads[at][5] = writes[rng.integers(len(writes))][5]

    config, generators, records = sound_run(edit=scramble)
    plain = reference.PlainTable(np, generators)
    replica = records["replica_0"]["replica"]
    names = replica["key_names"].tolist()
    final = {names[k]: v.decode() for k, v in zip(
        replica["store_keys"].tolist(), replica["store_values"].tolist())}
    place = plain.check_log(replica["keys"], replica["values"], names,
                            final)[1]
    looped, fast = {}, {}
    wrong_looped = reference.kv.PlainRegisters.check_reads(plain, place,
                                                           looped)[0]
    wrong_fast = plain.check_reads(place, fast)[0]
    assert wrong_fast == wrong_looped and 50 < wrong_fast <= 100

    def seen(evidence):
        return {(row["key"], row["issued"])
                for row in evidence["reads_wrong"]}

    assert len(seen(fast)) == 20 and seen(fast) <= {
        (plain.key_names[k], float(t)) for k, t in zip(
            plain.read_keys.tolist(), plain.read_issued.tolist())}


def test_the_law_of_the_reference_is_its_own():
    shares = reference.zipfian_shares(np, 100000, 0.99, 10)
    assert np.allclose(shares, law(100000, 0.99)[:10], rtol=1e-12)
    assert reference.band(np, 0.95, 10**6) == pytest.approx(
        6 * np.sqrt(0.95 * 0.05 / 10**6))


# --- a whole run with a stale read planted ---------------------------------

def test_a_planted_stale_read_prints_correct_false(tmp_path_factory):
    """The real cell at toy size, with the fault's role entry in the
    benchmark's place: writes are applied as given, reads are told the
    value before the newest."""
    broken = toy_manifest(tmp_path_factory,
                          role_entry=os.path.join(FAULTS, "stale_read.py"))
    code, result, errors = run_cell(broken, CELL)
    assert code == 0, errors[-3000:]
    assert result["correct"] is False
    assert over(result["compared"]) == {"reads_wrong"}, result["compared"]
    assert result["compared"]["reads_wrong"][0] > 0
    assert set(result["offenders"]) == {"reads_wrong"}
    assert "offender reads_wrong: " + json.dumps(
        result["offenders"]["reads_wrong"][0]) in errors
    assert result["failed"] == 0

"""``BENCHMARK.json`` against the driver's rules of form, the files it
names, and the requirement that a later PR adds a cell, a mix and a metric
as files and edits nothing that is there."""

import hashlib
import json
import os
import re
import shutil

from bench_util import BENCHMARK, manifest, REPO, run_cell
from harness.manifest import Manifest
import pytest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(_dim|_rank)$")

M = manifest()
LOADED = Manifest(os.path.join(REPO, "BENCHMARK.json"))
METRICS = M["end_to_end"] + M["per_layer"]
CELLS = {cell["name"]: cell for cell in M["workloads"]}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 65536
    assert 1 <= len(M["command"]) <= 32
    assert all(one_line(word) for word in M["command"])
    assert 1 <= len(M["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in M["paths"])
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    # A full check with all 24 cells fits the driver's 43200 seconds.
    assert ((2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16
    assert 1 <= len(M["per_layer"]) <= 128


def test_the_command_names_only_files_of_the_benchmark():
    for word in M["command"][1:]:
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in M["paths"])
        assert not word.startswith("/") and ".." not in word


def test_every_file_under_paths_is_named_from_a_names_characters():
    for base in M["paths"]:
        for folder, folders, files in os.walk(os.path.join(REPO, base)):
            folders[:] = [f for f in folders if f != "__pycache__"]
            for name in files:
                relative = os.path.relpath(os.path.join(folder, name), REPO)
                assert PATH.match(relative), relative


@pytest.mark.parametrize("section", sorted(ENTRY_KEYS))
def test_names_are_unique_and_entries_have_just_their_keys(section):
    names = [entry["name"] for entry in M[section]]
    assert len(names) == len(set(names))
    for entry in M[section]:
        assert NAME.match(entry["name"]), entry["name"]
        assert set(entry) - {"workloads"} == ENTRY_KEYS[section], entry
    if section in ("end_to_end", "per_layer"):
        assert len({m["name"] for m in METRICS}) == len(METRICS)


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_configuration(config):
    assert one_line(config["source"]) and one_line(config["why"])
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert [c["file"] for c in M["configs"]].count(config["file"]) == 1
    assert any(cell["config"] == config["name"] for cell in CELLS.values())
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTHS.search(key)
    with open(os.path.join(REPO, config["file"])) as f:
        held = json.load(f)
    # The file states what it is a deployment of, what it guarantees and
    # what the plain reference needs, and names files that exist.
    assert held["source"] == config["source"]
    assert held["reduced"] == config["reduced"]
    for key in ("protocol", "f", "flexible", "acceptor_groups",
                "acceptors_per_group", "leaders", "proxy_leaders",
                "replicas", "proxy_replicas", "batchers", "read_batchers",
                "state_machine", "options", "client_options", "guarantees",
                "quorum", "board", "chips", "layout", "upstream", "assumed"):
        assert key in held, key
    # Every count that was cut is a key of the file, with upstream's
    # beside it, and is cut: not a shape, and not left as upstream's.
    for key in held["reduced"]:
        assert held[key] < held["upstream"][key], key
    assert os.path.isfile(os.path.join(REPO, held["role_entry"]))
    assert LOADED.module_path("deployments", held["deployment"])
    assert LOADED.module_path("reference", held["reference"])
    nodes = held["acceptor_groups"] * held["acceptors_per_group"]
    assert held["board"] == {"nodes": nodes, "window": int(
        held["options"]["tpu_window"])}
    assert sorted(n for row in held["quorum"]["rows"] for n in row) == list(
        range(nodes))


@pytest.mark.parametrize("cell", CELLS.values(), ids=lambda c: c["name"])
def test_cell(cell):
    loaded = LOADED
    assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    assert one_line(cell["why"])
    assert cell["chips"] in (1, 4)
    assert loaded.config(cell["config"])["chips"] == cell["chips"]
    traffic = loaded.traffic(cell["traffic"])
    for key in ("generator", "loop", "client_procs", "loops_per_proc",
                "read_share", "keys", "key_distribution", "value_bytes",
                "warmup_s", "source"):
        assert key in traffic, key
    assert loaded.module_path("generators", traffic["generator"])
    pairs = [(c["config"], c["traffic"]) for c in CELLS.values()]
    assert pairs.count((cell["config"], cell["traffic"])) == 1
    reports = lambda section: [  # noqa: E731
        m["name"] for m in loaded.metrics_of(section, cell["name"])]
    assert "setup_s" in reports("end_to_end")
    assert len(reports("end_to_end")) >= 2 and reports("per_layer")


def test_at_most_half_the_cells_take_four_chips():
    four = sum(cell["chips"] == 4 for cell in CELLS.values())
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    assert LOADED.reader_path(metric["name"]), "every metric has a reader"
    if "bound" in metric:  # end to end
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        return
    assert one_line(metric["layer"])
    moved = [m for m in M["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1, metric["moves"]
    # Each of its cells reports the end-to-end metric it should move.
    for cell in metric.get("workloads", list(CELLS)):
        assert cell in moved[0].get("workloads", list(CELLS)), cell
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_setup_s_is_an_end_to_end_metric_of_every_cell():
    setup = [m for m in M["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.25


def test_the_harness_names_no_cell_configuration_mix_or_metric():
    names = ({c["name"] for c in M["configs"]} | set(CELLS)
             | {c["traffic"] for c in CELLS.values()}
             | {m["name"] for m in METRICS} - {"setup_s"})
    sources = [os.path.join(BENCHMARK, "run.py")] + [
        os.path.join(BENCHMARK, folder, name)
        for folder in ("harness", "deployments", "generators", "reference")
        for name in os.listdir(os.path.join(BENCHMARK, folder))
        if name.endswith(".py")]
    for path in sources:
        with open(path) as f:
            text = f.read()
        found = [name for name in names
                 if re.search(r"(?<![\w.])" + re.escape(name) + r"(?![\w.])",
                              text)]
        assert not found, (path, found)


def digest_tree(root: str) -> dict:
    out = {}
    for folder, folders, files in os.walk(root):
        folders[:] = [f for f in folders
                      if f not in ("__pycache__", ".bench_runs")]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_one_reader_serves_every_percentile_of_a_quantity():
    paths = {LOADED.reader_path(name) for name in (
        "commit_p50_ms", "commit_p95_ms", "client.commit_p99_ms",
        "client.commit_p95_ms.saturated")}
    assert len(paths) == 1 and None not in paths
    assert LOADED.reader_path("no.such_p95_thing") is None


def test_a_cell_a_mix_and_a_metric_are_added_as_files(tmp_path):
    """In a copy of the benchmark, a new configuration, traffic mix, cell,
    metric, deployment module and generator arrive as new files and new
    manifest entries; no file that was there changes, and the new cell
    runs and reports the new metric.
    The mix shares three keys among its loops and reads half the time,
    which no cell of the manifest does."""
    shutil.copytree(BENCHMARK, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest_tree(tmp_path)

    new = json.loads(json.dumps(M))
    new["paths"] = ["benchmark"]
    with open(os.path.join(REPO, M["configs"][0]["file"])) as f:
        config = json.load(f)
    config["options"]["tpu_window"] = "4096"
    config["board"]["window"] = 4096
    config["replicas"] = 3
    # A deployment module and a generator of the later PR's own, found by
    # the names its configuration and its mix give them.
    config["deployment"] = "later_deployment"
    shutil.copy(tmp_path / "benchmark/deployments/multipaxos.py",
                tmp_path / "benchmark/deployments/later_deployment.py")
    shutil.copy(tmp_path / "benchmark/generators/closed_kv.py",
                tmp_path / "benchmark/generators/later_generator.py")
    with open(tmp_path / "benchmark/configs/later_config.json", "w") as f:
        json.dump(config, f)
    with open(tmp_path / "benchmark/traffic/later_mix.json", "w") as f:
        json.dump({"generator": "later_generator", "loop": "closed",
                   "client_procs": 2, "loops_per_proc": 3,
                   "read_share": 0.5, "keys": 3,
                   "key_distribution": "uniform", "value_bytes": 24,
                   "warmup_s": 0.3}, f)
    with open(tmp_path / "benchmark/metrics/later.reads.py", "w") as f:
        f.write("def read(run, metric):\n"
                "    return int((run.ops['kind'] == 1).sum())\n")
    new["configs"].append({**M["configs"][0], "name": "later_config",
                           "file": "benchmark/configs/later_config.json"})
    new["workloads"].append({"name": "later.cell", "config": "later_config",
                             "traffic": "later_mix", "chips": 1,
                             "why": "added by files alone"})
    for metric in new["end_to_end"]:
        if metric["name"] == "commit_p50_ms":
            metric["workloads"].append("later.cell")
    new["per_layer"].append({
        "name": "later.reads.in_run", "unit": "reads", "better": "higher",
        "source": "program_counter", "layer": new["per_layer"][0]["layer"],
        "moves": "commit_p50_ms", "workloads": ["later.cell"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(new, f)

    env = dict(os.environ, PYTHONPATH=REPO)
    run_py = str(tmp_path / "benchmark/run.py")
    manifest_path = str(tmp_path / "BENCHMARK.json")
    assert [m["name"] for m in Manifest(manifest_path).metrics_of(
        "end_to_end", "later.cell")] == ["commit_p50_ms", "setup_s"]
    code, result, errors = run_cell(manifest_path, "later.cell", trace=1,
                                    run_py=run_py, env=env)
    assert code == 0, errors[-3000:]
    assert result["correct"] is True, result["compared"]
    assert result["metrics"]["later.reads.in_run"]["value"] > 0

    after = digest_tree(tmp_path)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "BENCHMARK.json", "benchmark/configs/later_config.json",
        "benchmark/traffic/later_mix.json",
        "benchmark/metrics/later.reads.py",
        "benchmark/deployments/later_deployment.py",
        "benchmark/generators/later_generator.py"}

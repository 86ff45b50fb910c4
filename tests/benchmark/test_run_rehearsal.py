"""Every cell of ``BENCHMARK.json`` end to end through ``benchmark/run.py``
at toy size on the CPU: the same command, launcher, role entry, generators,
readers and reference as on the chip, with test-only copies of the
configuration and traffic files that ``bench_util.derive`` makes from the
real ones (window 4096, a few loops, 2 s)."""

import json
import os
import shutil
import subprocess
import sys

from bench_util import manifest, REPO, RESULT_KEYS, run_cell, toy_manifest
import pytest

CELLS = [cell["name"] for cell in manifest()["workloads"]]


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return toy_manifest(tmp_path_factory)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_at_toy_size(toy, cell, trace):
    code, result, errors = run_cell(toy, cell, trace=trace)
    assert code == 0, errors[-3000:]
    wanted = RESULT_KEYS | ({"breakdown"} if trace else set())
    # What the driver reads, whether the wall clock moved, and the
    # compared numbers, which come last. A correct run has no offenders.
    assert set(result) == wanted | {"clock", "compared"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    # How far the wall clock moved, whatever this host's did meanwhile:
    # reported for every process, and decides nothing.
    moved = result["clock"]["by_process_ms"]
    assert set(moved) == {"launcher"} | {
        f"generator_{n}" for n in range(len(moved) - 1)} and len(moved) > 1
    assert result["clock"]["wall_step_ms"] == max(moved.values(), key=abs)
    assert (f"clock wall_step_ms: {result['clock']['wall_step_ms']}"
            in errors)
    assert "offender " not in errors
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {
        "platform", "kind", "count", "memory_peak_bytes"} | (
            {"busy_s", "window_s"} if trace else set())
    section = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m["unit"] for m in manifest(toy)[section]
                if cell in m.get("workloads", [cell])}
    assert result["metrics"], "a run reports at least one metric"
    for name, reading in result["metrics"].items():
        assert reading["unit"] == declared[name]
        assert reading["value"] > 0
    if trace:
        # CPU XLA has no peak, so no share of one is reported; every
        # other declared per-layer metric found something to read.
        assert set(declared) - set(result["metrics"]) <= {
            m for m in declared if "roofline" in m}
        assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]
        assert result["breakdown"]["device_ops"]
        assert len(result["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(result["metrics"]) == set(declared)
    # Each number compared stands beside its limit, on stderr too.
    for name, (value, limit) in result["compared"].items():
        assert f"compared {name}: {value} (limit {limit})" in errors


def test_no_chip_and_no_cpu_pin_is_a_failure_without_a_result(toy):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    code, result, errors = run_cell(toy, CELLS[0], env=env)
    assert code != 0
    assert result is None, result
    assert "needs the TPU" in errors or "exited before" in errors, errors


def test_alone_with_the_manifest_it_fails_without_a_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: there is no program to measure."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in manifest()["paths"]:
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert done.returncode != 0
    assert '"correct"' not in done.stdout


def test_bench_run_is_ignored_a_wide_seed_runs_and_nothing_is_left(toy):
    """``BENCH_RUN`` is the driver's own, a seed may pass 32 signed bits,
    and after a run no role and no generator of it is alive."""
    env = dict(os.environ, BENCH_RUN="7")
    code, result, errors = run_cell(toy, CELLS[1], seed=2 ** 31 + 12345,
                                    env=env)
    assert code == 0, errors[-3000:]
    assert result["correct"] is True
    listing = subprocess.run(["ps", "-eo", "args"], capture_output=True,
                             text=True).stdout
    mine = os.path.join(REPO, ".bench_runs", CELLS[1])
    assert mine not in listing

"""Shared by the benchmark's tests: where things are, manifests derived
from the real one (toy size for the CPU, or another role entry), and one
run of ``benchmark/run.py`` as a child process under the tests' CPU pin.

    python3 tests/benchmark/bench_util.py <role entry> <out dir>
    python3 tests/benchmark/bench_util.py --generator <generator> <out dir>

writes the real manifest with every configuration's ``role_entry``, or
every traffic mix's generator, replaced, at the cells' own size, and
prints its path: the control and the planted faults, for runs on the
chip.
"""

import fcntl
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCHMARK = os.path.join(REPO, "benchmark")
FAULTS = os.path.join(REPO, "tests", "benchmark", "faults")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TOY_WINDOW = 4096

if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)


def manifest(path: str = os.path.join(REPO, "BENCHMARK.json")) -> dict:
    with open(path) as f:
        return json.load(f)


def derive(out_dir: str, *, toy: bool, role_entry: "str | None" = None,
           generator: "str | None" = None, change=None) -> str:
    """A copy of the real manifest under ``out_dir`` whose configurations
    and traffic mixes are the real files with a few keys replaced: at
    ``toy`` size a 4096-slot window, a few loops and a short warm-up;
    with ``role_entry`` that entry in the benchmark's place; with
    ``generator`` that file as every mix's generator. ``change`` may edit
    the manifest before it is written. Returns its path."""
    derived = manifest()
    out_dir = os.path.abspath(out_dir)
    for kind in ("configs", "traffic", "generators"):
        os.makedirs(os.path.join(out_dir, kind), exist_ok=True)
    generator_name = None
    if generator is not None:
        # Found by name, as the harness finds any generator: a link under
        # the derived manifest's first path.
        generator_name = os.path.splitext(os.path.basename(generator))[0]
        link = os.path.join(out_dir, "generators", generator_name + ".py")
        if os.path.lexists(link):
            os.remove(link)
        os.symlink(os.path.abspath(generator), link)
    derived["paths"] = [out_dir] + derived["paths"]
    for entry in derived["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            config = json.load(f)
        if toy:
            config["options"]["tpu_window"] = str(TOY_WINDOW)
            config["board"]["window"] = TOY_WINDOW
        if role_entry is not None:
            config["role_entry"] = role_entry
        entry["file"] = os.path.join(out_dir, "configs",
                                     entry["name"] + ".json")
        with open(entry["file"], "w") as f:
            json.dump(config, f)
    for name in ({cell["traffic"] for cell in derived["workloads"]}
                 if toy or generator is not None else ()):
        with open(os.path.join(BENCHMARK, "traffic", name + ".json")) as f:
            traffic = json.load(f)
        if toy:
            many = traffic["client_procs"] > 1
            traffic.update(client_procs=2 if many else 1,
                           loops_per_proc=8 if many else 4, warmup_s=0.5)
        if generator is not None:
            traffic["generator"] = generator_name
        with open(os.path.join(out_dir, "traffic", name + ".json"),
                  "w") as f:
            json.dump(traffic, f)
    if change is not None:
        change(derived)
    path = os.path.join(out_dir, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(derived, f)
    return path


def toy_manifest(tmp_path_factory, **planted) -> str:
    """``planted``: ``role_entry`` or ``generator``, as ``derive`` takes."""
    return derive(str(tmp_path_factory.mktemp("toy")), toy=True, **planted)


def run_cell(manifest_path: str, cell: str, *, trace: int = 0,
             seconds: float = 2.0, seed: int = 2_147_483_659,
             run_py: str = os.path.join(BENCHMARK, "run.py"),
             env: "dict | None" = None) -> tuple:
    """``(exit code, the last line of stdout parsed or None, stderr)``.

    A run starts a dozen processes. So that the other tests of a parallel
    test run keep their cores, one deployment runs at a time (a file lock
    across the test workers) and at the lowest priority."""
    os.makedirs(os.path.join(REPO, ".bench_runs"), exist_ok=True)
    with open(os.path.join(REPO, ".bench_runs", ".tests.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        done = subprocess.run(
            ["nice", "-n", "19", sys.executable, run_py,
             "--manifest", manifest_path, "--workload", cell,
             "--seed", str(seed), "--seconds", str(seconds),
             "--trace", str(trace)],
            cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    lines = done.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return done.returncode, result, done.stderr


if __name__ == "__main__":
    if sys.argv[1] == "--generator":
        print(derive(sys.argv[3], toy=False, generator=sys.argv[2]))
    else:
        print(derive(sys.argv[2], toy=False, role_entry=sys.argv[1]))

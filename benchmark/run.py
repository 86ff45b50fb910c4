#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

This process is the launcher. It never initialises JAX on the chip: it
writes the deployment's cluster file from the cell's configuration,
starts every role as a process of its own through the program's
``launch_roles`` with the benchmark's role entry, starts the load
generators, measures for ``--seconds``, reads every key back, stops what
it started, compares with the plain reference, and prints one JSON line.
Which cell, configuration, traffic mix, deployment, generator, reference
and metric readers there are it learns from the manifest and the files
that names; none is named here.

``setup_s`` runs from this process's start to the first instant of the
measured window, the generators' warm-up included. The reference and the
reading of the trace run after the window and count in neither.

One clock. Every instant that the window, a metric or ``correct``
compares (this process's start, ``go <start> <end>``, the generators'
issue and answer instants) is a read of ``time.monotonic()``:
``CLOCK_MONOTONIC``, one clock for all processes of a Linux host, never
stepped. The wall clock decides nothing. The launcher and every generator
read it against the monotonic one at ``go`` and at their end; the largest
change is printed as ``clock wall_step_ms`` and carried, with each
process's own, under ``clock`` in the result line, so that a reader learns
whether it was stepped inside the run.
"""

from __future__ import annotations

import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness.manifest import Manifest  # noqa: E402

#: Seconds of the measured window that the chip owner traces.
TRACE_S = 3.0
#: How long after the window's start the trace begins.
TRACE_AFTER_S = 1.0


class Run:
    """What one run measured, as the metric readers get it."""

    def __init__(self):
        self.cell: dict = {}
        self.config: dict = {}
        self.traffic: dict = {}
        self.seconds = 0.0
        self.window = (0.0, 0.0)     # time.monotonic() seconds
        self.wall_minus_mono_s = 0.0  # the launcher's reading at go
        self.setup_s = 0.0
        self.ops: dict = {}          # the generators' rows, concatenated
        self.scrapes: dict = {}      # "start" / "end" -> {label: /metrics}
        self.cpu_s: dict = {}        # process -> CPU seconds in the window
        self.records: dict = {}      # label -> what its role entry wrote
        self.chip_owner = None       # label
        self.device: dict = {}
        self.trace = None            # the reduced trace; None untraced
        self.span = None             # the chip owner's trace.json


def log(message: str) -> None:
    print(f"[bench {time.monotonic() - STARTED:7.2f}s] {message}",
          file=sys.stderr, flush=True)


def start_generators(bench, run: Run, manifest: Manifest, cluster_path: str,
                     seed: int) -> list:
    from frankenpaxos_tpu.bench.deploy_suite import role_process_env

    traffic_path = manifest.find("traffic", run.cell["traffic"] + ".json")
    generator = manifest.module_path("generators", run.traffic["generator"])
    generators = []
    for index in range(run.traffic["client_procs"]):
        out = bench.abspath(f"generator_{index}")
        with open(out + ".log", "w") as errors:
            generators.append((out, subprocess.Popen(
                [sys.executable, generator,
                 "--cluster", cluster_path,
                 "--protocol", run.config["protocol"],
                 "--traffic", traffic_path,
                 "--client_options", json.dumps(run.config["client_options"]),
                 "--seed", str(seed), "--index", str(index), "--out", out],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=errors, text=True, env=role_process_env(),
                cwd=ROOT)))
    for out, process in generators:
        if process.stdout.readline().strip() != "ready":
            raise RuntimeError(f"a load generator did not start; see "
                               f"{out}.log")
    return generators


def scrape_all(bench) -> dict:
    from frankenpaxos_tpu.bench.metrics import scrape

    return {label: scrape(port)
            for label, port in bench.prometheus_ports.items()}


def cpu_seconds(pids: dict) -> dict:
    """User plus system CPU seconds so far of each process, all its
    threads together (``/proc/<pid>/stat``)."""
    tick = os.sysconf("SC_CLK_TCK")
    out = {}
    for label, pid in pids.items():
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(") ", 1)[-1].split()
        out[label] = (int(fields[11]) + int(fields[12])) / tick
    return out


def sleep_until(mono_s: float) -> None:
    delay = mono_s - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def wall_minus_mono() -> float:
    """The wall clock against the monotonic one; see ``clock_report``."""
    return time.time() - time.monotonic()


def clock_report(readings: dict) -> dict:
    """``readings``: for each process its ``wall_minus_mono`` at ``go`` and
    at its end. The change in each, in milliseconds, and the one of
    largest size as ``wall_step_ms``: how far the wall clock was stepped
    (or slewed) inside the run. It decides nothing; no wall-clock instant
    is compared."""
    moved = {name: 1e3 * (last - first)
             for name, (first, last) in readings.items()}
    return {"wall_step_ms": max(moved.values(), key=abs),
            "by_process_ms": moved}


def drive(bench, run: Run, manifest: Manifest, seed: int,
          trace_s: float, record_dir: str) -> None:
    """Everything between the launch and the roles' exit."""
    deployment = manifest.module("deployments", run.config["deployment"])
    grace_s = deployment.GRACE_S
    cluster_path = deployment.launch_with_retry(bench, run.config,
                                                record_dir, trace_s)
    run.chip_owner = bench.chip_owner
    log(f"{len(bench.role_commands)} role processes ready; chip owner "
        f"{bench.chip_owner}")
    generators = start_generators(bench, run, manifest, cluster_path, seed)
    try:
        run.wall_minus_mono_s = wall_minus_mono()
        start = time.monotonic() + run.traffic["warmup_s"]
        end = start + run.seconds
        for _, process in generators:
            process.stdin.write(f"go {start!r} {end!r}\n")
            process.stdin.flush()
        run.window = (start, end)
        run.setup_s = start - STARTED
        log(f"{len(generators)} generators warming up; window opens in "
            f"{run.traffic['warmup_s']}s, set-up {run.setup_s:.2f}s")
        pids = {label: proc.pid()
                for label, proc in bench.labeled_procs.items()}
        pids.update({os.path.basename(out): process.pid
                     for out, process in generators})
        sleep_until(start)
        cpu_before = cpu_seconds(pids)
        run.scrapes["start"] = scrape_all(bench)
        if trace_s > 0:
            sleep_until(start + TRACE_AFTER_S)
            os.kill(bench.labeled_procs[bench.chip_owner].pid(),
                    signal.SIGUSR1)
        sleep_until(end)
        cpu_after = cpu_seconds(pids)
        run.cpu_s = {label: cpu_after[label] - cpu_before[label]
                     for label in pids}
        run.scrapes["end"] = scrape_all(bench)
        log("window closed; waiting for late answers and the read-back")
        for out, process in generators:
            code = process.wait(timeout=3 * grace_s)
            if code != 0:
                raise RuntimeError(f"a load generator exited with code "
                                   f"{code}; see {out}.log")
    finally:
        for _, process in generators:
            if process.poll() is None:
                process.kill()
            process.wait()
            process.stdin.close()
            process.stdout.close()
    deployment.settle(bench)
    if trace_s > 0:
        span_path = os.path.join(record_dir,
                                 f"{bench.chip_owner}.trace.json")
        deadline = time.monotonic() + grace_s
        while not os.path.exists(span_path) and time.monotonic() < deadline:
            time.sleep(0.1)
    # SIGTERM, then wait: the role entries dump their records at exit,
    # which bench.cleanup()'s five seconds might cut short.
    for proc in bench.labeled_procs.values():
        if proc.running():
            os.kill(proc.pid(), signal.SIGTERM)
    for label, proc in bench.labeled_procs.items():
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            log(f"{label} did not exit on SIGTERM")


def load_arrays(path: str):
    """The arrays of one ``.npz``, or None where a role wrote none."""
    import numpy as np

    if not os.path.exists(path):
        return None
    with np.load(path) as loaded:
        return dict(loaded)


def load_results(bench, run: Run, record_dir: str) -> list:
    import numpy as np

    generators = []
    for index in range(run.traffic["client_procs"]):
        out = bench.abspath(f"generator_{index}")
        with open(out + ".json") as f:
            info = json.load(f)
        generators.append({"info": info, "ops": load_arrays(out + ".npz")})
    run.ops = {name: np.concatenate([g["ops"][name] for g in generators])
               for name in generators[0]["ops"]}
    for label in bench.labeled_procs:
        path = os.path.join(record_dir, f"{label}.json")
        if not os.path.exists(path):
            raise RuntimeError(f"{label} wrote no record")
        with open(path) as f:
            record = json.load(f)
        prefix = os.path.join(record_dir, label)
        run.records[label] = {
            "record": record,
            "replica": load_arrays(f"{prefix}.replica.npz"),
            "trackers": [load_arrays(f"{prefix}.tracker{k}.npz")
                         for k in range(len(record["trackers"]))]}
    return generators


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--manifest",
                        default=os.path.join(ROOT, "BENCHMARK.json"),
                        help="the tests' own manifests; the driver gives "
                             "none")
    args = parser.parse_args(argv)

    manifest = Manifest(args.manifest)
    run = Run()
    run.cell = manifest.cell(args.workload)
    run.config = manifest.config(run.cell["config"])
    run.traffic = manifest.traffic(run.cell["traffic"])
    run.seconds = args.seconds
    reference = manifest.module("reference", run.config["reference"])
    trace_s = min(TRACE_S, args.seconds / 2) if args.trace else 0.0

    from frankenpaxos_tpu import device

    device.pin_cpu()  # the launcher stays off the chip

    import numpy as np

    from frankenpaxos_tpu.bench.harness import BenchmarkDirectory

    workdir = os.path.join(ROOT, ".bench_runs", args.workload)
    shutil.rmtree(workdir, ignore_errors=True)
    record_dir = os.path.join(workdir, "records")
    os.makedirs(record_dir)
    bench = BenchmarkDirectory(workdir)

    def stop(signum, frame):
        raise SystemExit(f"stopped by signal {signum}")

    signal.signal(signal.SIGTERM, stop)
    try:
        drive(bench, run, manifest, args.seed, trace_s, record_dir)
    finally:
        bench.cleanup()
    generators = load_results(bench, run, record_dir)

    owner = run.records[run.chip_owner]["record"]
    found = owner["device"]
    if found["platform"] != "tpu" and not device.explicit_cpu():
        raise SystemExit(f"the chip owner ran on {found}, not on a TPU")
    if found["count"] < run.cell["chips"]:
        raise SystemExit(f"the cell asks for {run.cell['chips']} chips and "
                         f"JAX found {found['count']}")
    run.device = {"platform": found["platform"], "kind": found["kind"],
                  "count": found["count"],
                  "memory_peak_bytes": owner["memory_peak_bytes"]}
    breakdown = None
    if args.trace:
        from harness import trace_reduce

        with open(os.path.join(record_dir,
                               f"{run.chip_owner}.trace.json")) as f:
            run.span = json.load(f)
        run.trace = trace_reduce.reduce(
            os.path.join(record_dir, f"{run.chip_owner}.trace"),
            found["kind"], run.cell["chips"])
        run.device["busy_s"] = run.trace["busy_s"]
        run.device["window_s"] = run.trace["window_s"]
        breakdown = {"device_ops": run.trace["device_ops"],
                     "idle_gaps": run.trace["idle_gaps"]}

    metrics = {}
    section = "per_layer" if args.trace else "end_to_end"
    for metric in manifest.metrics_of(section, args.workload):
        value = manifest.reader(metric["name"]).read(run, metric)
        if value is not None:
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}

    log("comparing with the plain reference")
    evidence: dict = {}
    compared = reference.compare(np, run.config, generators, run.records,
                                 evidence)
    clock = clock_report({
        "launcher": (run.wall_minus_mono_s, wall_minus_mono()),
        **{f"generator_{g['info']['index']}": g["info"]["wall_minus_mono_s"]
           for g in generators}})
    in_window = ((run.ops["issue_mono_s"] >= run.window[0])
                 & (run.ops["issue_mono_s"] < run.window[1]))
    result = {
        "correct": all(value <= limit for value, limit in compared.values()),
        "attempted": int(in_window.sum()),
        "failed": int((in_window & (run.ops["latency_s"] < 0)).sum()),
        "metrics": metrics,
        "device": run.device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    if evidence:
        result["offenders"] = evidence
    result["clock"] = clock
    result["compared"] = {name: [value, limit]
                          for name, (value, limit) in compared.items()}
    acked_at = (run.ops["issue_mono_s"] + run.ops["latency_s"])[
        run.ops["latency_s"] >= 0]
    log("answers in each second of the window: " + str(np.histogram(
        acked_at, bins=np.arange(run.window[0], run.window[1] + 0.5)
    )[0].tolist()))
    log("CPU seconds in the window: " + json.dumps(
        {label: round(s, 2) for label, s in sorted(run.cpu_s.items())}))
    log("seconds in garbage collection, whole run: " + json.dumps(
        {label: [round(s, 2) for s in r["record"]["gc_pause_s"]]
         for label, r in sorted(run.records.items())}))
    log(f"done; set-up {run.setup_s:.2f}s, claim "
        f"{owner.get('claim_s', 0):.2f}s, compile cache {owner['cache']}")
    print_compared(compared, evidence, clock)
    print(json.dumps(result), flush=True)
    return 0


def print_compared(compared: dict, evidence: dict, clock: dict) -> None:
    """The last lines of standard error: for each number above its limit
    the rows it was counted from, whether the wall clock moved, and each
    number compared beside its limit."""
    for name, rows in evidence.items():
        for row in rows:
            print(f"offender {name}: {json.dumps(row)}", file=sys.stderr)
    print(f"clock wall_step_ms: {clock['wall_step_ms']}", file=sys.stderr)
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark orchestration harness.

Reference behavior: benchmarks/ (proc.py:65-160 PopenProc, host.py:10-37,
benchmark.py:73-335 SuiteDirectory/BenchmarkDirectory/Suite with
latency/throughput output schemas, workload.py). This is the local-
process slice of that harness: launch every role as its own OS process
via the CLI (frankenpaxos_tpu/cli.py), drive a closed-loop workload from
in-process clients, and record the reference-compatible stats
(latency.median_ms, start_throughput_1s.p90 analogs) as JSON/CSV.
SSH deployment (ParamikoProc) plugs in behind Proc.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
from typing import Optional, Sequence

import numpy as np


class Proc:
    """A managed subprocess (the PopenProc shape, proc.py:65-110)."""

    def __init__(self, args: Sequence[str], out_path: str,
                 env: Optional[dict] = None):
        self._out = open(out_path, "w")
        self._proc = subprocess.Popen(
            list(args), stdout=self._out, stderr=subprocess.STDOUT,
            env=env,
            cwd=os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__)))))

    def pid(self) -> int:
        return self._proc.pid

    def kill(self) -> None:
        """Stop the process and REAP it: a chip's next owner must not
        start while the previous one still holds the device."""
        if self._proc.poll() is None:
            self._proc.send_signal(signal.SIGTERM)
            try:
                self._proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self._proc.kill()
                self._proc.wait()
        self._out.close()

    def running(self) -> bool:
        return self._proc.poll() is None

    def wait(self, timeout: Optional[float] = None) -> int:
        return self._proc.wait(timeout=timeout)


@dataclasses.dataclass(frozen=True)
class LocalHost:
    """(host.py:10-24)."""

    ip: str = "127.0.0.1"

    def popen(self, args: Sequence[str], out_path: str,
              env: Optional[dict] = None) -> Proc:
        return Proc(args, out_path, env=env)

    def read_output(self, path: str) -> str:
        """Current contents of a launched process' output file (the
        ready-wait seam; RemoteHost reads through its shell instead)."""
        try:
            with open(path) as f:
                return f.read()
        except OSError:
            return ""

    def grep_ready(self, paths: Sequence[str], needle: str) -> set:
        """Which of ``paths`` currently contain ``needle`` (RemoteHost
        answers this in one shell round-trip for the whole set)."""
        return {p for p in paths if needle in self.read_output(p)}


def _ephemeral_floor() -> int:
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            return int(f.read().split()[0])
    except (OSError, ValueError, IndexError):
        return 32768  # the Linux default


#: Ports this process has handed out. A placement draws a dozen ports
#: long before any role binds one, so the allocator itself must never
#: repeat -- role and /metrics ports of one launch stay distinct.
_handed_out: set = set()
_port_rng = random.Random(os.getpid() ^ time.time_ns())


def free_port() -> int:
    """A localhost port that is free now and that nothing takes by
    accident before its role binds it. Ports come from BELOW the
    kernel's ephemeral range: a port the kernel picked (``bind(0)``) is
    released back to the pool every outgoing connection draws its source
    port from, and a busy test host steals it before the role starts --
    the ``[Errno 98] address already in use`` start-up flake."""
    lo, hi = 10240, _ephemeral_floor()
    for _ in range(10_000):
        port = _port_rng.randrange(lo, hi)
        if port in _handed_out:
            continue
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        _handed_out.add(port)
        return port
    raise RuntimeError(f"no free port in [{lo}, {hi})")


class BenchmarkDirectory:
    """A directory collecting one benchmark's artifacts
    (benchmark.py:220-340)."""

    def __init__(self, path: str):
        self.path = path
        os.makedirs(path, exist_ok=True)
        self.procs: list[Proc] = []
        #: label -> Proc, for per-role accounting (CPU-time breakdowns).
        self.labeled_procs: dict[str, Proc] = {}
        # label -> /metrics port, filled by deploy_suite.launch_roles
        # when prometheus=True.
        self.prometheus_ports: dict[str, int] = {}
        # label -> (cmd, env), filled by deploy_suite.launch_roles so a
        # role can be relaunched verbatim (readiness retry, chaos
        # driver).
        self.role_commands: dict[str, tuple] = {}
        # The label of the one process launch_roles left unpinned to
        # own the chip (None: every role is pinned to the CPU).
        self.chip_owner: Optional[str] = None
        # label -> obs.telemetry.TelemetryReporter, registered by
        # harnesses that drive a device pipeline beside the roles;
        # chaos SIGKILL post-mortems snapshot each reporter's last
        # device-counter summary next to the flight ring.
        self.telemetry_reporters: dict = {}

    def abspath(self, name: str) -> str:
        return os.path.join(self.path, name)

    def write_json(self, name: str, data) -> str:
        path = self.abspath(name)
        with open(path, "w") as f:
            json.dump(data, f, indent=2, default=str)
        return path

    def popen(self, host: LocalHost, label: str,
              args: Sequence[str], env: Optional[dict] = None) -> Proc:
        proc = host.popen(args, self.abspath(f"{label}.log"), env=env)
        self.procs.append(proc)
        self.labeled_procs[label] = proc
        return proc

    @staticmethod
    def stage_projection(role_cpu: dict) -> dict:
        """The decoupling projection from a per-role CPU split: once
        every stage owns a core, pipeline wall time shrinks from
        sum(stage cpu) to max(stage cpu) -- Amdahl on the stage graph
        (DistributionScheme.scala:151-162). Returns {} when there is
        nothing to project. The ONE implementation shared by the sweep
        families and the protocol suite."""
        if not role_cpu:
            return {}
        total = sum(role_cpu.values())
        bottleneck_stage = max(role_cpu, key=role_cpu.get)
        bottleneck = role_cpu[bottleneck_stage]
        if bottleneck <= 0:
            return {}
        return {
            "role_cpu_s": round(total, 3),
            "bottleneck_stage": bottleneck_stage,
            "bottleneck_cpu_s": round(bottleneck, 3),
            "parallelizable_fraction": round(1 - bottleneck / total, 3),
            "projected_stage_speedup": round(total / bottleneck, 2),
        }

    def role_cpu_seconds(self) -> dict:
        """Per-role CPU time (user+sys, /proc/<pid>/stat) for every
        still-running local role process. Call BEFORE cleanup(). The
        per-stage accounting behind the compartmentalization
        projection (bench/coupled.py): on a one-core host the 4-8x
        decoupling win cannot show up in wall-clock, but the
        parallelizable fraction is exactly this breakdown."""
        tick = os.sysconf("SC_CLK_TCK")
        out = {}
        for label, proc in self.labeled_procs.items():
            if not isinstance(proc, Proc):
                # RemoteProc.pid() is a REMOTE pid: /proc/<it>/stat on
                # the launcher machine would describe some unrelated
                # local process. Per-role CPU accounting is
                # local-launch only.
                continue
            pid = proc.pid()
            try:
                with open(f"/proc/{pid}/stat") as f:
                    fields = f.read().rsplit(") ", 1)[-1].split()
                # utime, stime are fields 14,15 (1-indexed) = 11,12
                # after the (comm) split leaves state at index 0.
                out[label] = round(
                    (int(fields[11]) + int(fields[12])) / tick, 3)
            except (OSError, IndexError, ValueError):
                pass
        return out

    def cleanup(self) -> None:
        for proc in self.procs:
            proc.kill()


class SuiteDirectory:
    """(benchmark.py:73-130)."""

    def __init__(self, root: str, name: str):
        self.path = os.path.join(root, f"{name}_{int(time.time())}")
        os.makedirs(self.path, exist_ok=True)
        self._counter = 0

    def benchmark_directory(self) -> BenchmarkDirectory:
        self._counter += 1
        return BenchmarkDirectory(
            os.path.join(self.path, f"{self._counter:03d}"))


def rolling_throughput(starts_s: Sequence[float],
                       window_s: float = 1.0) -> np.ndarray:
    """Rolling-window throughput series (pd_util.py:35-86 semantics).

    For each request start t, the count of starts in (t - window, t]
    divided by the window, with the first window of samples trimmed
    (they see a partially-filled window and read artificially low).
    """
    starts = np.asarray(sorted(starts_s), dtype=np.float64)
    if starts.size == 0:
        return starts
    lo = np.searchsorted(starts, starts - window_s, side="right")
    counts = np.arange(1, starts.size + 1) - lo
    series = counts / window_s
    keep = starts >= starts[0] + window_s
    # Match pd_util.throughput's fallback: if everything happened within
    # one window, trim the first 100 samples instead of all of them.
    if not keep.any():
        return series[100:]
    return series[keep]


def _dist(values: np.ndarray, prefix: str, scale: float = 1.0,
          suffix: str = "") -> dict:
    if values.size == 0:
        return {}
    q = lambda p: float(np.percentile(values, p) * scale)
    return {
        f"{prefix}.mean{suffix}": float(values.mean() * scale),
        f"{prefix}.median{suffix}": q(50),
        f"{prefix}.min{suffix}": float(values.min() * scale),
        f"{prefix}.max{suffix}": float(values.max() * scale),
        f"{prefix}.p90{suffix}": q(90),
        f"{prefix}.p95{suffix}": q(95),
        f"{prefix}.p99{suffix}": q(99),
    }


def latency_throughput_stats(latencies_s: Sequence[float],
                             duration_s: float,
                             starts_s: Optional[Sequence[float]] = None,
                             ) -> dict:
    """The reference's RecorderOutput schema (benchmark.py:308-341).

    latency.* in milliseconds over per-request latencies;
    start_throughput_1s.* as percentiles of the rolling 1-second-window
    throughput series over request start times (benchmark.py:420) — NOT
    a mean disguised as a percentile.
    """
    lat = np.asarray(sorted(latencies_s))
    if lat.size == 0:
        return {"num_requests": 0}
    stats = {"num_requests": int(lat.size)}
    stats.update(_dist(lat, "latency", scale=1000.0, suffix="_ms"))
    series = (rolling_throughput(starts_s)
              if starts_s is not None and len(starts_s) > 0
              else np.empty(0))
    if series.size > 0:
        stats.update(_dist(series, "start_throughput_1s"))
    else:
        # No start timestamps recorded: report the honest mean under an
        # honest name rather than a fake percentile.
        stats["throughput_mean"] = float(lat.size / duration_s)
    return stats

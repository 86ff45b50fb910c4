"""chip_smoke.py between chip runs: its stage functions at toy size on
the CPU (the explicit JAX_PLATFORMS=cpu of conftest.py), and its entry,
which must fail wherever there is no TPU."""

import json
import os
import shutil
import subprocess
import sys

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _assert_checks(result: dict) -> None:
    failed = [name for name, ok in result["checks"].items() if not ok]
    assert not failed, (failed, result)
    # A CPU result is labelled one; on the chip run_stage() would refuse
    # it.
    assert result["device"]["platform"] == "cpu"


def test_stage_served_at_toy_size(tmp_path):
    result = chip_smoke.stage_served(
        str(tmp_path), client_procs=2, loops_per_proc=32, duration_s=1.0,
        window=4096)
    _assert_checks(result)
    assert result["chip_owner"] == "proxy_leader_0_1"
    assert result["writes_acked"] >= result["closed_loops"]
    assert result["device_launches"] >= result["device_drains"] > 0

    # cli.py's per-process decision, as the roles logged it: only the
    # chip owner claimed a device, and it said the pin made it a CPU.
    def log(label: str) -> str:
        with open(tmp_path / f"{label}.log") as f:
            return f.read()

    assert '"platform": "cpu"' in log("proxy_leader_0_1")
    assert "JAX_PLATFORMS=cpu is set" in log("proxy_leader_0_1")
    assert "device:" not in log("acceptor_0")


def test_stage_kernels_at_toy_size():
    _assert_checks(chip_smoke.stage_kernels(
        window=1024, dense_width=64, sparse_votes=1024,
        pipeline_iters=16, pipeline_block=128))


def test_stage_mesh_at_toy_size(need_8_devices):
    _assert_checks(chip_smoke.stage_mesh(
        window=1024, dense_width=64, pipeline_iters=16,
        pipeline_block=128))


def test_entry_fails_without_a_tpu():
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout


def test_entry_fails_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=60)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout


def test_last_line_is_the_result_and_nothing_else(monkeypatch, capsys,
                                                  tmp_path):
    """main() with its stage children replaced by ones that only write
    a passing record: the driver parses the last line of stdout and
    admits exactly these keys."""
    found = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    class PassingStage:
        pid = 0

        def __init__(self, argv, **_):
            with open(argv[-1], "w") as f:
                json.dump({"stage": argv[-2], "device": found,
                           "checks": {"passed": True}, "ok": True}, f)

        def wait(self, timeout=None):
            return 0

    (tmp_path / "frankenpaxos_tpu").mkdir()
    monkeypatch.setattr(chip_smoke, "REPO", str(tmp_path))
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(chip_smoke.subprocess, "Popen", PassingStage)
    monkeypatch.setattr(chip_smoke.os, "killpg",
                        lambda pid, sig: None)
    chip_smoke.main()
    summary, last = capsys.readouterr().out.strip().splitlines()[-2:]
    assert json.loads(last) == {"ok": True, "device": found}
    assert list(json.loads(summary))[-1] == "claim"
    assert json.loads(summary)["claim"] is None

"""paxlint CLI contract tests: the SARIF/JSON document round trip,
``--changed-since`` diff-aware equivalence, the diff-mode runtime
budget, and the burned-down (empty, and staying empty) baseline.

tests/test_analysis.py owns the rule-family fixtures and the full-run
budget; this file owns the machine-readable surfaces the CI lint job
consumes (paxlint.json + paxlint.sarif artifacts, the diff-aware
fast path).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import frankenpaxos_tpu
from frankenpaxos_tpu.analysis import diff as diff_mod
from frankenpaxos_tpu.analysis.core import Project, run_rules

REPO_ROOT = os.path.dirname(os.path.dirname(frankenpaxos_tpu.__file__))

ACTOR_PREAMBLE = """\
    import threading
    import time

    class Actor:
        def receive(self, src, message): ...
        def on_drain(self): ...
        def timer(self, name, delay_s, f): ...
        def send(self, dst, message): ...
        def broadcast(self, dsts, message): ...
"""

SLEEPY_ACTOR = ACTOR_PREAMBLE + """
    class {name}(Actor):
        def on_drain(self):
            time.sleep({delay})
"""


def _write_pkg(root, files: dict) -> None:
    for rel, source in files.items():
        path = root / "frankenpaxos_tpu" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))


def _run_cli(root, *extra, expect=None):
    proc = subprocess.run(
        [sys.executable, "-m", "frankenpaxos_tpu.analysis",
         "--root", str(root), *extra],
        capture_output=True, text=True, timeout=300)
    if expect is not None:
        assert proc.returncode == expect, proc.stdout + proc.stderr
    return proc


def _git(root, *args):
    subprocess.run(
        ["git", "-c", "user.email=paxlint@test", "-c",
         "user.name=paxlint", *args],
        cwd=str(root), capture_output=True, text=True, check=True)


# --- SARIF <-> JSON round trip ----------------------------------------------


def test_sarif_and_json_carry_identical_finding_sets(tmp_path):
    """One new + one baselined violation: paxlint.json records and
    paxlint.sarif results are the same finding set, with ``baselined``
    mapping to SARIF level note (grandfathered) vs error (new)."""
    _write_pkg(tmp_path, {
        "old.py": SLEEPY_ACTOR.format(name="Old", delay="0.1")})
    baseline = tmp_path / "baseline.json"
    _run_cli(tmp_path, "--baseline", str(baseline),
             "--write-baseline", expect=0)
    _write_pkg(tmp_path, {
        "new.py": SLEEPY_ACTOR.format(name="New", delay="0.2")})

    json_out = tmp_path / "paxlint.json"
    sarif_out = tmp_path / "paxlint.sarif"
    _run_cli(tmp_path, "--baseline", str(baseline),
             "--output", str(json_out),
             "--sarif-output", str(sarif_out), expect=1)

    document = json.loads(json_out.read_text())
    sarif = json.loads(sarif_out.read_text())
    assert sarif["version"] == "2.1.0"
    (run,) = sarif["runs"]
    from_json = {(r["file"], r["line"], r["rule"], r["baselined"])
                 for r in document["findings"]}
    from_sarif = {
        (r["locations"][0]["physicalLocation"]["artifactLocation"]["uri"],
         r["locations"][0]["physicalLocation"]["region"]["startLine"],
         r["ruleId"],
         r["level"] == "note")
        for r in run["results"]}
    assert from_json == from_sarif and len(from_json) == 2
    assert {r["level"] for r in run["results"]} == {"note", "error"}
    # The driver carries metadata for exactly the rules that fired.
    assert {rule["id"] for rule in run["tool"]["driver"]["rules"]} \
        == {r["rule"] for r in document["findings"]}
    # Fingerprints are the baseline's stable (line-independent) keys.
    assert all(r["partialFingerprints"]["paxlintKey/v1"].count("|") == 3
               for r in run["results"])


def test_format_sarif_prints_document_and_gates(tmp_path):
    """--format=sarif: stdout IS the document, exit code still gates
    on new findings."""
    _write_pkg(tmp_path, {
        "bad.py": SLEEPY_ACTOR.format(name="Bad", delay="0.5")})
    proc = _run_cli(tmp_path, "--format", "sarif", expect=1)
    sarif = json.loads(proc.stdout)
    (result,) = sarif["runs"][0]["results"]
    assert result["ruleId"] == "PAX103" and result["level"] == "error"


# --- --changed-since: diff-aware equivalence --------------------------------


def test_changed_since_equals_full_run_on_closure(tmp_path):
    """The equivalence contract: for a synthetic diff touching one
    module, the diff-aware run reports exactly the full run's findings
    restricted to the changed module plus its reverse-import closure
    (and drops the untouched module's findings)."""
    _write_pkg(tmp_path, {
        "a.py": ACTOR_PREAMBLE,
        "b.py": SLEEPY_ACTOR.format(name="B", delay="0.2"),
        "c.py": "    from frankenpaxos_tpu import a\n"
                + SLEEPY_ACTOR.format(name="C", delay="0.3"),
    })
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    # The synthetic diff: a violation lands in a.py (imported by c.py).
    _write_pkg(tmp_path, {
        "a.py": SLEEPY_ACTOR.format(name="A", delay="0.1")})

    full_out = tmp_path / "full.json"
    _run_cli(tmp_path, "--output", str(full_out), expect=1)
    full = json.loads(full_out.read_text())["findings"]
    assert {f["file"] for f in full} == {
        "frankenpaxos_tpu/a.py", "frankenpaxos_tpu/b.py",
        "frankenpaxos_tpu/c.py"}

    diff_out = tmp_path / "diff.json"
    proc = _run_cli(tmp_path, "--changed-since", "HEAD",
                    "--output", str(diff_out), expect=1)
    assert "diff-aware" in proc.stdout + proc.stderr
    diff = json.loads(diff_out.read_text())["findings"]
    closure = {"frankenpaxos_tpu/a.py", "frankenpaxos_tpu/c.py"}
    assert diff == [f for f in full if f["file"] in closure]


def test_changed_since_out_of_package_change_runs_everything(tmp_path):
    """A change the rules might read (here: the analysis package
    itself is absent, so any in-package non-module path) degrades to a
    full run; a tests/docs-only change proves no finding can have
    changed and reports none."""
    _write_pkg(tmp_path, {
        "b.py": SLEEPY_ACTOR.format(name="B", delay="0.2")})
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    (tmp_path / "README.md").write_text("docs only\n")
    _git(tmp_path, "add", "-A")

    out = tmp_path / "diff.json"
    _run_cli(tmp_path, "--changed-since", "HEAD",
             "--output", str(out), expect=0)
    assert json.loads(out.read_text())["findings"] == []

    # An in-package asset (not a parsed module) forces the full run.
    (tmp_path / "frankenpaxos_tpu" / "table.json").write_text("{}\n")
    _git(tmp_path, "add", "-A")
    proc = _run_cli(tmp_path, "--changed-since", "HEAD",
                    "--output", str(out), expect=1)
    assert "everything" in proc.stdout + proc.stderr
    assert len(json.loads(out.read_text())["findings"]) == 1


def test_affected_closure_on_this_repo_stays_narrow():
    """The fast path the <10s budget depends on: a leaf bench module's
    closure must stay a handful of modules, not the project."""
    proj = Project(REPO_ROOT, package="frankenpaxos_tpu")
    closure = diff_mod.affected_closure(
        proj, ["frankenpaxos_tpu/bench/pipeline.py"])
    assert "frankenpaxos_tpu/bench/pipeline.py" in closure
    assert len(closure) < 10, sorted(closure)


def test_changed_since_runtime_budget():
    """Diff-aware mode on a one-module change stays under 10s (the
    full-run budget is 30s in tests/test_analysis.py): the project
    parses once, the global passes stay memoized, and every rule
    family skips or narrows to the focus closure.

    The budget is held on the process's own CPU time: the run is
    single-threaded Python in this process, and a wall clock would
    also count whatever the other test workers load the host with."""
    import time as _time

    start = _time.process_time()
    proj = Project(REPO_ROOT, package="frankenpaxos_tpu")
    proj.focus = diff_mod.affected_closure(
        proj, ["frankenpaxos_tpu/bench/pipeline.py"])
    run_rules(proj)
    elapsed = _time.process_time() - start
    assert elapsed < 10.0, (
        f"diff-aware paxlint run took {elapsed:.1f}s of CPU; the budget "
        f"is 10s on a one-module change (docs/ANALYSIS.md)")


# --- the baseline is burned down and stays empty ----------------------------


def test_baseline_is_empty_and_stays_empty():
    """COD301 was the last grandfathered family: the committed
    baseline is the empty list, and the CI lint job fails if an entry
    is ever re-added (fix or pragma instead of re-baselining)."""
    path = os.path.join(REPO_ROOT, ".paxlint-baseline.json")
    assert json.loads(open(path).read()) == [], (
        ".paxlint-baseline.json must stay empty: fix the finding or "
        "add a justified pragma; do not re-baseline")

"""OBS13xx: metric-name drift between exporters and dashboards.

The observability plane has two halves that only meet at runtime: the
``fpx_*`` series registered on a collector registry (obs/trace.py's
RuntimeMetrics and friends -- ``collectors.counter/gauge/histogram/
summary("fpx_...")``), and the PromQL expressions the Grafana
generator (``grafana/generate_dashboards.py``) and the committed
dashboards chart. Nothing ties them together: rename a metric on one
side and the dashboard goes silently blank -- the worst observability
failure mode, because every panel still renders.

Two directions, one rule family:

  * **OBS1301 -- charted but never exported.** An ``fpx_*`` series
    referenced anywhere under ``grafana/`` that no registered metric
    can produce. Histogram registrations export ``_bucket``/``_sum``/
    ``_count`` children and summaries ``_sum``/``_count``, so those
    suffixed forms resolve to their base registration; every other
    name must match a registration exactly.
  * **OBS1302 -- exported but never charted.** A registered ``fpx_*``
    metric that no dashboard or generator expression references (via
    any of its exported series forms) and that is not explicitly
    exempted. Anchored on the registration call so a justified
    ``# paxlint: disable=OBS1302`` pragma (or an ``_UNCHARTED_OK``
    entry here, for families) can clear it.

OBS1301 findings anchor in ``grafana/`` files, which are outside the
package: they surface in full runs (the CI gate) but not in
``--changed-since`` focus runs, like every out-of-focus finding.
"""

from __future__ import annotations

import ast
import os
import re

from frankenpaxos_tpu.analysis.core import (
    cached_walk,
    Finding,
    Project,
    register_rules,
)

RULES = {
    "OBS1301": "dashboard charts an fpx_* series no registered metric "
               "exports (renamed or deleted exporter)",
    "OBS1302": "registered fpx_* metric is charted nowhere and not "
               "exempted (dead series or missing panel)",
}

#: Registered metrics that are deliberately NOT charted. Each entry
#: needs a trailing comment saying why (scrape-only debugging series,
#: metrics consumed by alerts rather than panels, ...). Keep this
#: empty-by-default: the honest fix is usually a panel.
_UNCHARTED_OK: frozenset = frozenset()

#: Exported-series suffixes per registration kind. Counters/gauges
#: export exactly their registered name (this repo registers counters
#: WITH the ``_total`` suffix).
_CHILD_SUFFIXES = {
    "histogram": ("_bucket", "_sum", "_count"),
    "summary": ("_sum", "_count"),
    # Read when scraped (Collectors.sampled_summary): no buckets.
    "sampled_summary": ("_sum", "_count"),
}

_COLLECTOR_METHODS = ("counter", "gauge", "histogram", "summary",
                      "sampled_summary")

#: A series token: fpx_ followed by snake_case, not ending in ``_``
#: (so a bare ``fpx_runtime_`` prefix in prose never matches).
_SERIES_RE = re.compile(r"\bfpx_[a-z0-9_]*[a-z0-9]\b")

_GRAFANA_DIR = "grafana"


def _registrations(project: Project) -> dict:
    """{metric name: (module path, lineno, kind)} for every
    ``<obj>.counter/gauge/histogram/summary("fpx_...", ...)`` call in
    the package."""
    out: dict = {}
    for mod in project:
        for node in cached_walk(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _COLLECTOR_METHODS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("fpx_")):
                continue
            name = node.args[0].value
            out.setdefault(name, (mod.path, node.lineno, node.func.attr))
    return out


def _grafana_files(project: Project) -> list:
    """Repo-relative paths of the generator + committed dashboards."""
    root = os.path.join(project.root, _GRAFANA_DIR)
    files = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in sorted(filenames):
            if fn.endswith((".py", ".json")):
                abspath = os.path.join(dirpath, fn)
                files.append(os.path.relpath(abspath, project.root)
                             .replace(os.sep, "/"))
    return files


def _charted_series(project: Project) -> dict:
    """{series name: (grafana file, first lineno)}."""
    out: dict = {}
    for rel in _grafana_files(project):
        abspath = os.path.join(project.root, rel)
        with open(abspath, encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                for m in _SERIES_RE.finditer(line):
                    out.setdefault(m.group(0), (rel, lineno))
    return out


def _exported_forms(name: str, kind: str) -> tuple:
    """Every series name a registration can emit."""
    return (name,) + tuple(
        name + sfx for sfx in _CHILD_SUFFIXES.get(kind, ()))


def check(project: Project):
    registered = _registrations(project)
    charted = _charted_series(project)

    exported: set = set()
    for name, (_, _, kind) in registered.items():
        exported.update(_exported_forms(name, kind))

    findings = []
    for series, (rel, lineno) in sorted(charted.items()):
        if series in exported:
            continue
        findings.append(Finding(
            rule="OBS1301", file=rel, line=lineno,
            scope="<grafana>", detail=series,
            message=f"charts series {series} that no registered metric "
                    f"exports -- the panel renders blank; rename the "
                    f"expression or (re)register the metric"))

    for name, (path, lineno, kind) in sorted(registered.items()):
        if name in _UNCHARTED_OK:
            continue
        if any(form in charted for form in _exported_forms(name, kind)):
            continue
        findings.append(Finding(
            rule="OBS1302", file=path, line=lineno,
            scope="<registry>", detail=name,
            message=f"{kind} {name} is exported but charted nowhere -- "
                    f"add a panel (grafana/generate_dashboards.py), "
                    f"exempt it in analysis/obs_rules.py, or drop the "
                    f"registration"))
    return findings


register_rules(RULES, check)

"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one
reference or one metric is a file of its own, found by the name the
manifest gives it. The directories searched are the manifest's own
``paths``, in order, so a later PR adds a cell, a mix or a metric by
adding files and manifest entries and edits nothing that is there.

  configuration   the ``file`` of its ``configs`` entry
  traffic mix     ``<path>/traffic/<traffic>.json``
  deployment      ``<path>/deployments/<name>.py`` (named by the
                  configuration): cluster file, launch, settling
  role entry      the configuration's ``role_entry``, a path in the checkout
  reference       ``<path>/reference/<name>.py`` (named by the configuration)
  generator       ``<path>/generators/<name>.py`` (named by the traffic mix)
  metric reader   ``<path>/metrics/<part>.py``, where ``<part>`` is the
                  longest run of the metric's dotted parts that has a file,
                  tried as it stands and then with ``_p<NN>`` taken out, so
                  that one reader serves every percentile of a quantity and
                  takes ``NN`` from the name it is given
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

#: harness/ -> benchmark/ -> the checkout
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ManifestError(Exception):
    pass


def load_module(path: str):
    name = "bench_" + os.path.relpath(path, ROOT).replace(
        os.sep, "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        with open(self.path) as f:
            self.data = json.load(f)

    def _entry(self, section: str, name: str) -> dict:
        for entry in self.data[section]:
            if entry["name"] == name:
                return entry
        known = sorted(e["name"] for e in self.data[section])
        raise ManifestError(f"no {section} entry named {name!r}; "
                            f"there are {known}")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        """The configuration as it is run: the contents of its file."""
        with open(os.path.join(ROOT, self._entry("configs", name)["file"])) as f:
            return json.load(f)

    def find(self, kind: str, filename: str) -> "str | None":
        for base in self.data["paths"]:
            path = os.path.join(ROOT, base, kind, filename)
            if os.path.isfile(path):
                return path
        return None

    def traffic(self, name: str) -> dict:
        path = self.find("traffic", name + ".json")
        if path is None:
            raise ManifestError(f"no traffic/{name}.json under "
                                f"{self.data['paths']}")
        with open(path) as f:
            return json.load(f)

    def module_path(self, kind: str, name: str) -> str:
        path = self.find(kind, name + ".py")
        if path is None:
            raise ManifestError(f"no {kind}/{name}.py under "
                                f"{self.data['paths']}")
        return path

    def module(self, kind: str, name: str):
        """``<path>/<kind>/<name>.py``, loaded: a deployment or a
        reference, by the name its configuration gives it."""
        return load_module(self.module_path(kind, name))

    def reader_path(self, metric: str) -> "str | None":
        parts = metric.split(".")
        for length in range(len(parts), 0, -1):
            for first in range(len(parts) - length + 1):
                run = ".".join(parts[first:first + length])
                for name in (run, re.sub(r"_p\d+(?=_|$)", "", run)):
                    path = self.find("metrics", name + ".py")
                    if path is not None:
                        return path
        return None

    def reader(self, metric: str):
        path = self.reader_path(metric)
        if path is None:
            raise ManifestError(f"no metrics/<prefix>.py for {metric!r} "
                                f"under {self.data['paths']}")
        return load_module(path)

    def metrics_of(self, section: str, cell: str) -> list:
        """The ``section`` entries that ``cell`` reports."""
        return [m for m in self.data[section]
                if cell in m.get("workloads", [cell])]

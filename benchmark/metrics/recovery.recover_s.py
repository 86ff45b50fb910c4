"""Seconds from the kill of the whole storage tier to a write committed
by what came back from the logs: ``recover_s`` of the ``recovery.json``
that the deployment leaves beside the records (monotonic instants of the
launcher; after the window, so no other metric sees it). Nothing where
the deployment kills nothing, or the probe write never committed."""

import json
import os

from harness.manifest import ROOT


def read(run, metric):
    path = os.path.join(ROOT, ".bench_runs", run.cell["name"], "records",
                        "recovery.json")
    try:
        with open(path) as f:
            return json.load(f).get("recover_s")
    except (OSError, ValueError):
        return None

"""What the stage readers share: the program's own account of where a
role's threads spent the window.

Every role exports ``fpx_runtime_drain_stage_seconds_{sum,count}{role,
stage}``, made when scraped from per-thread accumulators: one
observation per batch of work (a chunk of frames, a drain, a flush pass,
a selector wait, a collection), holding SELF time: a stage opened inside
another on the same thread subtracts from it, so the stages of one
thread add up. The readers take the growth of ``_sum`` and ``_count``
between the window's two scrapes in the busiest process of a kind (by
CPU time, as ``role.cpu_pct`` picks it). A program that has no such
stage gives no growth, and the reader then returns nothing.

Told apart by name: a stage ending in ``-wait`` is time waited, not time
busy; ``loop-wait`` is the event loop's own wait in its selector;
``collect`` runs on the collector threads, beside the loop, and counts
all of them; every other stage is the loop's thread (``gc`` also counts
the few collections that stopped another thread).
"""

from __future__ import annotations

import re

SERIES = re.compile(
    r'^fpx_runtime_drain_stage_seconds_(sum|count)\{.*\bstage="([^"]*)"')
LOOP_WAIT = "loop-wait"
#: Stages that run beside the event loop's thread.
OFF_LOOP = ("collect",)


def busiest(run, kind: str):
    """The label of the process of ``kind`` that burned most CPU in the
    window, or None."""
    labels = [label for label in run.scrapes["end"]
              if label.startswith(kind)]
    if not labels:
        return None
    return max(labels, key=lambda label: run.cpu_s.get(label, 0.0))


def growth(run, kind: str) -> dict:
    """``{stage: (seconds, observations)}`` over the window in the
    busiest process of ``kind``; only stages that were observed."""
    label = busiest(run, kind)
    if label is None:
        return {}
    first = run.scrapes["start"].get(label, {})
    grown: dict = {}
    for series, value in run.scrapes["end"][label].items():
        match = SERIES.match(series)
        if match is None:
            continue
        part, stage = match.groups()
        pair = grown.setdefault(stage, [0.0, 0.0])
        pair[1 if part == "count" else 0] = value - first.get(series, 0.0)
    return {stage: (seconds, count)
            for stage, (seconds, count) in grown.items() if count > 0}


def on_loop(stage: str) -> bool:
    """Is ``stage`` time of the event loop's thread, busy or waiting?"""
    if stage in OFF_LOOP:
        return False
    return stage == LOOP_WAIT or not stage.endswith("-wait")

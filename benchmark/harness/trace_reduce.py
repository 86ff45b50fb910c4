"""From a profiler trace to numbers, and the table of peaks.

``reduce`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote in the
chip-owning process and returns, for the span the role entry marked
(``role_entry.TRACED_SPAN``): its length, the seconds in which an
operation ran on the device (the union of the device operations'
intervals, averaged over the chips), the operations that took most
time, the longest idle gaps named by what the host was doing in them,
and the host spans of the benchmark's own annotations.

Which planes and lines of a trace are device operations depends on the
device, so ``peaks.json`` names them beside the peaks, keyed by
``device_kind``. A kind that is not in the table is an error, never a
default.

``least_bytes`` is the bytes the quorum work of a span needs, counted
from what went in and what came out and from the board's shape. It names
no kernel: whatever program does the work is held to the same count.
"""

from __future__ import annotations

import json
import os

from harness.role_entry import TRACED_SPAN

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
TOP = 10
#: A gap is named by a host event only if that event covers this share.
NAMED_SHARE = 0.5
UNTRACED = "host_outside_any_traced_event"
#: A TPU operation's name is its whole HLO line; this much of it is kept.
NAME_CHARS = 120


def peaks_of(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"{PEAKS_FILE}: add it with its source, there is "
                       f"no default")
    return table[device_kind]


def least_bytes(votes: int, decisions: int, nodes: int) -> int:
    """Each vote's board cell is read and written (2 bytes of a uint8
    board), and each decision reads its slot's column of ``nodes`` cells
    and writes one byte out."""
    return 2 * votes + (nodes + 1) * decisions


def union(intervals: list) -> list:
    """Sorted, disjoint ``[start, end]`` intervals covering the same
    points."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def clip(events: list, lo: float, hi: float) -> list:
    """``(name, start, end)`` events cut to ``[lo, hi]``; those outside
    are dropped."""
    return [(name, max(start, lo), min(end, hi))
            for name, start, end in events if end > lo and start < hi]


def read_planes(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, end_ns)]}}``. Lines of one name
    within a plane are merged."""
    from jax.profiler import ProfileData

    planes: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (event.name, event.start_ns,
                 event.start_ns + event.duration_ns)
                for event in line.events)
    return planes


def name_gap(host_events: list, lo: float, hi: float) -> str:
    """The host event that covers most of the gap, if it covers at least
    ``NAMED_SHARE`` of it."""
    best, best_overlap = UNTRACED, NAMED_SHARE * (hi - lo)
    for name, start, end in host_events:
        overlap = min(end, hi) - max(start, lo)
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def reduce_planes(planes: dict, device_kind: str, chips: int) -> dict:
    layout = peaks_of(device_kind)["trace"]
    host_events = [event
                   for plane, lines in planes.items()
                   if plane.startswith("/host:")
                   for line, events in lines.items()
                   if not any(line.startswith(p)
                              for p in layout["op_lines"])
                   for event in events]
    spans = [e for e in host_events if e[0] == TRACED_SPAN]
    if len(spans) != 1:
        raise ValueError(f"the trace holds {len(spans)} {TRACED_SPAN!r} "
                         f"events; the role entry writes exactly one")
    _, lo, hi = spans[0]
    host_events = [e for e in clip(host_events, lo, hi)
                   if e[0] != TRACED_SPAN]

    devices = []
    for plane, lines in sorted(planes.items()):
        if not plane.startswith(layout["plane_prefix"]):
            continue
        ops = [event for line, events in lines.items()
               if any(line.startswith(p) for p in layout["op_lines"])
               for event in events if event[2] > event[1]]
        devices.append(clip(ops, lo, hi))
    devices = [ops for ops in devices if ops][:chips] or [[]]

    busy_ns = 0.0
    by_op: dict = {}
    for ops in devices:
        busy_ns += sum(end - start for start, end in
                       union([(s, e) for _, s, e in ops]))
        for name, start, end in ops:
            by_op[name] = by_op.get(name, 0.0) + (end - start)
    # Gaps of the first device, between its busy intervals and to the
    # span's ends.
    edges = [lo] + [t for interval in
                    union([(s, e) for _, s, e in devices[0]])
                    for t in interval] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:TOP]
    host_spans: dict = {}
    for name, start, end in host_events:
        if name.startswith("bench."):
            span = host_spans.setdefault(name, {"count": 0, "total_s": 0.0})
            span["count"] += 1
            span["total_s"] += (end - start) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / len(devices),
        "device_ops": [[name[:NAME_CHARS], ns / 1e9] for name, ns in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name_gap(host_events, start, end), length / 1e9]
                      for length, start, end in gaps],
        "host_spans": host_spans,
    }


def find_xplane(trace_dir: str) -> str:
    found = [os.path.join(base, name)
             for base, _, names in os.walk(trace_dir)
             for name in names if name.endswith(".xplane.pb")]
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under "
                                f"{trace_dir}")
    return found[0]


def reduce(trace_dir: str, device_kind: str, chips: int) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)), device_kind,
                         chips)

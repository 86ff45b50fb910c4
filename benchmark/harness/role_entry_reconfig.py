#!/usr/bin/env python3
"""One role process of a deployment whose acceptor set is replaced under
load: ``role_entry.py`` with two recorders more. What they hold is added
to the record ``role_entry`` writes when the process exits.

  the chip owner  for each epoch tracker (the program's counting of votes
                  once an epoch has changed), the votes it was fed, by
                  the voter's ADDRESS, and the quorums it reported, in
                  arrival order, as ``<label>.tracker<k>.npz`` beside the
                  single-epoch trackers' (``votes`` rows are sequence
                  number, first slot, end slot, round, voter; ``reports``
                  rows sequence number, slot, round). Its entry in the
                  record's ``trackers`` has ``kind: "epoch"``, the
                  addresses by voter number, the board's shape, which
                  single-epoch tracker it took over from
                  (``predecessor``, its place in ``trackers``).
  a leader        ``epoch_events``, in order: every epoch it defined
                  (``define``: id, start slot, round, members by address,
                  whether it re-drove an adopted one), every
                  acknowledgement of a commit that reached it (``ack``:
                  epoch, round, from whom), the first proposal after each
                  definition (``proposed``: first slot, how many), and
                  each activation; every one with its monotonic instant.
  every process   ``cluster_acceptors``: the acceptor groups of the
                  cluster file it was started with, by address (the
                  first group is epoch 0).

Each costs O(1) a message: one append. Expansion and dumping happen at
exit.

Names of the program this file holds on to, beside ``role_entry``'s:
``proxy_leader.EpochQuorumTracker`` (the module's name for
``reconfig.EpochQuorumTracker``) with ``record`` / ``record_range`` /
``record_votes`` / ``drain``, its ``votes`` / ``launches`` / ``planes``
and ``_checker.board.votes``; ``ProxyLeader.__init__``,
``._ensure_epoch_tracker``, ``.tracker`` and ``._epoch_tracker``; ``Leader._drive_epoch_change``,
``._handle_epoch_ack``, ``._send_epoch_runs``, ``._epoch_change`` (its
``config`` and ``activated``), ``.round`` and ``.next_slot``.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCHMARK not in sys.path:
    sys.path.insert(0, BENCHMARK)

from harness import role_entry  # noqa: E402

EPOCH = "epoch"


def main(argv: list, wrap_tracker=None, wrap_store=None) -> None:
    """``wrap_tracker`` and ``wrap_store`` are ``role_entry.main``'s."""
    record_dir, cli_argv = argv[0], argv[2:]
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    import numpy as np

    from frankenpaxos_tpu.deploy import process_label
    from frankenpaxos_tpu.protocols.multipaxos import leader, proxy_leader

    label = process_label(cli_argv[cli_argv.index("--role") + 1],
                          cli_argv[cli_argv.index("--index") + 1])
    epoch_trackers: list = []
    base = proxy_leader.EpochQuorumTracker
    chain = itertools.chain.from_iterable

    class RecordingEpochTracker(base):
        def __init__(self, *args, **kwargs):
            t0 = time.monotonic()
            super().__init__(*args, **kwargs)
            self.init_s = time.monotonic() - t0
            # In arrival order: a 4-tuple is a range of votes, a 3-tuple
            # an array of votes, an [n, 2] array what one drain reported.
            self.events: list = []
            self.note = self.events.append
            self.voters: dict = {}
            self.predecessor = None      # the tracker it took over from
            epoch_trackers.append(self)

        def voter(self, address) -> int:
            return self.voters.setdefault(address, len(self.voters))

        def record(self, slot, round, voter):
            self.note((slot, slot + 1, round, self.voter(voter)))
            super().record(slot, round, voter)

        def record_range(self, slot_start, slot_end, round, voter):
            self.note((slot_start, slot_end, round, self.voter(voter)))
            super().record_range(slot_start, slot_end, round, voter)

        def record_votes(self, slots, rounds, voter):
            self.note((slots, rounds, self.voter(voter)))
            super().record_votes(slots, rounds, voter)

        def drain(self):
            out = super().drain()
            if out:
                self.note(np.fromiter(chain(out), dtype=np.int64,
                                      count=2 * len(out)).reshape(-1, 2))
            return out

    proxy_leader.EpochQuorumTracker = RecordingEpochTracker

    # The recording single-epoch trackers in the order they were built
    # in, which is the order ``role_entry`` lists them in.
    built: list = []
    init = proxy_leader.ProxyLeader.__init__
    ensure = proxy_leader.ProxyLeader._ensure_epoch_tracker

    def recording_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        if hasattr(self.tracker, "events"):
            built.append(self.tracker)

    def recording_ensure(self):
        had = self._epoch_tracker
        ensure(self)
        made = self._epoch_tracker
        if had is None and made is not None:
            made.predecessor = next(
                (k for k, t in enumerate(built) if t is self.tracker), None)

    proxy_leader.ProxyLeader.__init__ = recording_init
    proxy_leader.ProxyLeader._ensure_epoch_tracker = recording_ensure

    # A leader's part in every epoch change, in order.
    epoch_events: list = []
    note = epoch_events.append
    awaited: set = set()         # leaders whose next proposal is noted
    drive = leader.Leader._drive_epoch_change
    on_ack = leader.Leader._handle_epoch_ack
    send_runs = leader.Leader._send_epoch_runs

    def recording_drive(self, config, predecessor, recommit):
        note(("define", config.epoch, config.start_slot, self.round,
              [list(a) for a in config.members], bool(recommit),
              time.monotonic()))
        awaited.add(id(self))
        drive(self, config, predecessor, recommit)

    def recording_ack(self, src, ack):
        note(("ack", ack.epoch, ack.round, list(src), time.monotonic()))
        change = self._epoch_change
        was = change is not None and change.activated
        on_ack(self, src, ack)
        if change is not None and not was and change.activated:
            note(("activated", change.config.epoch, time.monotonic()))

    def recording_send_runs(self, values):
        if id(self) in awaited:
            awaited.discard(id(self))
            note(("proposed", self.next_slot, len(values),
                  time.monotonic()))
        send_runs(self, values)

    leader.Leader._drive_epoch_change = recording_drive
    leader.Leader._handle_epoch_ack = recording_ack
    leader.Leader._send_epoch_runs = recording_send_runs

    def dump_more() -> None:
        """Runs after ``role_entry``'s own dump (registered before it)."""
        path = os.path.join(record_dir, f"{label}.json")
        if not os.path.exists(path):
            return
        with open(path) as f:
            record = json.load(f)
        record["epoch_events"] = epoch_events
        # The acceptor groups of the cluster file this process was
        # started with, by address: the first group is epoch 0.
        with open(cli_argv[cli_argv.index("--config") + 1]) as f:
            record["cluster_acceptors"] = json.load(f)["acceptors"]
        for tracker in epoch_trackers:
            votes, reports = expand(np, tracker.events)
            np.savez(os.path.join(
                record_dir,
                f"{label}.tracker{len(record['trackers'])}.npz"),
                votes=votes, reports=reports)
            board = getattr(tracker._checker, "board", None)
            record["trackers"].append({
                "kind": EPOCH, "init_s": tracker.init_s,
                "predecessor": tracker.predecessor,
                "addresses": [list(a) for a in tracker.voters],
                "board_shape": (None if board is None
                                else list(board.votes.shape)),
                "votes": tracker.votes, "launches": tracker.launches,
                "planes": tracker.planes, "window_violations": 0})
        with open(path + ".tmp", "w") as f:
            json.dump(record, f)
        os.replace(path + ".tmp", path)

    atexit.register(dump_more)
    role_entry.main(argv, wrap_tracker=wrap_tracker, wrap_store=wrap_store)


def expand(np, events: list) -> tuple:
    """``events`` as two int64 arrays: votes ``[n, 5]`` of (sequence
    number, first slot, end slot, round, voter), one row a range or a vote
    of an array, and reports ``[m, 3]`` of (sequence number, slot,
    round)."""
    ranges, arrays, reports = [], [], []
    for seq, event in enumerate(events):
        if not isinstance(event, tuple):
            block = np.empty((len(event), 3), dtype=np.int64)
            block[:, 0] = seq
            block[:, 1:] = event
            reports.append(block)
        elif len(event) == 4:
            ranges.append((seq, *event))
        else:
            slots, rounds, voter = event
            block = np.empty((len(slots), 5), dtype=np.int64)
            block[:, 0] = seq
            block[:, 1] = slots
            block[:, 2] = block[:, 1] + 1
            block[:, 3] = rounds
            block[:, 4] = voter
            arrays.append(block)
    votes = np.concatenate(
        [np.asarray(ranges, dtype=np.int64).reshape(-1, 5), *arrays])
    votes = votes[np.argsort(votes[:, 0], kind="stable")]
    reported = (np.concatenate(reports) if reports
                else np.empty((0, 3), dtype=np.int64))
    return votes, reported


if __name__ == "__main__":
    main(sys.argv[1:])

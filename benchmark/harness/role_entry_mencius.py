#!/usr/bin/env python3
"""One role process of a benchmarked Mencius deployment.

``role_entry.py`` with two more recorders:

  the chip owner   a Mencius proxy leader builds its device tracker as
                   ``mencius.roles.TpuQuorumTracker``; here that name
                   builds ``role_entry``'s recording tracker, so its votes
                   and reports are recorded as a MultiPaxos proxy
                   leader's are. A strided range of votes (a leader
                   group's run, or its slots of a noop range) is noted as
                   it arrives as one ``range`` object, O(1) in the
                   window, and expanded into one row a vote when the
                   records are written at exit, as ``role_entry.expand``
                   expands arrays.
  a replica        at exit, beside the executed writes ``role_entry``
                   records, what the replica's log holds at every slot
                   below its executed watermark, added to
                   ``<label>.replica.npz``: ``slot_kinds`` (1 a command
                   batch, 0 a noop, -1 nothing) and ``slot_commands``
                   (the commands a batch holds, 0 for a noop).

Reads ordered through the log execute a ``GetRequest``, which carries no
``key_values``; ``role_entry``'s store recorder skips them, so the
executed writes are the writes alone.

Everything else (the timed claim, the traced span, the counters, the
dump) is ``role_entry.main``, untouched. A planted fault's
``wrap_tracker`` and ``wrap_store`` go where they go there.
"""

from __future__ import annotations

import atexit
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HARNESS_PARENT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, HARNESS_PARENT):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import role_entry  # noqa: E402


def strided(recording):
    """``recording`` (role_entry's tracker class) taking strided ranges:
    noted as one ``range`` each, and passed to the tracker underneath
    the recorder."""
    underneath = recording.__bases__[0]

    class StridedRecording(recording):
        def record_range(self, slot_start, slot_end, round, group_index,
                         acceptor_index, stride=1):
            if stride == 1:
                super().record_range(slot_start, slot_end, round,
                                     group_index, acceptor_index)
                return
            self.note((range(slot_start, slot_end, stride), round,
                       group_index, acceptor_index))
            underneath.record_range(self, slot_start, slot_end, round,
                                    group_index, acceptor_index, stride)

    return StridedRecording


def main(argv: list, wrap_tracker=None, wrap_store=None) -> None:
    import numpy as np

    from frankenpaxos_tpu.deploy import process_label
    from frankenpaxos_tpu.protocols.mencius import replica, roles
    from frankenpaxos_tpu.protocols.multipaxos import proxy_leader

    record_dir, cli_argv = argv[0], argv[2:]
    label = process_label(cli_argv[cli_argv.index("--role") + 1],
                          cli_argv[cli_argv.index("--index") + 1])
    made: dict = {}

    def board_tracker(*args, **kwargs):
        # role_entry.main has put its recorder in proxy_leader's place
        # before any role is built.
        recording = proxy_leader.TpuQuorumTracker
        if recording not in made:
            made[recording] = strided(recording)
        return made[recording](*args, **kwargs)

    roles.TpuQuorumTracker = board_tracker

    replicas: list = []
    replica_init = replica.MenciusReplica.__init__

    def recording_init(self, *args, **kwargs):
        replica_init(self, *args, **kwargs)
        replicas.append(self)

    replica.MenciusReplica.__init__ = recording_init

    def dump_slots() -> None:
        # Registered before role_entry's dump, so it runs after it, and
        # adds to the file that one wrote.
        path = os.path.join(record_dir, f"{label}.replica.npz")
        if not replicas or not os.path.exists(path):
            return
        with np.load(path) as written:
            arrays = dict(written)
        r = replicas[0]
        kinds = np.full(r.executed_watermark, -1, dtype=np.int8)
        commands = np.zeros(r.executed_watermark, dtype=np.int32)
        for slot in range(r.executed_watermark):
            value = r.log.get(slot)
            if value is None:
                continue
            held = getattr(value, "commands", None)
            kinds[slot] = 0 if held is None else 1
            commands[slot] = 0 if held is None else len(held)
        np.savez(path, slot_kinds=kinds, slot_commands=commands, **arrays)

    atexit.register(dump_slots)
    role_entry.main(argv, wrap_tracker=wrap_tracker, wrap_store=wrap_store)


if __name__ == "__main__":
    main(sys.argv[1:])

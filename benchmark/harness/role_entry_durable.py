#!/usr/bin/env python3
"""One role process of a benchmarked deployment that keeps a write-ahead
log, and is killed and started again from it at the end of the run.

``role_entry.py`` for every role. The chip owner also notes, on the
dump's signal, how many events its trackers' recorders hold by then
(``first_life_events`` in its record: what came before the kill). Around
an acceptor or a replica that is given ``--wal_dir``:

  the sidecar   the role's ``FileStorage`` is wrapped so that after every
                ``sync()`` the segment's number and the length the file
                system then holds of it are stored, as one 8-byte word,
                in ``<wal_dir>/<label>/synced.len`` through a shared
                mapping (the word survives the process). What lies past
                it when the process is killed was never covered by an
                fsync: ``cut_unsynced`` truncates it away, which is the
                deployment's "discard everything written after the last
                sync". It reads the file, not the program's buffers, so
                a program that writes early and fsyncs late is held too.
  the dump      on SIGUSR2 (SIGUSR1 is the trace's in ``role_entry``) the
                records ``role_entry`` dumps at exit, plus the state
                below, are written as ``<label>.life1.json`` and
                ``<label>.life1.replica.npz``, and the process goes on.
  two lives     a process that finds ``<label>.life1.json`` is the second
                life. At its exit, after ``role_entry`` has dumped,
                ``<label>.json`` and ``<label>.replica.npz`` are rewritten
                to hold both: the first life's under the names
                ``reference/multipaxos_kv.py`` reads, the second's beside.

``<label>.json`` of a storage role, besides ``role_entry``'s keys:

  kind, lives, storage      "acceptor" / "replica"; 1 or 2; the file
                            system under the log (``/proc/mounts``)
  life1                     at the dump: a replica's executed watermark
                            and client table, an acceptor's round
  life2.recovered           as rebuilt from the log, before any message:
                            a replica's executed watermark, the
                            snapshot's watermark, how many writes the
                            replay executed (and how many of them before
                            the snapshot, which replaces them), its store
                            and client table; an acceptor's round
  life2.final               the same at exit, after the probe
  unsynced_bytes_discarded, what the deployment left beside the records
  recovery                  (``<label>.cut.json``, ``recovery.json``)

``<label>.replica.npz``:

  a replica    keys, values (as ``role_entry``), slots: the slot of each
               executed write; recovered_keys, recovered_values,
               recovered_slots: every write the second life executed,
               replay first (key ids index ``life2.key_names``)
  an acceptor  voted_runs [n, 3] (first slot, end slot, round) and
               voted_slots [m, 2] (slot, round) at the dump;
               recovered_voted_runs, recovered_voted_slots as rebuilt
               from the log

Names of the program this file holds on to, besides ``role_entry``'s:
``frankenpaxos_tpu.wal.FileStorage`` (``sync``, ``size``, ``root``) as
``DeployCtx.wal`` looks it up; ``multipaxos.acceptor.Acceptor`` with
``round``, ``_voted_runs`` (start -> (end, round, values)) and ``states``
(slot -> ``vote_round``); ``multipaxos.replica.Replica`` with
``executed_watermark`` (the slot being executed while its command runs),
``client_table`` and ``_restore_snapshot``.
"""

from __future__ import annotations

import atexit
import json
import mmap
import os
import signal
import struct
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HARNESS_PARENT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, HARNESS_PARENT):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import role_entry  # noqa: E402

SIDECAR = "synced.len"
DUMP_SIGNAL = signal.SIGUSR2
STORAGE_KINDS = ("acceptor", "replica")
_WORD = struct.Struct("<Q")
_LENGTH_BITS = 40


def segment_index(name: str) -> int:
    """``seg-00000012.wal`` -> 12."""
    return int(name[4:-4])


def read_synced(root: str) -> tuple:
    """(segment number, length) of the last recorded fsync under
    ``root``; (0, 0) where none was recorded."""
    try:
        with open(os.path.join(root, SIDECAR), "rb") as f:
            word = f.read(_WORD.size)
    except FileNotFoundError:
        return 0, 0
    if len(word) < _WORD.size:
        return 0, 0
    (value,) = _WORD.unpack(word)
    return value >> _LENGTH_BITS, value & (1 << _LENGTH_BITS) - 1


def cut_unsynced(root: str) -> int:
    """Truncate every segment under ``root`` to what the last recorded
    fsync covered, and return the bytes cut. A segment before the
    recorded one was synced whole before the log moved on; the recorded
    one is cut to the recorded length; a later one was never synced."""
    if not os.path.isdir(root):
        return 0
    synced_segment, synced_length = read_synced(root)
    cut = 0
    for name in sorted(os.listdir(root)):
        if not (name.startswith("seg-") and name.endswith(".wal")):
            continue
        index = segment_index(name)
        if index < synced_segment:
            continue
        path = os.path.join(root, name)
        keep = synced_length if index == synced_segment else 0
        size = os.path.getsize(path)
        if size > keep:
            cut += size - keep
            with open(path, "r+b") as f:
                f.truncate(keep)
                os.fsync(f.fileno())
    return cut


def storage_of(path: str) -> dict:
    """The file system that holds ``path``: the longest mount point of
    ``/proc/mounts`` that is a prefix of it."""
    path = os.path.realpath(path)
    best = {"path": path, "mount": None, "device": None, "fstype": None}
    try:
        with open("/proc/mounts") as f:
            mounts = [line.split() for line in f]
    except OSError:
        return best
    for fields in mounts:
        if len(fields) < 3:
            continue
        device, mount, fstype = fields[:3]
        mount = mount.replace("\\040", " ")
        if (path == mount or path.startswith(mount.rstrip("/") + "/")) and (
                best["mount"] is None or len(mount) >= len(best["mount"])):
            best.update(mount=mount, device=device, fstype=fstype)
    return best


def synced_length_storage(base):
    """``base`` (the program's ``FileStorage``, or a planted fault over
    it) with the sidecar: after every ``sync`` one 8-byte store."""

    class SyncedLengthStorage(base):
        def __init__(self, root: str):
            super().__init__(root)
            path = os.path.join(root, SIDECAR)
            if not os.path.exists(path):
                with open(path, "wb") as f:
                    f.write(bytes(_WORD.size))
            self._sidecar_file = open(path, "r+b")
            self._sidecar = mmap.mmap(self._sidecar_file.fileno(),
                                      _WORD.size)

        def sync(self, name: str) -> None:
            super().sync(name)
            # What the file system holds of the segment now is what the
            # fsync covered: one thread writes it.
            _WORD.pack_into(self._sidecar, 0,
                            segment_index(name) << _LENGTH_BITS
                            | self.size(name))

    return SyncedLengthStorage


def mark_trackers(argv: list, wrap_tracker, wrap_store) -> None:
    """A role that keeps no log, run as ``role_entry`` runs it. If it
    has device trackers (the chip owner), then on the dump's signal it
    notes how many events each tracker's recorder holds: what came
    before the storage tier was killed. At exit the counts go into
    ``<label>.json`` as ``first_life_events``."""
    from frankenpaxos_tpu.deploy import process_label

    record_dir, cli_argv = argv[0], argv[2:]
    label = process_label(cli_argv[cli_argv.index("--role") + 1],
                          cli_argv[cli_argv.index("--index") + 1])
    trackers: list = []
    marks: list = []

    def listed(base):
        if wrap_tracker is not None:
            base = wrap_tracker(base)

        class Listed(base):
            def __init__(self, *args, **kwargs):
                super().__init__(*args, **kwargs)
                trackers.append(self)

        return Listed

    def mark(*_) -> None:
        marks[:] = [len(tracker.events) for tracker in trackers]

    def finish() -> None:
        path = os.path.join(record_dir, f"{label}.json")
        if not marks or not os.path.exists(path):
            return
        with open(path) as f:
            record = json.load(f)
        record["first_life_events"] = marks
        with open(path + ".tmp", "w") as f:
            json.dump(record, f)
        os.replace(path + ".tmp", path)

    atexit.register(finish)  # before ``role_entry``'s dump: runs after it
    signal.signal(DUMP_SIGNAL, mark)
    role_entry.main(argv, wrap_tracker=listed, wrap_store=wrap_store)


def main(argv: list, wrap_tracker=None, wrap_store=None,
         wrap_storage=None) -> None:
    """``wrap_storage(cls) -> cls`` lets a planted fault break the
    storage underneath the sidecar; the benchmark passes none."""
    record_dir, cli_argv = argv[0], argv[2:]
    kind = cli_argv[cli_argv.index("--role") + 1]
    if kind not in STORAGE_KINDS or "--wal_dir" not in cli_argv:
        mark_trackers(argv, wrap_tracker, wrap_store)
        return

    import numpy as np

    from frankenpaxos_tpu import wal
    from frankenpaxos_tpu.deploy import process_label
    from frankenpaxos_tpu.protocols.multipaxos import acceptor, replica

    label = process_label(kind, cli_argv[cli_argv.index("--index") + 1])
    wal_root = os.path.join(cli_argv[cli_argv.index("--wal_dir") + 1], label)
    prefix = os.path.join(record_dir, label)
    second_life = os.path.exists(prefix + ".life1.json")

    base = wal.FileStorage
    if wrap_storage is not None:
        base = wrap_storage(base)
    wal.FileStorage = synced_length_storage(base)

    # The one role of this process, and its state as its constructor
    # left it (in a second life: as rebuilt from the log).
    roles: list = []
    recovered: dict = {}
    snapshot_watermark = [0, 0]  # the watermark, writes replayed before
    executed_slots: list = []

    def table_of(role) -> dict:
        return {f"{address}/{pseudonym}": client_id
                for (address, pseudonym), (client_id, _)
                in role.client_table.items()}

    def state_of(role) -> dict:
        if kind == "acceptor":
            return {"round": role.round,
                    "max_voted_slot": role.max_voted_slot}
        return {"executed_watermark": role.executed_watermark,
                "client_table": table_of(role),
                "store": dict(role.state_machine.kvs)}

    def votes_of(role) -> dict:
        runs = [(start, end, round) for start, (end, round, _)
                in role._voted_runs.items()]
        slots = [(slot, state.vote_round)
                 for slot, state in role.states.items()]
        return {"voted_runs": np.asarray(runs, dtype=np.int64).reshape(-1, 3),
                "voted_slots": np.asarray(slots,
                                          dtype=np.int64).reshape(-1, 2)}

    role_class = acceptor.Acceptor if kind == "acceptor" else replica.Replica
    role_init = role_class.__init__

    def recording_init(self, *args, **kwargs):
        roles.append(self)
        role_init(self, *args, **kwargs)
        recovered.update(state_of(self))
        if kind == "acceptor":
            recovered["votes"] = votes_of(self)
        else:
            recovered["snapshot_watermark"] = snapshot_watermark[0]
            recovered["superseded_writes"] = snapshot_watermark[1]
            recovered["replayed_writes"] = len(executed_slots)

    role_class.__init__ = recording_init

    if kind == "replica":
        restore = replica.Replica._restore_snapshot

        def recording_restore(self, payload):
            restore(self, payload)
            # What the replay executed before a snapshot, the snapshot
            # replaces.
            snapshot_watermark[:] = [self.executed_watermark,
                                     len(executed_slots)]

        replica.Replica._restore_snapshot = recording_restore

    def record_slots(store_class) -> None:
        """On top of ``role_entry``'s recorder, which it leaves in
        place: the slot of every executed write, one append a write.
        While a slot's command runs the replica's watermark is that
        slot."""
        recorded_run = store_class.typed_run

        def run_with_slot(self, input):
            writes = getattr(input, "key_values", None)
            if writes is not None and roles:
                slot = roles[0].executed_watermark
                for _ in writes:
                    executed_slots.append(slot)
            return recorded_run(self, input)

        store_class.typed_run = run_with_slot
        if wrap_store is not None:
            wrap_store(store_class)

    # ``role_entry.main`` registers its dump with ``atexit`` and never
    # returns: take the function as it is registered.
    dumps: list = []
    register = atexit.register

    def capturing_register(function, *args, **kwargs):
        dumps.append(function)
        return register(function, *args, **kwargs)

    def load(path: str):
        if not os.path.exists(path):
            return {}
        with np.load(path) as arrays:
            return dict(arrays)

    def write_json(path: str, record: dict) -> None:
        with open(path + ".tmp", "w") as f:
            json.dump(record, f)
        os.replace(path + ".tmp", path)

    def write_arrays(path: str, arrays: dict) -> None:
        # numpy appends ".npz" to a name that lacks it.
        np.savez(path + ".tmp.npz", **arrays)
        os.replace(path + ".tmp.npz", path)

    def this_life() -> tuple:
        """What ``role_entry``'s dump wrote, taken back from where it
        wrote it, with this file's additions: (record, arrays)."""
        with open(prefix + ".json") as f:
            record = json.load(f)
        arrays = load(prefix + ".replica.npz")
        record.update(kind=kind, storage=storage_of(wal_root))
        if roles:
            role = roles[0]
            if kind == "acceptor":
                arrays.update(votes_of(role))
            else:
                arrays["slots"] = np.asarray(
                    executed_slots[:len(arrays.get("keys", ()))],
                    dtype=np.int64)
            record["state"] = state_of(role)
        return record, arrays

    def dump_first_life(*_) -> None:
        for dump in dumps:
            dump()
        record, arrays = this_life()
        record["lives"] = 1
        record["life1"] = record.pop("state", {})
        for name in (prefix + ".json", prefix + ".replica.npz"):
            if os.path.exists(name):
                os.remove(name)
        if arrays:
            write_arrays(prefix + ".life1.replica.npz", arrays)
        write_json(prefix + ".life1.json", record)

    def finish() -> None:
        """At exit, after ``role_entry``'s dump (registered later, so run
        earlier). A first life that was never killed leaves one life; a
        second life leaves both."""
        record, arrays = this_life()
        if not second_life:
            record["lives"] = 1
            record["life1"] = record.pop("state", {})
        else:
            with open(prefix + ".life1.json") as f:
                first = json.load(f)
            votes = recovered.pop("votes", {})
            second = {key: record[key] for key in record
                      if key not in ("state", "storage", "kind")}
            second["recovered"] = recovered
            second["final"] = record.get("state", {})
            first.update(lives=2, life2=second)
            for name, extra in ((".cut.json", "unsynced_bytes_discarded"),
                                ("", "recovery")):
                path = (prefix + name if name
                        else os.path.join(record_dir, "recovery.json"))
                if os.path.exists(path):
                    with open(path) as f:
                        first[extra] = json.load(f)
            record = first
            both = load(prefix + ".life1.replica.npz")
            if kind == "acceptor":
                both.update({"recovered_" + name: array
                             for name, array in votes.items()})
            else:
                both.update({"recovered_" + name: arrays[name]
                             for name in ("keys", "values", "slots")
                             if name in arrays})
            arrays = both
        if arrays:
            write_arrays(prefix + ".replica.npz", arrays)
        write_json(prefix + ".json", record)

    register(finish)
    atexit.register = capturing_register
    signal.signal(DUMP_SIGNAL, dump_first_life)
    role_entry.main(argv, wrap_tracker=wrap_tracker, wrap_store=record_slots)


if __name__ == "__main__":
    main(sys.argv[1:])

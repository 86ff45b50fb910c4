"""The paxlog WAL core: framing, group commit, rotation, compaction,
torn-tail recovery, and the record codecs (docs/DURABILITY.md)."""

import struct

import pytest

from frankenpaxos_tpu.wal import (
    FileStorage,
    MemStorage,
    Wal,
    WalChosenRun,
    WalNoopRange,
    WalPromise,
    WalSnapshot,
    WalVote,
    WalVoteRun,
)
from frankenpaxos_tpu.wal.records import WAL_SERIALIZER

RECORDS = [
    WalPromise(round=3),
    WalVote(slot=7, round=1, value=b"\x00"),
    WalVoteRun(start_slot=10, stride=2, round=4, values=b"\x01\x02\x03"),
    WalNoopRange(slot_start_inclusive=5, slot_end_exclusive=95, round=2),
    WalChosenRun(start_slot=0, stride=1, values=b""),
    WalSnapshot(payload=b"snap-bytes"),
]


@pytest.mark.parametrize("record", RECORDS,
                         ids=lambda r: type(r).__name__)
def test_record_codecs_round_trip(record):
    data = WAL_SERIALIZER.to_bytes(record)
    assert WAL_SERIALIZER.from_bytes(data) == record


def test_record_codec_rejects_hostile_length():
    data = bytearray(WAL_SERIALIZER.to_bytes(
        WalVote(slot=1, round=0, value=b"xyzw")))
    # Layout: tag(1) + slot(8) + round(8) + len(4) + bytes.
    struct.pack_into("<i", data, 17, 1 << 30)
    with pytest.raises(ValueError):
        WAL_SERIALIZER.from_bytes(bytes(data))
    struct.pack_into("<i", data, 17, -5)
    with pytest.raises(ValueError):
        WAL_SERIALIZER.from_bytes(bytes(data))


def test_record_serializer_is_closed():
    """No pickle fallback in the record space: unknown tags and
    unregistered types refuse outright (recovery never executes
    code)."""
    with pytest.raises(ValueError):
        WAL_SERIALIZER.from_bytes(b"\x7f\x00\x00")
    with pytest.raises(ValueError):
        WAL_SERIALIZER.from_bytes(b"\x80\x04x")  # a pickle frame
    with pytest.raises(ValueError):
        WAL_SERIALIZER.to_bytes(object())


@pytest.mark.parametrize("kind", ["mem", "file"])
def test_append_sync_recover_round_trip(kind, tmp_path):
    root = str(tmp_path / "wal")
    storage = MemStorage() if kind == "mem" else FileStorage(root)
    wal = Wal(storage)
    for record in RECORDS:
        wal.append(record)
    wal.sync()
    assert wal.metrics.syncs == 1
    assert wal.metrics.records_synced == len(RECORDS)
    wal.close()

    wal2 = Wal(storage if kind == "mem" else FileStorage(root))
    assert wal2.recover() == RECORDS


def test_unsynced_records_die_with_the_actor():
    """The group-commit rule's crash contract: appended-but-unsynced
    records are NOT durable -- discarding the Wal object (the sim's
    crash) loses exactly them."""
    storage = MemStorage()
    wal = Wal(storage)
    wal.append(WalPromise(round=1))
    wal.sync()
    wal.append(WalPromise(round=2))  # staged, never synced
    # Crash: new Wal over the surviving storage.
    wal2 = Wal(storage)
    assert wal2.recover() == [WalPromise(round=1)]


def test_group_commit_amortizes_fsyncs():
    storage = MemStorage()
    wal = Wal(storage)
    for drain in range(5):
        for i in range(40):
            wal.append(WalVote(slot=drain * 40 + i, round=0, value=b"v"))
        wal.sync()
    assert wal.metrics.syncs == 5  # one fsync per drain, not per record
    assert storage.fsyncs == 5
    assert wal.metrics.records_synced == 200
    assert wal.metrics.bytes_per_sync() > 0


def test_torn_tail_truncated_and_idempotent(tmp_path):
    """A partial group commit at the tail (the crash shape) is
    truncated on recovery; records synced AFTER that recovery survive
    a second restart (recovery is idempotent)."""
    root = str(tmp_path / "wal")
    storage = FileStorage(root)
    wal = Wal(storage)
    wal.append(WalPromise(round=1))
    wal.append(WalVote(slot=0, round=1, value=b"a"))
    wal.sync()
    wal.close()
    # Tear: chop the last 3 bytes off the live segment.
    storage = FileStorage(root)
    name = storage.segments()[-1]
    data = storage.read(name)
    storage.truncate(name, len(data) - 3)
    storage.close()

    storage = FileStorage(root)
    wal2 = Wal(storage)
    assert wal2.recover() == [WalPromise(round=1)]
    assert wal2.metrics.truncated_tail_bytes > 0
    wal2.append(WalVote(slot=9, round=2, value=b"b"))
    wal2.sync()
    wal2.close()

    wal3 = Wal(FileStorage(root))
    assert wal3.recover() == [WalPromise(round=1),
                              WalVote(slot=9, round=2, value=b"b")]


def test_zero_filled_tail_truncates_cleanly():
    """Review-found: a zero-filled (extended-but-unwritten) tail
    parses as a 'valid' frame (len=0, crc=0, crc32(b'')==0); recovery
    must truncate it as torn, not crash the restarting role with an
    IndexError."""
    storage = MemStorage()
    wal = Wal(storage)
    wal.append(WalPromise(round=1))
    wal.sync()
    name = storage.segments()[0]
    storage.files[name].extend(b"\x00" * 64)
    wal2 = Wal(storage)
    assert wal2.recover() == [WalPromise(round=1)]
    assert wal2.metrics.truncated_tail_bytes == 64
    # Idempotent: a third restart sees a clean log.
    wal3 = Wal(storage)
    assert wal3.recover() == [WalPromise(round=1)]


def test_corrupt_crc_stops_replay():
    storage = MemStorage()
    wal = Wal(storage)
    wal.append(WalPromise(round=1))
    wal.append(WalPromise(round=2))
    wal.sync()
    name = storage.segments()[0]
    storage.files[name][10] ^= 0xFF  # flip a byte inside frame 1
    wal2 = Wal(storage)
    assert wal2.recover() == []  # replay stops at the corrupt frame


def test_segment_rotation_and_compaction():
    storage = MemStorage()
    wal = Wal(storage, segment_bytes=256)
    for i in range(50):
        wal.append(WalVote(slot=i, round=0, value=b"x" * 16))
        wal.sync()
    assert len(storage.segments()) > 1  # rotated past 256 bytes

    # Compaction: snapshot + re-logged live state replaces history.
    live = [WalVote(slot=49, round=0, value=b"x" * 16)]
    wal.compact(WalSnapshot(payload=b"S"), live)
    assert len(storage.segments()) == 1
    assert wal.metrics.compactions == 1
    assert wal.metrics.segments_deleted >= 1

    wal2 = Wal(storage)
    assert wal2.recover() == [WalSnapshot(payload=b"S")] + live


def test_compaction_crash_before_delete_is_safe():
    """A crash after writing the snapshot segment but before deleting
    old segments replays history THEN the snapshot: roles treat
    WalSnapshot as a reset point, so the prefix is harmless."""
    storage = MemStorage()
    wal = Wal(storage)
    wal.append(WalPromise(round=1))
    wal.sync()
    # Simulate the crash window: write the compact segment by hand.
    snap_wal = Wal(storage)
    snap_wal._seg_index = wal._seg_index + 1
    snap_wal._segment = f"seg-{snap_wal._seg_index:08d}.wal"
    snap_wal.append(WalSnapshot(payload=b"S"))
    snap_wal.append(WalPromise(round=5))
    snap_wal.sync()
    wal2 = Wal(storage)
    records = wal2.recover()
    # The snapshot marker appears AFTER the stale prefix; replay-side
    # reset-at-snapshot discards everything before it.
    assert records[-2:] == [WalSnapshot(payload=b"S"),
                            WalPromise(round=5)]


def test_wants_compaction_threshold():
    wal = Wal(MemStorage(), compact_every_bytes=128)
    assert not wal.wants_compaction()
    for i in range(20):
        wal.append(WalVote(slot=i, round=0, value=b"y" * 8))
    wal.sync()
    assert wal.wants_compaction()
    wal.compact(WalSnapshot(payload=b""), [])
    assert not wal.wants_compaction()


@pytest.mark.parametrize("kept, rewrite_bound", [
    (0.0, 1), (0.5, 1), (1.0, 2),
], ids=["nothing-kept", "half-kept", "everything-kept"])
def test_compaction_is_due_when_the_log_has_doubled(kept, rewrite_bound):
    """A compaction is due once the log has grown by what the last one
    wrote, and never before ``compact_every_bytes``. Over a long
    append-only history of a role whose compactions keep the newest
    ``kept`` share of what it has: the first comes at
    ``compact_every_bytes``; all compactions together write at most
    ``rewrite_bound`` times the bytes appended (under twice whatever
    is kept, and no more than once unless nearly all is); the disk
    never holds more than twice what the last one kept plus
    ``compact_every_bytes`` (plus the drain that crossed the line);
    and after ``recover()`` the next one waits for the recovered bytes
    again. A fixed threshold fails the first bound for
    everything-kept: it rewrites the whole history every
    ``compact_every_bytes``."""
    every = 4096
    storage = MemStorage()
    wal = Wal(storage, segment_bytes=1024, compact_every_bytes=every)
    live: list = []  # what a compaction of this role would re-log
    appended = 0  # bytes the role's own records took
    drain_bytes = 0  # the widest group commit
    rewritten = 0  # bytes the compactions wrote
    last_wrote = 0
    due_at = []  # (bytes appended since the last compaction, its size)

    def disk():
        return sum(len(data) for data in storage.files.values())

    def drain(slot):
        before = wal.metrics.bytes_synced
        for i in range(8):
            record = WalVote(slot=slot + i, round=0, value=b"v" * 24)
            wal.append(record)
            live.append(record)
        wal.sync()
        return wal.metrics.bytes_synced - before

    since = 0
    for n in range(600):
        wrote = drain(8 * n)
        appended += wrote
        since += wrote
        drain_bytes = max(drain_bytes, wrote)
        assert disk() <= 2 * last_wrote + every + drain_bytes
        if not wal.wants_compaction():
            continue
        due_at.append((since, last_wrote))
        del live[:len(live) - int(len(live) * kept)]
        before = wal.metrics.bytes_synced
        wal.compact(WalSnapshot(payload=b""), list(live))
        last_wrote = wal.metrics.bytes_synced - before
        rewritten += last_wrote
        since = 0
        assert disk() == last_wrote  # every older segment is gone
        assert not wal.wants_compaction()
        assert rewritten <= rewrite_bound * appended

    # The first comes at compact_every_bytes, each later one once the
    # log has grown by what the one before it wrote, and not a drain
    # later than that.
    assert due_at[0][1] == 0
    for since, wrote in due_at:
        assert max(every, wrote) <= since < max(every, wrote) + drain_bytes
    assert wal.metrics.compactions == len(due_at)
    if kept == 0.0:
        # One marker a time: every compact_every_bytes (rounded up
        # to whole drains), as under the fixed threshold.
        assert all(wrote < 64 for _, wrote in due_at)
        assert len({since for since, _ in due_at}) == 1
        assert len(due_at) == appended // due_at[0][0]
    if kept == 1.0:
        # At 1x, 2x, 4x ... compact_every_bytes: six in ~250 KB, where
        # a fixed threshold made sixty.
        assert len(due_at) == 6
        sizes = [wrote for _, wrote in due_at[1:]]
        assert all(b >= 2 * a - 64 for a, b in zip(sizes, sizes[1:]))

    # A restart: the recovered log is what the next compaction would
    # rewrite, so it waits for that much growth again.
    recovered = disk()
    wal2 = Wal(storage, segment_bytes=1024, compact_every_bytes=every)
    assert len(wal2.recover()) > 0
    assert not wal2.wants_compaction()
    wal, since = wal2, 0
    while not wal.wants_compaction():
        since += drain(10 ** 6 + since)
    assert max(every, recovered) <= since \
        < max(every, recovered) + drain_bytes


# --- paxchaos: FsyncStallStorage over REAL FileStorage on disk ---------------


def test_fsync_stall_over_file_storage_blocking(tmp_path):
    """The deployed fault arm (satellite of paxchaos): a BLOCKING
    FsyncStallStorage over a real FileStorage actually sleeps through
    its count-cadence stalls, and every synced record is durable on
    disk afterwards."""
    import time

    from frankenpaxos_tpu.wal import FsyncStallStorage

    root = str(tmp_path / "wal")
    storage = FsyncStallStorage(
        FileStorage(root), seed=7, label="a0", stall_every=2,
        stall_s=0.02, jitter=0.0, blocking=True)
    wal = Wal(storage)
    t0 = time.perf_counter()
    for i in range(4):
        wal.append(WalVote(slot=i, round=1, value=b"v%d" % i))
        wal.sync()
    elapsed = time.perf_counter() - t0
    assert len(storage.stalls) == 2
    assert elapsed >= sum(storage.stalls)  # the sleeps were real
    wal.close()
    recovered = Wal(FileStorage(root)).recover()
    assert recovered == [WalVote(slot=i, round=1, value=b"v%d" % i)
                         for i in range(4)]


def test_fsync_stall_periodic_windows_align_on_shared_clock(tmp_path):
    """Periodic-window mode: two storages sharing one clock stall in
    the SAME windows (the property that makes deployed overlap faults
    reproducible), and outside a window no stall fires."""
    from frankenpaxos_tpu.wal import FsyncStallStorage

    now = {"t": 0.0}
    clock = lambda: now["t"]  # noqa: E731
    storages = [
        FsyncStallStorage(FileStorage(str(tmp_path / f"w{i}")),
                          label=f"a{i}", stall_period_s=1.0,
                          stall_window_s=0.1, clock=clock)
        for i in range(2)]
    for t, expect_stall in ((0.05, True), (0.5, False),
                            (1.02, True), (1.9, False)):
        now["t"] = t
        for storage in storages:
            before = len(storage.stalls)
            storage.append("seg-00000000.wal", b"x")
            storage.sync("seg-00000000.wal")
            assert (len(storage.stalls) > before) == expect_stall, t
    # Both stalled at exactly the same instants, to the window end.
    assert storages[0].stalls == storages[1].stalls
    assert storages[0].stalls[0] == pytest.approx(0.05)


def test_torn_tail_recovery_with_stall_in_flight(tmp_path):
    """Crash DURING a stall (satellite 3's torn-tail case): the stall
    fires after the real fsync, so records of the stalled group
    commit are durable -- a crash mid-stall loses nothing synced, and
    a torn tail appended by the dying process truncates away on
    recovery over the SAME wrapped storage."""
    from frankenpaxos_tpu.wal import FsyncStallStorage

    root = str(tmp_path / "wal")
    crashed = {}

    def crash_mid_stall(stall_s):
        # The "crash": capture the on-disk state AT the stall (fsync
        # done, ack held, process about to die).
        crashed["segments"] = FileStorage(root).segments()

    storage = FsyncStallStorage(
        FileStorage(root), seed=1, label="a0", stall_every=2,
        stall_s=0.001, on_stall=crash_mid_stall)
    wal = Wal(storage)
    wal.append(WalPromise(round=1))
    wal.sync()            # sync 1: no stall
    wal.append(WalVote(slot=1, round=1, value=b"durable"))
    wal.sync()            # sync 2: stall fires -- the "crash" point
    assert crashed["segments"]  # the record was already on disk
    # The dying process had staged (unsynced) records AND a torn
    # half-frame reached the file (the kill landed mid-write).
    wal.append(WalVote(slot=2, round=1, value=b"lost-with-buffer"))
    name = storage.segments()[-1]
    storage.append(name, b"\xff\xff\xff")  # torn garbage, no sync
    storage.close()

    # Recovery over a FRESH wrapped FileStorage (the relaunch keeps
    # its fault arming, as the deployed launch spec does).
    storage2 = FsyncStallStorage(
        FileStorage(root), seed=1, label="a0", stall_every=2,
        stall_s=0.001)
    wal2 = Wal(storage2)
    records = wal2.recover()
    assert records == [WalPromise(round=1),
                       WalVote(slot=1, round=1, value=b"durable")]
    assert wal2.metrics.truncated_tail_bytes == 3
    # Post-recovery appends survive another restart (idempotent), and
    # the wrapper keeps injecting on the recovered log.
    wal2.append(WalVote(slot=3, round=2, value=b"after"))
    wal2.sync()
    wal2.sync_count_before = storage2.syncs
    wal2.close()
    final = Wal(FileStorage(root)).recover()
    assert final == [WalPromise(round=1),
                     WalVote(slot=1, round=1, value=b"durable"),
                     WalVote(slot=3, round=2, value=b"after")]

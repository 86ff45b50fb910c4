"""Pluggable Phase2b write-quorum tracking: host dict or TPU vote board.

The ProxyLeader's vote-collection loop (ProxyLeader.scala:217-258) is the
hottest code in the reference. Here it is a strategy interface with two
implementations:

  * ``DictQuorumTracker`` -- the reference's semantics verbatim: a dict
    keyed (slot, round) accumulating (group, acceptor) votes. The oracle.
  * ``TpuQuorumTracker`` -- votes buffered per event-loop drain, then
    dispatched to ``TpuQuorumChecker``'s vote board on the device (one
    ``record_block`` a drain as a rule: ``BoardDrainPlanner``, which
    ``reconfig.EpochQuorumTracker`` plans its drains with too) and
    collected off the loop.
    Acceptor coordinates flatten to columns ``group * group_size + index``.
    In non-flexible mode only a slot's own group is ever messaged, so a
    universe-wide count >= f+1 threshold is exactly the per-group f+1
    quorum; in flexible mode the grid write-spec applies.

Both report each (slot, round)'s quorum exactly once.
"""

from __future__ import annotations

import abc

import numpy as np

from frankenpaxos_tpu.protocols.multipaxos.config import MultiPaxosConfig
from frankenpaxos_tpu.quorums import QuorumSpec
from frankenpaxos_tpu.quorums.spec import ANY


class QuorumTracker(abc.ABC):
    """Tracks Phase2b votes; reports slots whose quorum completes."""

    @abc.abstractmethod
    def record(self, slot: int, round: int, group_index: int,
               acceptor_index: int) -> None:
        ...

    def record_range(self, slot_start: int, slot_end: int, round: int,
                     group_index: int, acceptor_index: int) -> None:
        """One acceptor's votes for slots [slot_start, slot_end) in one
        round (a Phase2bRange). Default: per-slot expansion."""
        for slot in range(slot_start, slot_end):
            self.record(slot, round, group_index, acceptor_index)

    def record_votes(self, slots, rounds, group_index: int,
                     acceptor_index: int) -> None:
        """One acceptor's votes for an ARBITRARY slot array (a packed
        Phase2bVotes from a fragmented drain). Default: per-slot
        expansion."""
        for slot, round in zip(slots.tolist(), rounds.tolist()):
            self.record(int(slot), int(round), group_index,
                        acceptor_index)

    def has_votes(self) -> bool:
        """Would :meth:`drain` have work? A drain without is neither
        called nor timed (stage ``drain``). Default: always."""
        return True

    @abc.abstractmethod
    def drain(self) -> list[tuple[int, int]]:
        """Flush buffered votes; return [(slot, round)] newly at quorum."""


class DictQuorumTracker(QuorumTracker):
    def __init__(self, config: MultiPaxosConfig):
        self.config = config
        self.grid = config.quorum_grid() if config.flexible else None
        self._row_size = len(config.acceptor_addresses[0])
        # (slot, round) -> set of (group, index); None once chosen.
        self.states: dict[tuple[int, int], set | None] = {}
        self._newly: list[tuple[int, int]] = []

    def record(self, slot, round, group_index, acceptor_index) -> None:
        key = (slot, round)
        votes = self.states.get(key)
        if votes is None and key in self.states:
            return  # already chosen (Done)
        if votes is None:
            votes = set()
            self.states[key] = votes
        votes.add((group_index, acceptor_index))
        if self.config.flexible:
            flat = {g * self._row_size + i for g, i in votes}
            if not self.grid.is_superset_of_write_quorum(flat):
                return
        else:
            if len(votes) < self.config.f + 1:
                return
        self.states[key] = None  # Done
        self._newly.append(key)

    def has_votes(self) -> bool:
        return bool(self._newly)

    def drain(self) -> list[tuple[int, int]]:
        newly, self._newly = self._newly, []
        return newly


def expand_votes(slots: list, cols: list, rounds: list, ranges: list,
                 array_votes: list) -> tuple:
    """A drain's three vote buffers (single votes as three lists, ranges
    ``(start, end, col, round)``, packed arrays ``(slots, col, rounds)``)
    as three arrays, one entry a vote, in that order. Vectorized: the
    whole point of Phase2bRange / Phase2bVotes is no per-slot Python
    before the device call."""
    slots = np.asarray(slots, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int32)
    rounds = np.asarray(rounds, dtype=np.int32)
    if not (ranges or array_votes):
        return slots, cols, rounds
    parts_s = [slots] if slots.size else []
    parts_c = [cols] if slots.size else []
    parts_r = [rounds] if slots.size else []
    for start, end, col, rnd in ranges:
        width = end - start
        parts_s.append(np.arange(start, end, dtype=np.int64))
        parts_c.append(np.full(width, col, dtype=np.int32))
        parts_r.append(np.full(width, rnd, dtype=np.int32))
    for s_arr, col, r_arr in array_votes:
        parts_s.append(s_arr)
        parts_c.append(np.full(s_arr.size, col, dtype=np.int32))
        parts_r.append(r_arr)
    return (np.concatenate(parts_s), np.concatenate(parts_c),
            np.concatenate(parts_r))


class BoardDrainPlanner:
    """How a drain's votes reach a vote board, and what its device calls
    chose: the ONE plan of ``TpuQuorumTracker`` and of
    ``reconfig.EpochQuorumTracker``'s ``tpu`` backend, over whichever
    board checker it is handed (``ops.quorum.TpuQuorumChecker`` judges
    every column by one predicate, ``EpochSegmentedChecker`` each by its
    slot's epoch; the plan does not know which).

    :meth:`dispatch` turns a drain's buffered votes into asynchronous
    device calls, one dense ``record_block`` as a rule:
    votes in rounds OLDER than the dominant round go first (scattered),
    so that an old-round quorum completing in this drain is reported
    before the newer round's preemption clears it; the dominant round's
    slots are clustered into contiguous runs and chunked at the
    prewarmed bucket widths; what is too thin for a block, and newer
    rounds, take the scatter in chunks of ``max_chunk``. It returns the
    calls' ``parts``; :meth:`fetch` waits for them and reports each
    (slot, round) exactly once.

    ``votes`` counts the votes dispatched, ``launches`` the jitted
    calls they made, dense or sparse, and ``dense_votes`` the votes
    that reached the device in a dense block (a block that straddles
    the ring end counts its cells)."""

    def __init__(self, window: int):
        self.votes = 0
        self.launches = 0
        self.dense_votes = 0
        # Exactly-once reporting, vectorized. Each device call answers
        # with what IT newly chose, and one drain can be several calls
        # (an older round's scatter before the dense block, a ring
        # straddle, a sparse tail) whose votes may name one slot twice.
        # collect() passes every part's hits through this host-side
        # ring keyed slot % window (owner slot + round per column,
        # numpy fancy-indexed), so a (slot, round) is reported once
        # across parts, drains and re-acks, first round first as the
        # dict oracle does, with O(batch) numpy instead of per-slot set
        # ops. The board's `chosen` plane guards the same on the
        # device; whether it alone would do is unproven (ROADMAP.md
        # D3). Like the vote board itself the ring forgets a slot once
        # it wraps past it -- covered by the same "window > max slots
        # in flight" invariant.
        self._dedup_slot = np.full(window, -1, dtype=np.int64)
        self._dedup_round = np.full(window, np.iinfo(np.int64).min,
                                    dtype=np.int64)
        # Kernel width buckets. Drains are chunked to these so ONLY the
        # prewarmed widths ever compile -- an unexpected width compiling
        # mid-run stalls the event loop for seconds. Dense buckets go
        # wide (a contiguous 4k-slot run is one slice+matmul call); the
        # sparse scatter tail stays narrow.
        self.max_chunk = 256
        self.dense_buckets = tuple(
            b for b in (64, 256, 1024, 4096) if b <= window)
        if not self.dense_buckets:
            raise ValueError(f"window must be >= 64 (got {window}): the "
                             f"smallest prewarmed dense kernel bucket is "
                             f"64 columns")
        self.max_dense = self.dense_buckets[-1]
        # A dominant-round cluster goes dense when it's at least this
        # filled; emptier clusters cost fewer device calls via scatter.
        self.min_fill = 0.25

    def prewarm(self, checker) -> None:
        """Compile every width a plan can launch on ``checker``'s board
        (the dense buckets and the scatter's two) -- at construction,
        before client traffic -- so that no drain stalls on an XLA
        compile. Prewarm votes land at round -1 (below any real round),
        and release() clears the touched columns (including the ring
        owners the prewarm claimed)."""
        for width in self.dense_buckets:
            warm = np.zeros((checker.num_nodes, width), dtype=np.uint8)
            warm[0, 0] = 1
            checker.record_block(0, warm, vote_round=-1)
        for width in (1, self.max_chunk):
            checker.record_and_check([0] * width, [0] * width,
                                     [-1] * width)
        checker.release(np.arange(self.max_dense))

    def dispatch(self, checker, slots: list, cols: list, rounds: list,
                 ranges: list, array_votes: list) -> list:
        """Launch a drain's votes (a tracker's three buffers, as
        :func:`expand_votes` takes them, at least one vote in all) onto
        ``checker``'s board without waiting; the calls' parts, for
        :meth:`fetch`."""
        slots, cols, rounds = expand_votes(slots, cols, rounds, ranges,
                                           array_votes)
        self.votes += slots.shape[0]
        parts: list[tuple] = []
        # The drain's dominant round (fast path: single-round drain).
        if rounds[0] == rounds[-1] and (rounds == rounds[0]).all():
            dom = int(rounds[0])
            # Single-round drain within one dense bucket: one block.
            lo = int(slots.min())
            hi = int(slots.max())
            width = hi - lo + 1
            bucket = next((b for b in self.dense_buckets if b >= width),
                          None) if width <= self.max_dense else None
            if (bucket is not None
                    and slots.shape[0] >= width * self.min_fill):
                block = np.zeros((checker.num_nodes, bucket),
                                 dtype=np.uint8)
                block[cols, slots - lo] = 1
                self._record_board(checker, parts, lo, block, bucket,
                                   dom, slots.shape[0])
                return parts
            dense_idx = np.arange(slots.shape[0])
            pre = post = None
        else:
            round_values, round_counts = np.unique(rounds,
                                                   return_counts=True)
            dom = int(round_values[np.argmax(round_counts)])
            dense_idx = np.flatnonzero(rounds == dom)
            pre = np.flatnonzero(rounds < dom)
            post = np.flatnonzero(rounds > dom)
        if pre is not None and pre.size:
            self._dispatch_sparse(checker, parts, slots, cols, rounds, pre)

        # Cluster the dominant round's slots into contiguous runs.
        ds = slots[dense_idx]
        if ds.size and np.all(ds[:-1] <= ds[1:]):  # arrival order is
            sidx = dense_idx                       # already slot-sorted
            ss = ds
        else:
            order = np.argsort(ds, kind="stable")
            sidx = dense_idx[order]
            ss = ds[order]
        sparse_leftover = []
        cluster_bounds = np.flatnonzero(np.diff(ss) >= self.max_dense) + 1
        for cluster in np.split(np.arange(sidx.size), cluster_bounds):
            cl = sidx[cluster]
            cs = ss[cluster]
            hi = int(cs[-1])
            width = hi - int(cs[0]) + 1
            if cl.size < width * self.min_fill:
                sparse_leftover.append(cl)
                continue
            # Chunk the run at prewarmed bucket widths. Each chunk
            # starts at an actual member slot, so the loop is
            # O(#chunks).
            i = 0
            while i < cs.size:
                start = int(cs[i])
                remaining = hi - start + 1
                bucket = next((b for b in self.dense_buckets
                               if b >= min(remaining, self.max_dense)))
                j = int(np.searchsorted(cs, start + bucket))
                members = cl[i:j]
                block = np.zeros(
                    (checker.num_nodes, bucket), dtype=np.uint8)
                block[cols[members], slots[members] - start] = 1
                self._record_board(checker, parts, start, block, bucket,
                                   dom, members.size)
                i = j

        for cl in sparse_leftover:
            self._dispatch_sparse(checker, parts, slots, cols, rounds, cl)
        if post is not None and post.size:
            self._dispatch_sparse(checker, parts, slots, cols, rounds,
                                  post)
        return parts

    def _record_board(self, checker, parts: list, start: int,
                      block: np.ndarray, bucket: int, rnd: int,
                      votes: int) -> None:
        """Record a dense run of ``votes`` votes on the vote board,
        splitting at the ring end (record_block's no-straddle
        contract)."""
        room = checker.window - start % checker.window
        if bucket <= room:
            newly = checker.record_block_async(start, block,
                                               vote_round=rnd)
            self.launches += 1
            self.dense_votes += votes
            parts.append(("block", start, bucket, rnd, newly))
        else:
            self._record_board_split(checker, parts, start, block, room,
                                     rnd)

    def _record_board_split(self, checker, parts: list, start: int,
                            block: np.ndarray, room: int,
                            rnd: int) -> None:
        """Record a block that straddles the ring end WITHOUT compiling
        any new kernel width: each piece is decomposed into prewarmed
        bucket widths, and sub-bucket remainders take the (prewarmed)
        scatter path. A mid-run XLA compile would stall the event loop
        for seconds."""
        self._record_board_bucketed(checker, parts, start,
                                    block[:, :room], rnd)
        rest = block[:, room:]
        if rest.any():
            self._record_board_bucketed(checker, parts, start + room,
                                        np.ascontiguousarray(rest), rnd)

    def _record_board_bucketed(self, checker, parts: list, start: int,
                               block: np.ndarray, rnd: int) -> None:
        width = block.shape[1]
        i = 0
        while i < width:
            bucket = next((b for b in reversed(self.dense_buckets)
                           if b <= width - i), None)
            if bucket is None:
                # Remainder narrower than the smallest bucket: scatter.
                rows, pos = np.nonzero(block[:, i:])
                if rows.size:
                    self._dispatch_sparse(
                        checker, parts,
                        (start + i + pos).astype(np.int64),
                        rows.astype(np.int32),
                        np.full(rows.size, rnd, dtype=np.int32),
                        np.arange(rows.size))
                return
            sub = block[:, i:i + bucket]
            if sub.any():
                newly = checker.record_block_async(
                    start + i, np.ascontiguousarray(sub), vote_round=rnd)
                self.launches += 1
                self.dense_votes += int(sub.sum())
                parts.append(("block", start + i, bucket, rnd, newly))
            i += bucket

    def _dispatch_sparse(self, checker, parts, slots, cols, rounds,
                         idx) -> None:
        """Scatter-path dispatch, chunked so only prewarmed widths run."""
        for at in range(0, idx.size, self.max_chunk):
            chunk = idx[at:at + self.max_chunk]
            self.launches += 1
            parts.append(("votes", slots[chunk], rounds[chunk],
                          checker.record_and_check_async(
                              slots[chunk], cols[chunk], rounds[chunk],
                              pad_to=(64 if chunk.size <= 64
                                      else self.max_chunk)),
                          chunk.size))

    def fetch(self, parts) -> list[tuple[int, int]]:
        """Fetch a dispatch's results (blocking on the device for any
        part not done yet) and dedup per slot, keeping each slot's
        first reporting round in part order (as the dict oracle's
        arrival-order reporting does).

        Parts come in two shapes: ``("block", start, width, round,
        device_mask)`` -- a per-slot newly-chosen mask from the board;
        ``("votes", slots, rounds, device_mask, n)`` -- a per-vote mask
        from the scatter path."""
        out: list[tuple[int, int]] = []
        for part in parts:
            kind = part[0]
            if kind == "block":
                _, start, width, rnd, mask = part
                m = np.asarray(mask)[:width]
                slots = start + np.flatnonzero(m).astype(np.int64)
                if slots.size:
                    fresh = self._fresh_mask(slots, rnd)
                    out.extend(zip(slots[fresh].tolist(),
                                   (rnd,) * int(fresh.sum())))
            else:  # "votes"
                _, vslots, vrounds, mask, n = part
                m = np.asarray(mask)[:n]
                hit = np.flatnonzero(m)
                if hit.size:
                    # Dedup duplicate slots within the part (keep the
                    # first, as the per-vote mask reports per vote).
                    hslots = np.asarray(vslots, dtype=np.int64)[hit]
                    _, first = np.unique(hslots, return_index=True)
                    sel = hit[np.sort(first)]
                    slots = np.asarray(vslots, dtype=np.int64)[sel]
                    rounds = np.asarray(vrounds, dtype=np.int64)[sel]
                    fresh = self._fresh_mask(slots, rounds)
                    out.extend(zip(slots[fresh].tolist(),
                                   rounds[fresh].tolist()))
        return out

    def _fresh_mask(self, slots: np.ndarray, rounds) -> np.ndarray:
        """Vectorized exactly-once filter: True where (slot, round) has
        not been reported before (within the dedup ring's memory);
        marks the fresh ones reported. ``slots`` must be unique within
        the call."""
        idx = slots % self._dedup_slot.shape[0]
        dup = (self._dedup_slot[idx] == slots) \
            & (self._dedup_round[idx] == rounds)
        fresh = ~dup
        fi = idx[fresh]
        self._dedup_slot[fi] = slots[fresh]
        self._dedup_round[fi] = np.asarray(rounds)[fresh] \
            if isinstance(rounds, np.ndarray) else rounds
        return fresh


class TpuQuorumTracker(QuorumTracker):
    """Every vote goes to the stateful vote board on the device.

    A drain DISPATCHES its votes asynchronously (:class:`BoardDrainPlanner`:
    ``record_block`` for dense runs, the scatter for stragglers),
    returns [] and enqueues an in-flight record; the caller collects
    completed dispatches via :meth:`take_dispatch` + :meth:`collect` --
    from a worker thread (ProxyLeader posts results back onto the event
    loop) or a flush timer. This overlaps the device->host fetch of one
    drain's result with the decode of the next drain's messages, at the
    cost of one dispatch of added choose latency; the board must see
    every vote because results are not available within the drain.

    The tracker counts its work: ``device_drains`` and the
    ``device_votes`` they carried, and ``device_launches``, the jitted
    calls those drains made, dense or sparse: one a drain is the common
    case, and more says what splits drains (a ring straddle, several
    rounds, a sparse tail)."""

    # Read by benchmark/harness/role_entry.py::TRACKER_COUNTERS, which
    # still names the host tally that is gone: they can only read 0
    # (ROADMAP.md M6 drops them there, then here).
    host_drains = 0
    host_votes = 0
    spilled_votes = 0

    def __init__(self, config: MultiPaxosConfig, window: int = 1 << 20,
                 mesh=None):
        import collections

        self.config = config
        self.device_drains = 0
        # In-flight dispatches: each the parts of one drain's plan.
        # append/popleft are GIL-atomic, so a collector thread may pop
        # while the event loop appends.
        self._inflight = collections.deque()
        self._row_size = len(config.acceptor_addresses[0])
        num_cols = config.num_acceptor_groups * self._row_size
        universe = tuple(range(num_cols))
        if config.flexible:
            spec = config.quorum_grid().write_spec().reindexed(universe)
        else:
            spec = QuorumSpec(
                masks=np.ones((1, num_cols), dtype=np.uint8),
                thresholds=np.array([config.f + 1], dtype=np.int32),
                combine=ANY,
                universe=universe,
            )
        self._planner = BoardDrainPlanner(window)
        # Lazy: keeps jax out of dict-backend role processes entirely
        # (it costs seconds of startup per process).
        from frankenpaxos_tpu.ops.quorum import TpuQuorumChecker

        self.checker = TpuQuorumChecker(spec, window=window, mesh=mesh)
        self._slots: list[int] = []
        self._cols: list[int] = []
        self._rounds: list[int] = []
        # Ranged votes (Phase2bRange): [(start, end, col, round)] --
        # O(1) Python per message, expanded vectorized at drain time.
        self._ranges: list[tuple[int, int, int, int]] = []
        # Packed array votes (Phase2bVotes): [(slots, col, rounds)] --
        # O(1) Python per message, arrays straight off the native
        # codec's unpack.
        self._array_votes: list = []
        self._planner.prewarm(self.checker)

    @property
    def device_votes(self) -> int:
        return self._planner.votes

    @property
    def device_launches(self) -> int:
        return self._planner.launches

    def record(self, slot, round, group_index, acceptor_index) -> None:
        self._slots.append(slot)
        self._cols.append(group_index * self._row_size + acceptor_index)
        self._rounds.append(round)

    def record_range(self, slot_start, slot_end, round, group_index,
                     acceptor_index) -> None:
        if slot_end <= slot_start:
            # Drop empties like record_votes does.
            return
        self._ranges.append((slot_start, slot_end,
                             group_index * self._row_size
                             + acceptor_index, round))

    def record_votes(self, slots, rounds, group_index,
                     acceptor_index) -> None:
        slots = np.asarray(slots, dtype=np.int64)
        if not slots.size:
            # Drop empties at the door: every drain path assumes
            # non-empty entries (round scans, frontier max, rounds[0]).
            return
        self._array_votes.append(
            (slots, group_index * self._row_size + acceptor_index,
             np.asarray(rounds, dtype=np.int32)))

    def has_votes(self) -> bool:
        return bool(self._slots or self._ranges or self._array_votes)

    def drain(self) -> list[tuple[int, int]]:
        """Dispatch this drain's votes onto the stateful vote board
        asynchronously (usually one device call); results are collected
        later (take_dispatch + collect), so this returns []."""
        if not self.has_votes():
            return []
        self.device_drains += 1
        self._inflight.append(self._planner.dispatch(
            self.checker, self._slots, self._cols, self._rounds,
            self._ranges, self._array_votes))
        self._slots, self._cols, self._rounds = [], [], []
        self._ranges = []
        self._array_votes = []
        return []

    def has_pending(self) -> bool:
        return bool(self._inflight)

    def take_dispatch(self):
        """Pop the oldest in-flight dispatch (None if empty); pass it to
        :meth:`collect`. Safe to call from a collector thread."""
        try:
            return self._inflight.popleft()
        except IndexError:
            return None

    def collect(self, dispatch) -> list[tuple[int, int]]:
        """Fetch a dispatch's results and report each (slot, round)
        once (:meth:`BoardDrainPlanner.fetch`)."""
        return self._planner.fetch(dispatch)

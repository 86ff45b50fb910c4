"""Epoch-segmented write-quorum tracking for reconfig-wired proxies.

The reconfig twin of ``protocols.multipaxos.quorum_tracker``: votes are
recorded by VOTER ADDRESS (the transport's ``src`` -- carried indices
can collide across epochs when a replacement reuses a dead member's
config slot, addresses cannot), and each slot's quorum predicate is its
EPOCH's spec, resolved through the ``EpochStore``. Two backends:

  * ``dict`` -- the oracle: per-(slot, round) voter sets checked with
    ``EpochConfig.has_write_quorum`` (set intersection, the reference
    semantics). Counts only the slot's epoch's members.
  * ``tpu`` -- an ``ops.quorum.EpochSegmentedChecker`` board over the
    store's union universe, fed as ``TpuQuorumTracker`` feeds its own:
    the same three buffers (O(1) Python a message) and the same plan of
    a drain (``quorum_tracker.BoardDrainPlanner``: one dense block a
    drain as a rule). The epoch plane is selected per slot INSIDE the
    kernel, so a drain spanning any number of handover boundaries
    stays one dispatch. Non-member votes land in rows the epoch's mask
    zeroes -- they can never complete a quorum they do not belong to.
    What is this backend's own: voter address to row through the
    store, the plane stack following the store, the adopted board. A
    drain's answer is fetched inside :meth:`drain` and returned by it.

Both report each (slot, round)'s quorum exactly once (the dict's Done
sentinel; the board's chosen bitmap).
"""

from __future__ import annotations

import numpy as np

from frankenpaxos_tpu.reconfig.epoch import EpochStore


class EpochQuorumTracker:
    def __init__(self, store: EpochStore, backend: str = "dict",
                 window: int = 4096):
        if backend not in ("dict", "tpu"):
            raise ValueError(f"unknown epoch tracker backend {backend!r}")
        self.store = store
        self.backend = backend
        self._known = store.known()
        # dict backend: (slot, round) -> set of voter addresses; None
        # once reported (Done).
        self._states: dict = {}
        self._newly: list = []
        # tpu backend: TpuQuorumTracker's three per-drain buffers (single
        # votes, ranges (start, end, row, round), packed arrays (slots,
        # row, rounds)), the drain planner and the segmented checker.
        self._checker = None
        self._planner = None
        self._slots: list = []
        self._cols: list = []
        self._rounds: list = []
        self._ranges: list = []
        self._array_votes: list = []
        if backend == "tpu":
            from frankenpaxos_tpu.ops.quorum import EpochSegmentedChecker
            from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker \
                import BoardDrainPlanner

            specs, starts = store.specs_and_boundaries()
            self._planner = BoardDrainPlanner(window)
            self._checker = EpochSegmentedChecker(specs, starts,
                                                  window=window)
            # Every width a drain can launch, before this tracker's
            # first votes: no drain and no reconfiguration compiles.
            self._planner.prewarm(self._checker)

    # The tpu backend's work counts (ProxyLeader publishes them): votes
    # handed to drain(), the jitted calls they made, dense or sparse,
    # and the votes that reached the device in a dense block.
    @property
    def votes(self) -> int:
        return self._planner.votes if self._planner else 0

    @property
    def launches(self) -> int:
        return self._planner.launches if self._planner else 0

    @property
    def dense_votes(self) -> int:
        return self._planner.dense_votes if self._planner else 0

    def note_epochs(self) -> None:
        """Refresh after the store committed new epochs. Pure appends
        extend the TPU checker's plane stack in place (the epoch
        reshape gather keeps mid-flight votes); a round-superseded
        newest epoch (rare: a preempted leader's unactivated
        definition) rebuilds the checker -- in-flight quorums for that
        never-activated epoch are resolved by protocol-level resends."""
        known = self.store.known()
        if known == self._known:
            return
        if self._checker is not None:
            if known[:len(self._known)] == self._known:
                for config in known[len(self._known):]:
                    self._checker.add_epoch(self.store.spec(config),
                                            config.start_slot)
            else:
                from frankenpaxos_tpu.ops.quorum import (
                    EpochSegmentedChecker,
                )

                specs, starts = self.store.specs_and_boundaries()
                self._checker = EpochSegmentedChecker(
                    specs, starts, window=self._checker.window)
                # A replacement REBUILDS the universe ids: buffered
                # votes' column ids were computed under the old
                # mapping and would credit the wrong acceptor on the
                # new board (a quorum one real vote short). Drop them
                # -- they voted for the superseded definition's
                # proposals, which protocol-level resends re-drive.
                self._drop_buffered()
        self._known = known

    @property
    def planes(self) -> int:
        """K: the epochs whose predicate planes a check reads."""
        return len(self._known)

    def adopt_board(self, checker) -> None:
        """Take over the live single-epoch board of ``checker`` (a
        ``TpuQuorumChecker`` over the epoch-0 members in config order,
        which are this store's first universe ids): votes it holds
        for slots still collecting keep counting here. The caller has
        dispatched every vote it meant for ``checker`` and never uses
        it again (its buffers are donated to this tracker's calls)."""
        self._checker.adopt(checker)

    def _drop_buffered(self) -> None:
        self._slots, self._cols, self._rounds = [], [], []
        self._ranges, self._array_votes = [], []

    # --- recording (per message, O(1) Python) ------------------------------
    def record(self, slot: int, round: int, voter) -> None:
        if self.backend == "dict":
            self._record_dict(slot, round, voter)
            return
        col = self.store.column_of(voter)
        if col is None:
            return  # never a member of any epoch: nothing to count
        self._slots.append(slot)
        self._cols.append(col)
        self._rounds.append(round)

    def record_range(self, slot_start: int, slot_end: int, round: int,
                     voter) -> None:
        if self.backend == "dict":
            for slot in range(slot_start, slot_end):
                self._record_dict(slot, round, voter)
            return
        col = self.store.column_of(voter)
        if col is None or slot_end <= slot_start:
            return
        self._ranges.append((slot_start, slot_end, col, round))

    def record_votes(self, slots, rounds, voter) -> None:
        """One voter's votes for an arbitrary slot array (a packed
        Phase2bVotes)."""
        if self.backend == "dict":
            for slot, round in zip(np.asarray(slots).tolist(),
                                   np.asarray(rounds).tolist()):
                self._record_dict(int(slot), int(round), voter)
            return
        col = self.store.column_of(voter)
        slots = np.asarray(slots, dtype=np.int64)
        if col is None or not slots.size:
            return
        self._array_votes.append(
            (slots, col, np.asarray(rounds, dtype=np.int32)))

    def _record_dict(self, slot: int, round: int, voter) -> None:
        key = (slot, round)
        votes = self._states.get(key)
        if votes is None and key in self._states:
            return  # Done
        if votes is None:
            votes = set()
            self._states[key] = votes
        votes.add(voter)
        config = self.store.epoch_of_slot(slot)
        if voter not in config.members:
            return  # not this epoch's vote; kept only for debugging
        if config.has_write_quorum(votes):
            self._states[key] = None
            self._newly.append(key)

    # --- drain -------------------------------------------------------------
    def has_votes(self) -> bool:
        """Would :meth:`drain` have work (QuorumTracker.has_votes)?"""
        if self.backend == "dict":
            return bool(self._newly)
        return bool(self._slots or self._ranges or self._array_votes)

    def drain(self) -> list:
        """What this drain's votes chose, each (slot, round) once. The
        ``tpu`` backend dispatches every part of the drain's plan, then
        fetches them in order."""
        if self.backend == "dict":
            newly, self._newly = self._newly, []
            return newly
        if not self.has_votes():
            return []
        parts = self._planner.dispatch(
            self._checker, self._slots, self._cols, self._rounds,
            self._ranges, self._array_votes)
        self._drop_buffered()
        return self._planner.fetch(parts)

    def release(self, slots) -> None:
        """Watermark GC passthrough (ring wrap for the tpu board)."""
        if self._checker is not None and len(slots):
            self._checker.release(np.asarray(slots))

"""MultiPaxos ProxyLeader.

Reference behavior: multipaxos/ProxyLeader.scala:67-259. On Phase2a: fan
the message to a write quorum (thrifty f+1 of the slot's acceptor group,
or a random grid write quorum in flexible mode) and remember the value.
On Phase2b: collect votes per (slot, round) until quorum -- THE hot loop
-- then broadcast Chosen to every replica.

The vote-collection loop is delegated to a
:class:`~frankenpaxos_tpu.protocols.multipaxos.quorum_tracker.QuorumTracker`:
the host-dict oracle or the TPU vote board flushed once per transport
drain (``on_drain``).
"""

from __future__ import annotations

import bisect
import dataclasses
import random

import numpy as np

from frankenpaxos_tpu.protocols.multipaxos.config import MultiPaxosConfig
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    Chosen,
    ChosenRun,
    Phase2a,
    Phase2aRun,
    Phase2b,
    Phase2bRange,
    Phase2bVotes,
)
from frankenpaxos_tpu.protocols.multipaxos.quorum_tracker import (
    DictQuorumTracker,
    QuorumTracker,
    TpuQuorumTracker,
)
from frankenpaxos_tpu.reconfig import (
    EpochAck,
    EpochCommit,
    EpochConfig,
    EpochPhase2aRun,
    EpochQuorumTracker,
    EpochStore,
)
from frankenpaxos_tpu.runs.board import BoardDriver
from frankenpaxos_tpu.runtime import Actor, Collectors, FakeCollectors, Logger
from frankenpaxos_tpu.runtime.transport import Address, Transport


@dataclasses.dataclass(frozen=True)
class ProxyLeaderOptions:
    flush_phase2as_every_n: int = 1
    measure_latencies: bool = True
    # "dict" (host oracle) or "tpu" (batched vote board).
    quorum_backend: str = "dict"
    tpu_window: int = 1 << 20
    # Reconfiguration (reconfig/): backend for the epoch-segmented
    # tracker once epoch counting engages ("" follows quorum_backend).
    epoch_backend: str = ""
    # Engage the epoch tracker from construction even in a single
    # epoch (the reconfig_lt A/B's tagged arm); otherwise it engages
    # on the first committed epoch change / epoch-tagged run.
    epoch_quorums: bool = False


class ProxyLeader(Actor):
    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MultiPaxosConfig,
                 options: ProxyLeaderOptions = ProxyLeaderOptions(),
                 collectors: Collectors | None = None, seed: int = 0):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        self.options = options
        self.rng = random.Random(seed)
        collectors = collectors or FakeCollectors()
        self.metrics_latency = collectors.summary(
            "multipaxos_proxy_leader_requests_latency_seconds", labels=("type",))
        self.metrics_requests = collectors.counter(
            "multipaxos_proxy_leader_requests_total", labels=("type",))
        # The epoch tracker's counts (reconfig/), published after each
        # of its drains: in the order _publish_epoch_counts reads them.
        self.metrics_epoch_work = (
            collectors.counter(
                "multipaxos_proxy_leader_epoch_votes_total"),
            collectors.counter(
                "multipaxos_proxy_leader_epoch_launches_total"),
            # Of the votes, those that reached the device in a dense
            # block: how often a drain is one launch.
            collectors.counter(
                "multipaxos_proxy_leader_epoch_dense_votes_total"))
        self._epoch_published = (0,) * len(self.metrics_epoch_work)
        self.metrics_epoch_planes = collectors.gauge(
            "multipaxos_proxy_leader_epoch_planes")
        self.metrics_epoch_stashed = collectors.counter(
            "multipaxos_proxy_leader_epoch_stashed_runs_total")
        # Votes the single-epoch tracker held for slots still
        # collecting when epoch counting engaged, and that the epoch
        # tracker could not take over: their quorums complete only
        # through protocol-level resends.
        self.metrics_epoch_stranded = collectors.counter(
            "multipaxos_proxy_leader_epoch_switch_stranded_votes_total")
        self.grid = config.quorum_grid() if config.flexible else None
        self._row_size = len(config.acceptor_addresses[0])
        # paxingest (ingest/): control batch frames of vote acks land
        # as SoA range rows -- no Phase2b/Phase2bRange object per
        # segment (non-ack control batches parse to None and fall back
        # to per-message delivery).
        from frankenpaxos_tpu.ingest.columns import parse_ack_batch
        from frankenpaxos_tpu.runtime.paxwire import CONTROL_BATCH_TAG

        self.wire_sinks = {
            CONTROL_BATCH_TAG: (parse_ack_batch,
                                self._handle_ack_columns, "vote-intake"),
        }
        # (slot, round) -> pending value; moved to _done once chosen.
        self.pending: dict[tuple[int, int], object] = {}
        self._done: set[tuple[int, int]] = set()
        # Pending Phase2aRuns: start -> [end, round, values, remaining
        # (bool ndarray), left]. One O(1) record per run; chosen slots
        # resolve against it by bisect instead of per-slot dict entries.
        self._runs: dict[int, list] = {}
        self._run_starts: list[int] = []  # sorted (bisect.insort)
        # Completed runs' (start, end, round), kept for the stray-ack
        # fatal check (the per-slot path keeps _done forever; this is
        # the run equivalent, far smaller).
        self._done_runs: list[tuple[int, int, int]] = []
        self.chosen_count = 0
        self._unflushed_phase2as = 0
        # The commit paths (obs/trace.py) that came back with a vote,
        # by slot: each goes on with the chosen message to the replica
        # that answers its command. Bounded: a path whose slot is never
        # reported again (a vote that came late) ends here.
        self._chosen_paths: dict = {}
        if options.quorum_backend == "tpu":
            self.tracker: QuorumTracker = TpuQuorumTracker(
                config, window=options.tpu_window)
        else:
            self.tracker = DictQuorumTracker(config)
        # Reconfiguration (reconfig/): the epoch store resolves
        # acceptor sets per SLOT once epochs exist; the epoch tracker
        # counts votes by ADDRESS under each slot's epoch spec. Both
        # stay dormant (None tracker, single-epoch store) until a
        # reconfiguration touches this proxy, so the epoch-frozen hot
        # path is byte-identical to the pre-reconfig one.
        self.epochs: "EpochStore | None" = None
        if not config.flexible and config.num_acceptor_groups == 1:
            self.epochs = EpochStore.from_members(
                tuple(config.acceptor_addresses[0]), config.f)
        self._epoch_tracker: "EpochQuorumTracker | None" = None
        # EpochPhase2aRuns for epochs this proxy has not seen the
        # commit for yet: epoch -> [run]; replayed when it arrives.
        self._stashed_epoch_runs: dict[int, list] = {}
        # The device tracker's driving (runs/board.py): its counts, the
        # collector thread or the sim's flush timer, the hand-back.
        self.driver: "BoardDriver | None" = None
        if options.quorum_backend == "tpu":
            self.driver = BoardDriver(self, self.tracker, self._emit_chosen,
                                      collectors, "multipaxos_proxy_leader")
        if options.epoch_quorums and self.epochs is not None:
            self._ensure_epoch_tracker()

    def receive(self, src: Address, message) -> None:
        if self.transport.path_in is not None:
            self._keep_vote_path()
        # timed(label) handler latency summaries (Leader.scala:281-293).
        if self.options.measure_latencies:
            with self.receive_timer(self.metrics_latency, message):
                self._receive_impl(src, message)
        else:
            self._receive_impl(src, message)

    def _receive_impl(self, src: Address, message) -> None:
        if isinstance(message, Phase2a):
            self.metrics_requests.labels("Phase2a").inc()
            self._handle_phase2a(src, message)
        elif isinstance(message, Phase2aRun):
            self.metrics_requests.labels("Phase2aRun").inc()
            self._handle_phase2a_run(src, message)
        elif isinstance(message, EpochPhase2aRun):
            self.metrics_requests.labels("EpochPhase2aRun").inc()
            self._handle_epoch_phase2a_run(src, message)
        elif isinstance(message, EpochCommit):
            self.metrics_requests.labels("EpochCommit").inc()
            self._handle_epoch_commit(src, message)
        elif isinstance(message, Phase2b):
            self.metrics_requests.labels("Phase2b").inc()
            self._handle_phase2b(src, message)
        elif isinstance(message, Phase2bRange):
            self.metrics_requests.labels("Phase2bRange").inc()
            self._handle_phase2b_range(src, message)
        elif isinstance(message, Phase2bVotes):
            self.metrics_requests.labels("Phase2bVotes").inc()
            self._handle_phase2b_votes(src, message)
        else:
            self.logger.fatal(f"unexpected proxy leader message {message!r}")

    def _keep_vote_path(self) -> None:
        """The frame being handled carries a commit path: a vote's is
        kept for its slot's chosen message (a proposal's goes on with
        the relay, _relay_paths)."""
        path = self.transport.path_in
        if path.kind != "acceptor":
            return
        paths = self._chosen_paths
        if len(paths) >= 256:
            paths.clear()
        paths[path.slot] = path.onward("proxy_leader")

    def _relay_paths(self, quorum, start: int,
                     end: int) -> "dict | None":
        """The commit path of the frame being handled, if its command
        is among the slots ``[start, end)`` being relayed, to go on
        with the relay to one acceptor."""
        path = self.transport.path_in
        if path is None or not start <= path.slot < end:
            return None
        return {quorum[0]: path.onward("proxy_leader")}

    def _reply_paths(self, lo: int, hi: int) -> "dict | None":
        """The commit paths kept for chosen slots in ``[lo, hi)``, by
        the replica that answers each command. Two that meet in one
        replica's frame: one goes on, the other ends here."""
        paths = self._chosen_paths
        slots = [slot for slot in paths if lo <= slot < hi]
        if not slots:
            return None
        replicas = self.config.replica_addresses
        out = {}
        for slot in slots:
            out[replicas[slot % len(replicas)]] = paths.pop(slot)
        return out

    def _handle_phase2a(self, src: Address, phase2a: Phase2a) -> None:
        key = (phase2a.slot, phase2a.round)
        if key in self.pending:
            self.logger.debug(f"duplicate Phase2a for {key}; ignoring")
            return
        if self.epochs is not None:
            config = self.epochs.epoch_of_slot(phase2a.slot)
            quorum = self.rng.sample(list(config.members),
                                     config.quorum_size)
        elif not self.config.flexible:
            # Multi-group striping is epoch-frozen (no store).
            # paxlint: disable=PAX110
            group = list(self.config.acceptor_addresses[
                phase2a.slot % self.config.num_acceptor_groups])
            quorum = self.rng.sample(group, self.config.f + 1)
        else:
            write_quorum = self.grid.random_write_quorum(self.rng)
            quorum = [
                # paxlint: disable=PAX110 -- grids are epoch-frozen
                self.config.acceptor_addresses[flat // self._row_size]
                [flat % self._row_size] for flat in write_quorum]

        path = self.transport.path_in
        if path is not None and path.slot == phase2a.slot:
            # The relay to the first acceptor takes it.
            self.transport.path_out = path.onward("proxy_leader")
        if self.options.flush_phase2as_every_n <= 1:
            for acceptor in quorum:
                self.send(acceptor, phase2a)
        else:
            for acceptor in quorum:
                self.send_no_flush(acceptor, phase2a)
            self._unflushed_phase2as += 1
            if self._unflushed_phase2as >= self.options.flush_phase2as_every_n:
                # Flushing is connection upkeep, not membership: cover
                # every address ever buffered to.
                # paxlint: disable=PAX110
                for group_addresses in self.config.acceptor_addresses:
                    for acceptor in group_addresses:
                        self.flush(acceptor)
                if self.epochs is not None:
                    for acceptor in self.epochs.all_members():
                        self.flush(acceptor)
                self._unflushed_phase2as = 0
        self.pending[key] = phase2a.value

    def _admit_run(self, start_slot: int, round: int, values) -> bool:
        """Install a run's O(1) pending record, evicting a same-start
        LOWER-round predecessor (a new leader re-proposing the window;
        mirroring the acceptor's round-monotone vote store -- keeping
        the old record would swallow the new proposal and strand its
        slots until recovery). False: duplicate (same or stale round)."""
        pending = self._runs.get(start_slot)
        if pending is not None:
            if round <= pending[1]:
                return False
            del self._runs[start_slot]
            i = bisect.bisect_left(self._run_starts, start_slot)
            self._run_starts.pop(i)
            # Remember the evicted (start, end, round) so straggler
            # old-round acks are recognized instead of tripping the
            # stray-ack fatal check.
            bisect.insort(self._done_runs,
                          (start_slot, pending[0], pending[1]))
        self._runs[start_slot] = [
            start_slot + len(values), round, values,
            np.ones(len(values), dtype=bool), len(values)]
        bisect.insort(self._run_starts, start_slot)
        return True

    def _handle_phase2a_run(self, src: Address, run: Phase2aRun) -> None:
        """One write quorum for the whole run (drain-granular thrifty:
        the reference samples per slot, ProxyLeader.scala:67-120; one
        sample per run keeps acceptor-side runs whole), one forwarded
        message per quorum member, one O(1) pending record."""
        if len(run.values) == 0:
            return
        if not self._admit_run(run.start_slot, run.round, run.values):
            return
        if self.epochs is not None:
            # Epoch store = the acceptor-set authority (PAX110): for a
            # plain run the set is the start slot's epoch's (a run
            # never spans epochs -- the leader splits at boundaries).
            config = self.epochs.epoch_of_slot(run.start_slot)
            quorum = self.rng.sample(list(config.members),
                                     config.quorum_size)
        elif not self.config.flexible:
            # Multi-group striping is epoch-frozen (no store); the
            # config read IS the membership authority here.
            # paxlint: disable=PAX110
            group = list(self.config.acceptor_addresses[0])
            quorum = self.rng.sample(group, self.config.f + 1)
        else:
            write_quorum = self.grid.random_write_quorum(self.rng)
            quorum = [
                # paxlint: disable=PAX110 -- grids are epoch-frozen
                self.config.acceptor_addresses[flat // self._row_size]
                [flat % self._row_size] for flat in write_quorum]
        # Encode the values ONCE.
        self.broadcast(quorum, run, paths=self._relay_paths(
            quorum, run.start_slot, run.start_slot + len(run.values)))

    def _handle_epoch_phase2a_run(self, src: Address,
                                  run: EpochPhase2aRun) -> None:
        """An epoch-tagged run: fan it to ITS epoch's acceptors (as a
        plain Phase2aRun -- acceptors are epoch-agnostic voters) and
        count the acks under that epoch's spec. Unknown epoch: stash
        until the leader's EpochCommit resend lands -- never mis-route
        a new-epoch run to the old set."""
        if self.epochs is None:
            self.logger.fatal(
                "EpochPhase2aRun on a non-reconfigurable config")
        if len(run.values) == 0:
            return
        config = self.epochs.config(run.epoch)
        if config is None:
            self._stashed_epoch_runs.setdefault(run.epoch,
                                                []).append(run)
            self.metrics_epoch_stashed.inc()
            return
        self._ensure_epoch_tracker()
        if not self._admit_run(run.start_slot, run.round, run.values):
            return
        quorum = self.rng.sample(list(config.members),
                                 config.quorum_size)
        self.broadcast(quorum, Phase2aRun(
            start_slot=run.start_slot, round=run.round,
            values=run.values), paths=self._relay_paths(
                quorum, run.start_slot, run.start_slot + len(run.values)))

    def _handle_epoch_commit(self, src: Address,
                             commit: EpochCommit) -> None:
        """Adopt the epoch map entry, switch vote counting onto the
        epoch-segmented tracker, ack the committing leader, and replay
        any runs stashed for this epoch."""
        if self.epochs is None:
            return
        try:
            outcome = self.epochs.offer(
                EpochConfig(epoch=commit.epoch,
                            start_slot=commit.start_slot,
                            f=commit.f, members=commit.members),
                commit.round)
        except ValueError as e:
            self.logger.warn(f"EpochCommit rejected: {e}")
            return
        if outcome == "stale":
            return  # lower-round or non-contiguous: no ack
        self._ensure_epoch_tracker()
        self._epoch_tracker.note_epochs()
        self.metrics_epoch_planes.set(self._epoch_tracker.planes)
        self.send(src, EpochAck(epoch=commit.epoch, round=commit.round))
        for run in self._stashed_epoch_runs.pop(commit.epoch, []):
            self._handle_epoch_phase2a_run(src, run)

    def _ensure_epoch_tracker(self) -> None:
        """Engage epoch-segmented vote counting. Votes the
        single-epoch tracker holds for slots still collecting move
        over: a dict tracker's (group, index) votes map to addresses
        through the epoch-0 config, and a device board is adopted
        whole by a device epoch tracker (one gather onto its
        universe). Only a device board facing a dict epoch tracker
        stays behind; what it strands is counted."""
        if self._epoch_tracker is not None or self.epochs is None:
            return
        backend = self.options.epoch_backend or (
            "tpu" if self.options.quorum_backend == "tpu" else "dict")
        self._epoch_tracker = EpochQuorumTracker(
            self.epochs, backend=backend,
            window=self.options.tpu_window)
        self.metrics_epoch_planes.set(self._epoch_tracker.planes)
        if isinstance(self.tracker, DictQuorumTracker):
            for (slot, rnd), votes in self.tracker.states.items():
                if not votes:
                    continue  # Done: the chosen report already left
                for g, i in votes:
                    # One-shot migration of pre-epoch vote state; the
                    # epoch-0 members ARE the config group.
                    # paxlint: disable=PAX110
                    addr = self.config.acceptor_addresses[g][i]
                    self._epoch_tracker.record(slot, rnd, addr)
            self.tracker.states = {}
            return
        # The device board: what was buffered for it goes first, so
        # that the board holds every vote up to this instant.
        if self.tracker.has_votes():
            self.tracker.drain()
        self._hand_over_dispatches()
        if backend == "tpu":
            self._epoch_tracker.adopt_board(self.tracker.checker)
            return
        # paxlint: disable=TPU203 -- one fetch, at the one switch
        board = self.tracker.checker.board
        stranded = int(np.asarray(board.votes)[
            :, ~np.asarray(board.chosen)].sum())
        if stranded:
            self.metrics_epoch_stranded.inc(stranded)

    def _run_for(self, slot: int, round: int):
        """The pending run covering (slot, round), else None."""
        i = bisect.bisect_right(self._run_starts, slot) - 1
        if i < 0:
            return None
        run = self._runs.get(self._run_starts[i])
        if run is not None and slot < run[0] and run[1] == round:
            return run
        return None

    def _in_done_runs(self, slot: int, round: int) -> bool:
        i = bisect.bisect_right(self._done_runs, (slot, float("inf"),
                                                  float("inf"))) - 1
        if i < 0:
            return False
        # Same-start records can coexist (a retired run plus an evicted
        # lower-round predecessor); check every record sharing the
        # covering start (distinct starts never overlap).
        anchor = self._done_runs[i][0]
        while i >= 0 and self._done_runs[i][0] == anchor:
            _, end, rnd = self._done_runs[i]
            if slot < end and rnd == round:
                return True
            i -= 1
        return False

    def _handle_phase2b(self, src: Address, phase2b: Phase2b) -> None:
        key = (phase2b.slot, phase2b.round)
        if key not in self.pending and self._run_for(*key) is None:
            # Either never proposed here (a fatal bug in the reference,
            # ProxyLeader.scala:227-232) or already chosen. The tracker
            # dedups chosen slots; unknown (slot, round)s are fatal.
            if key not in self._done and not self._in_done_runs(*key):
                self.logger.fatal(
                    f"ProxyLeader got Phase2b for {key} but never sent a "
                    f"Phase2a there")
            return
        if self._epoch_tracker is not None:
            # Epoch mode counts by voter ADDRESS: carried (group,
            # index) coordinates collide across epochs when a
            # replacement reuses a dead member's config slot.
            self._epoch_tracker.record(phase2b.slot, phase2b.round, src)
            return
        self.tracker.record(phase2b.slot, phase2b.round,
                            phase2b.group_index, phase2b.acceptor_index)

    def _handle_phase2b_range(self, src: Address,
                              r: Phase2bRange) -> None:
        """A contiguous run of votes in one message: O(1) Python on the
        device tracker (the dict oracle expands per slot). No per-slot
        pending check here -- every slot in the range was a Phase2a THIS
        proxy leader sent to that acceptor, so each is in ``pending`` or
        already ``_done``; ``_emit_chosen`` dedups either way."""
        if self._epoch_tracker is not None:
            self._epoch_tracker.record_range(
                r.slot_start_inclusive, r.slot_end_exclusive, r.round,
                src)
            return
        self.tracker.record_range(r.slot_start_inclusive,
                                  r.slot_end_exclusive, r.round,
                                  r.group_index, r.acceptor_index)

    def _handle_ack_columns(self, src: Address, acks) -> None:
        """Wire-sink handler (paxingest): a whole batch frame of vote
        acks as (start, end, round, group, acceptor) rows, fed to the
        quorum tracker range-at-a-time. Width-1 rows keep the
        never-sent-a-Phase2a tripwire exactly like _handle_phase2b;
        wider rows follow _handle_phase2b_range's
        no-per-slot-pending-check rationale."""
        self.metrics_requests.labels("AckColumns").inc()
        if self.transport.path_in is not None:
            self._keep_vote_path()
        epoch_tracker = self._epoch_tracker
        for start, end, rnd, group, acceptor in acks.rows.tolist():
            if end - start == 1:
                key = (start, rnd)
                if key not in self.pending \
                        and self._run_for(start, rnd) is None:
                    if key not in self._done \
                            and not self._in_done_runs(start, rnd):
                        self.logger.fatal(
                            f"ProxyLeader got Phase2b for {key} but "
                            f"never sent a Phase2a there")
                    continue
            if epoch_tracker is not None:
                epoch_tracker.record_range(start, end, rnd, src)
            else:
                self.tracker.record_range(start, end, rnd, group,
                                          acceptor)

    def _handle_phase2b_votes(self, src: Address, m) -> None:
        """A packed fragmented-drain ack (Phase2bVotes): unpack with
        the native codec straight into the tracker's arrays -- no
        per-vote Python on either side (same no-pending-check rationale
        as ranges)."""
        from frankenpaxos_tpu import native

        with self.trace_stage("vote-intake"):
            slots, rounds = native.unpack_votes2(m.packed)
            if self._epoch_tracker is not None:
                self._epoch_tracker.record_votes(slots, rounds, src)
                return
            self.tracker.record_votes(slots, rounds, m.group_index,
                                      m.acceptor_index)

    def on_drain(self) -> None:
        # Stage ``drain``: the batched quorum check of a drain that had
        # votes (dict tally, or the tpu tracker's host side and its
        # kernel dispatch) and the hand-over of what it dispatched.
        # What it unlocks goes out under ``fan-out``.
        if self.tracker.has_votes():
            with self.trace_stage("drain"):
                chosen = self.tracker.drain()
                self._hand_over_dispatches()
            self._emit_chosen(chosen)
        else:
            self._hand_over_dispatches()
        if self._epoch_tracker is not None \
                and self._epoch_tracker.has_votes():
            # Stage ``epoch-drain``: the epoch tracker's whole check:
            # the drain's plan, its launches (one dense block as a
            # rule) and the fetch of their answers, here on the loop.
            with self.trace_stage("epoch-drain"):
                chosen = self._epoch_tracker.drain()
            self._publish_epoch_counts()
            self._emit_chosen(chosen)

    def _hand_over_dispatches(self) -> None:
        """After a drain: what the device tracker dispatched goes to its
        driver (runs/board.py)."""
        if self.driver is not None:
            self.driver.hand_over()

    def _publish_epoch_counts(self) -> None:
        """The epoch tracker's work counts into /metrics, as
        increments (as :meth:`_publish_tpu_counts`)."""
        t = self._epoch_tracker
        counts = (t.votes, t.launches, t.dense_votes)
        for series, now, then in zip(self.metrics_epoch_work, counts,
                                     self._epoch_published):
            series.inc(now - then)
        self._epoch_published = counts

    def _emit_chosen(self, keys) -> None:
        if not keys:
            return
        # Stage ``fan-out``: ChosenRun build + broadcast, one scope for
        # everything a drain or a collect reported.
        with self.trace_stage("fan-out"):
            if self._runs and len(keys) > 1:
                self._emit_chosen_grouped(keys)
                return
            for key in keys:
                self._emit_one(key)

    def _emit_one(self, key) -> None:
        value = self.pending.pop(key, None)
        if value is None:
            run = self._run_for(*key)
            if run is not None:
                self._emit_run_segment(run, key[0], key[0] + 1)
            return
        self._done.add(key)
        self.chosen_count += 1
        self.broadcast(self.config.replica_addresses,
                       Chosen(slot=key[0], value=value),
                       paths=(self._reply_paths(key[0], key[0] + 1)
                              if self._chosen_paths else None))

    def _emit_chosen_grouped(self, keys) -> None:
        """Group a drain's chosen (slot, round)s into contiguous
        same-round segments (preserving the tracker's arrival-order
        reporting -- no sort) and emit each segment as ONE ChosenRun per
        replica for every pending run it crosses; a slot outside any run
        falls back to the per-slot path."""
        slots = np.fromiter((k[0] for k in keys), dtype=np.int64,
                            count=len(keys))
        rounds = np.fromiter((k[1] for k in keys), dtype=np.int64,
                             count=len(keys))
        breaks = np.flatnonzero((np.diff(slots) != 1)
                                | (np.diff(rounds) != 0)) + 1
        at = 0
        for b in list(breaks.tolist()) + [len(keys)]:
            if b == at:
                continue
            self._emit_segment(int(slots[at]), int(slots[b - 1]) + 1,
                               int(rounds[at]))
            at = b

    def _emit_segment(self, lo: int, hi: int, rnd: int) -> None:
        """Chosen slots [lo, hi) of round ``rnd``, cut at run
        boundaries: one ChosenRun a pending run the segment crosses
        (many small runs, one a client's array, often share a
        drain's segment), a slot outside any run alone."""
        while lo < hi:
            run = (None if (lo, rnd) in self.pending
                   else self._run_for(lo, rnd))
            if run is None:
                self._emit_one((lo, rnd))
                lo += 1
            else:
                end = min(hi, run[0])
                self._emit_run_segment(run, lo, end)
                lo = end

    def _emit_run_segment(self, run: list, lo: int, hi: int) -> None:
        """Emit chosen slots [lo, hi) of one pending run: slice the
        values, one ChosenRun per replica, O(1) bookkeeping."""
        end, rnd, values, remaining, left = run
        start = end - len(values)
        seg = remaining[lo - start:hi - start]
        if not seg.all():
            # A re-report within the segment (cannot happen through the
            # tracker's exactly-once contract, but a different tracker
            # implementation might): emit only the fresh sub-slots.
            for off in np.flatnonzero(seg).tolist():
                self._emit_run_segment(run, lo + off, lo + off + 1)
            return
        seg[:] = False
        n = hi - lo
        run[4] = left - n
        self.chosen_count += n
        # Full-run emission (the steady state: the whole run's quorum
        # completes in one drain) forwards the values object itself --
        # for a LazyValueArray that re-encodes as a raw bytes copy,
        # with no Command ever materialized on this actor.
        seg_values = (values if lo == start and hi == end
                      else values[lo - start:hi - start])
        self.broadcast(self.config.replica_addresses,
                       ChosenRun(start_slot=lo, values=seg_values),
                       paths=(self._reply_paths(lo, hi)
                              if self._chosen_paths else None))
        if run[4] == 0:
            self._retire_run(start)

    def _retire_run(self, start: int) -> None:
        """Fully-chosen run: drop its values, remember (start, end,
        round) for the stray-ack check, prune the starts index."""
        run = self._runs.pop(start)
        bisect.insort(self._done_runs, (start, run[0], run[1]))
        i = bisect.bisect_left(self._run_starts, start)
        if i < len(self._run_starts) and self._run_starts[i] == start:
            self._run_starts.pop(i)

"""MultiPaxos Leader.

Reference behavior: multipaxos/Leader.scala:95-723. A state machine over
{Inactive, Phase1, Phase2}:

  * Phase1 (startPhase1, Leader.scala:409-430): send Phase1a with the
    chosen watermark to f+1 acceptors per group (or a grid read quorum);
    collect Phase1b until per-group quorums (or grid read quorum); adopt
    the highest-vote-round value per slot in [chosen_watermark, max_slot]
    -- `safeValue`, Leader.scala:318-330 -- propose them, jump to Phase2,
    replay pending batches.
  * Phase2 (processClientRequestBatch, Leader.scala:331-408): assign the
    next slot, hand the Phase2a to a proxy leader (round-robin in Hash
    mode, own colocated one otherwise).
  * Nacks bump the round and re-run Phase1 (Leader.scala:669-696);
    Recover triggers a leader change so holes get repaired
    (Leader.scala:698-722); the embedded election participant drives
    Inactive <-> active transitions (Leader.scala:192-203).
"""

from __future__ import annotations

import dataclasses
import random
import time
from typing import Optional

import numpy as np

from frankenpaxos_tpu.election.basic import (
    ElectionOptions,
    ElectionParticipant,
)
from frankenpaxos_tpu.ingest.columns import (
    CLIENT_ARRAY_TAG,
    COL_ID,
    COL_PSEUDONYM,
    parse_client_array,
    parse_client_batch,
    reject_value_suffix,
    value_view,
)
from frankenpaxos_tpu.ingest.messages import (
    IngestCredit,
    IngestRun,
    NotLeaderIngest,
)
from frankenpaxos_tpu.protocols.multipaxos.config import (
    DistributionScheme,
    MultiPaxosConfig,
)
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    ChosenWatermark,
    ClientRequest,
    ClientRequestArray,
    ClientRequestBatch,
    ClientRequestBatchRun,
    CommandBatch,
    LeaderInfoReplyBatcher,
    LeaderInfoReplyClient,
    LeaderInfoRequestBatcher,
    LeaderInfoRequestClient,
    Nack,
    NOOP,
    NotLeaderBatcher,
    NotLeaderClient,
    Phase1a,
    Phase1b,
    Phase2a,
    Phase2aRun,
    Recover,
)
from frankenpaxos_tpu.reconfig import (
    EpochAck,
    EpochCommit,
    EpochConfig,
    EpochPhase2aRun,
    EpochStore,
    Reconfigure,
)
from frankenpaxos_tpu.roundsystem import ClassicRoundRobin
from frankenpaxos_tpu.runtime import Actor, Collectors, FakeCollectors, Logger
from frankenpaxos_tpu.runtime.paxwire import CLIENT_BATCH_TAG
from frankenpaxos_tpu.runtime.transport import Address, Transport


@dataclasses.dataclass(frozen=True)
class LeaderOptions:
    resend_phase1as_period_s: float = 5.0
    flush_phase2as_every_n: int = 1
    # Assign this many CONSECUTIVE slots to one proxy leader before
    # rotating to the next (Hash scheme only). The reference rotates
    # per slot (Leader.scala:331-408); chunked rotation is the
    # TPU-first layout: each proxy leader's slot space stays
    # contiguous, so acceptors' ranged acks stay whole ranges and the
    # device tracker's drain blocks stay dense instead of shredding
    # into stride-N singles. Pure load balancing -- any proxy leader
    # can handle any slot -- so protocol semantics are unchanged.
    proxy_leader_chunk: int = 256
    noop_flush_period_s: float = 0.0  # 0 disables
    election_options: ElectionOptions = ElectionOptions()
    measure_latencies: bool = True
    # "host": the reference's per-slot safeValue scan. "tpu": one batched
    # ops/value.safe_values masked-argmax over the whole recovery window.
    phase1_backend: str = "host"
    # Tag every run proposal with its epoch (EpochPhase2aRun) even while
    # the store holds a single epoch. Off by default -- the single-epoch
    # steady state pays zero reconfig overhead; the reconfig_lt bench
    # turns this on to measure exactly that tagging cost. Once a real
    # reconfiguration commits, tagging engages regardless.
    epoch_tag_runs: bool = False
    resend_epoch_commit_period_s: float = 1.0
    # paxload admission control (serve/admission.py, docs/SERVING.md).
    # All zeros (the default) admits everything and builds NO
    # controller -- the admission-off hot path is one ``is None`` test.
    # The in-flight budget is tied to the run pipeline's watermark:
    # the live span is next_slot - chosen_watermark, refreshed on
    # every proposal and every ChosenWatermark advance.
    admission_token_rate: float = 0.0
    admission_token_burst: float = 0.0
    admission_inflight_limit: int = 0
    admission_inbox_capacity: int = 0
    admission_inbox_policy: str = "reject"
    admission_codel_target_s: float = 0.0
    admission_codel_interval_s: float = 0.1
    admission_retry_after_ms: int = 0

    def admission_options(self):
        from frankenpaxos_tpu.serve.admission import options_from_flat

        return options_from_flat(self)


class _Inactive:
    pass


@dataclasses.dataclass
class _Phase1:
    # group index -> acceptor index -> Phase1b
    phase1bs: list[dict[int, Phase1b]]
    phase1b_acceptors: set[tuple[int, int]]
    pending_batches: list[ClientRequestBatch]
    resend_phase1as: object  # Timer
    # Address-keyed Phase1bs (reconfig): across epochs, (group, index)
    # coordinates can collide -- a replacement reuses a dead member's
    # config slot -- but addresses cannot.
    by_addr: dict = dataclasses.field(default_factory=dict)
    # The Phase1a in flight, for epoch-discovery extension sends.
    phase1a: Optional[Phase1a] = None


@dataclasses.dataclass
class _Phase2:
    noop_flush: Optional[object] = None  # Timer


@dataclasses.dataclass
class _EpochChange:
    """An epoch change in flight (docs/RECONFIG.md state machine):
    PENDING until a write quorum of OLD-epoch acceptors durably acked
    the EpochCommit (proposals buffer -- the handover window), then
    ACTIVE (buffered proposals open the new epoch's slots) while
    resends keep chasing the stragglers' acks."""

    config: EpochConfig
    commit: EpochCommit
    targets: set
    acks: set
    resend: object  # Timer
    pending: list   # buffered CommandBatchOrNoop values
    started: float  # time.perf_counter() when the commit first left
    activated: bool = False
    # True when RE-driving an adopted epoch (post-failover, or a
    # Phase2 leader learning one from a peer broadcast): same gate --
    # an epoch may be proposed into only once f+1 of its PREDECESSOR's
    # acceptors durably hold its commit, because that is what makes
    # every future leader's Phase1 discover it (chaos-found: an
    # adopted-but-undurable epoch let a later leader re-propose its
    # slots under the old quorums -- a second chosen value).
    recommit: bool = False


class Leader(Actor):
    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MultiPaxosConfig,
                 options: LeaderOptions = LeaderOptions(),
                 collectors: Collectors | None = None, seed: int = 0):
        super().__init__(address, transport, logger)
        config.check_valid()
        logger.check(address in config.leader_addresses)
        self.config = config
        self.options = options
        self.rng = random.Random(seed)
        collectors = collectors or FakeCollectors()
        self.metrics_latency = collectors.summary(
            "multipaxos_leader_requests_latency_seconds", labels=("type",))
        self.metrics_requests = collectors.counter(
            "multipaxos_leader_requests_total", labels=("type",))
        # Reconfiguration (reconfig/): epochs this leader activated,
        # and the proposals it held back while each waited for its
        # predecessor's quorum of acks.
        self.metrics_epoch_changes = collectors.counter(
            "multipaxos_leader_epoch_changes_total")
        self.metrics_epoch_buffered = collectors.counter(
            "multipaxos_leader_epoch_buffered_proposals_total")
        self.index = list(config.leader_addresses).index(address)
        self.grid = config.quorum_grid() if config.flexible else None
        self._row_size = len(config.acceptor_addresses[0])
        # Live reconfiguration (reconfig/): the epoch store is THE
        # authority for acceptor-set reads on this role (paxlint
        # PAX110). Supported for the workhorse shape -- one
        # non-flexible 2f+1 group; grids and slot-striped multi-group
        # configs stay epoch-frozen.
        self.epochs: Optional[EpochStore] = None
        if not config.flexible and config.num_acceptor_groups == 1:
            self.epochs = EpochStore.from_members(
                tuple(config.acceptor_addresses[0]), config.f)
        self._epoch_change: Optional[_EpochChange] = None
        # Post-failover epoch re-broadcast state: {"epoch", "commits",
        # "pending" (proxies yet to ack), "timer"} or None.
        self._epoch_sync: Optional[dict] = None
        self.round_system = ClassicRoundRobin(config.num_leaders)
        # Active leader's round, or the largest known active round.
        self.round = self.round_system.next_classic_round(0, -1)
        self.next_slot = 0
        self.chosen_watermark = 0
        # Commands admitted while in _Phase1 (sitting in
        # pending_batches with no slot yet): the in-flight resyncs
        # must count them, or a long Phase1 admits without bound.
        self._admitted_backlog = 0
        # paxload admission (serve/): built only when an option arms
        # it, so admission-off deployments keep the exact pre-paxload
        # hot path (Actor.admission stays None for the transports too).
        admission_options = options.admission_options()
        if admission_options is not None:
            from frankenpaxos_tpu.serve.admission import (
                AdmissionController,
            )

            self.admission = AdmissionController(
                admission_options, role=f"leader_{self.index}",
                metrics=transport.runtime_metrics)
            transport.note_admission(address, self)
        self._current_proxy_leader = 0
        self._unflushed_phase2as = 0
        # The commit path (obs/trace.py) of a sampled command this
        # handler is proposing, with its slot: the send whose slots
        # hold it carries it on (_path_onto).
        self._path = None
        self._chunk_sent = 0
        # paxingest (ingest/, docs/TRANSPORT.md): client batch frames
        # and un-batched coalesced arrays land as SoA columns and
        # propose as ONE run -- the wire-to-device fast path for
        # direct client->leader deployments (batcher'd deployments
        # arrive as IngestRun).
        self.wire_sinks = {
            CLIENT_BATCH_TAG: (parse_client_batch,
                               self._handle_client_columns),
            CLIENT_ARRAY_TAG: (parse_client_array,
                               self._handle_client_columns),
        }
        # paxfan descriptor pipelining: per-batcher drained-seq
        # high-water accumulated across one event-loop pass (the leader
        # drains SEVERAL pipelined runs per pass) and flushed as ONE
        # IngestCredit per batcher in on_drain.
        self._ingest_credit_hw: dict = {}

        # Embedded election participant (Leader.scala:192-203).
        self.election = ElectionParticipant(
            config.leader_election_addresses[self.index], transport, logger,
            config.leader_election_addresses, initial_leader_index=0,
            options=options.election_options, seed=seed)
        self.election.register(
            lambda leader_index: self.leader_change(leader_index == self.index))

        self.state: object = (self._start_phase1(self.round,
                                                 self.chosen_watermark)
                              if self.index == 0 else _Inactive())

    # --- helpers ----------------------------------------------------------
    # Flat grid-index arithmetic for the flexible-grid branch, which
    # runs only when self.epochs is None: grid deployments are
    # epoch-frozen (docs/RECONFIG.md "Supported shapes"), so the static
    # config IS the membership.
    def _acceptor_address(self, flat: int) -> Address:  # paxlint: disable=PAX110
        return self.config.acceptor_addresses[flat // self._row_size][
            flat % self._row_size]

    def _proxy_leader_address(self) -> Address:
        if self.config.distribution_scheme == DistributionScheme.HASH:
            return self.config.proxy_leader_addresses[
                self._current_proxy_leader]
        return self.config.proxy_leader_addresses[self.index]

    def _advance_proxy_leader(self) -> None:
        self._current_proxy_leader = (
            (self._current_proxy_leader + 1) % self.config.num_proxy_leaders)

    @staticmethod
    def _safe_value(phase1bs, slot: int):
        """Highest-vote-round value for ``slot`` else Noop
        (Leader.scala:318-330)."""
        best_round, best_value = -1, None
        for phase1b in phase1bs:
            for info in phase1b.info:
                if info.slot == slot and info.vote_round > best_round:
                    best_round, best_value = info.vote_round, info.vote_value
        return NOOP if best_value is None else best_value

    def _recover_values(self, phase1: "_Phase1", max_slot: int) -> list:
        """Safe values for ``[chosen_watermark, max_slot]``, one per slot.

        The host path replays ``_safe_value`` per slot; the tpu path lifts
        the whole recovery window into one ``[S, N]`` masked argmax
        (ops/value.safe_values) -- votes become (round, value-id) matrices,
        the device returns each slot's highest-round value id, and the
        host maps ids back to values (Leader.scala:504-576's scan as a
        single reduction).
        """
        slots = range(self.chosen_watermark, max_slot + 1)
        # Multi-epoch recovery: every answering acceptor's votes are
        # scanned for every slot. Non-members of a slot's epoch can
        # hold no votes for it (proposals only ever fan to the epoch's
        # members), so the scan is a superset of the epoch's read
        # quorum -- the safe-value rule over exactly the right config.
        # The tpu phase1 backend indexes votes by (group, index)
        # coordinates, which collide across epochs; the host scan is
        # the multi-epoch path.
        if self.epochs is not None and self.epochs.multi_epoch:
            all_phase1bs = list(phase1.by_addr.values())
            return [self._safe_value(all_phase1bs, s) for s in slots]
        # Non-flexible mode partitions slots over acceptor groups
        # (slot % G owns the slot); in FLEXIBLE mode the "groups" are
        # grid ROWS -- every acceptor votes on every slot, so recovery
        # must scan ALL Phase1bs for every slot. Applying the
        # partitioning rule to a grid dropped reported votes whose
        # acceptor sat in the "wrong" row and recovered Noop over a
        # chosen value (found by the 500x250 soak, multipaxos/f1-grid
        # seed 493: replica logs diverged).
        if self.options.phase1_backend != "tpu":
            if self.config.flexible:
                all_phase1bs = [p for group in phase1.phase1bs
                                for p in group.values()]
                return [self._safe_value(all_phase1bs, s) for s in slots]
            return [
                self._safe_value(
                    phase1.phase1bs[s % self.config.num_acceptor_groups]
                    .values(), s)
                for s in slots
            ]

        from frankenpaxos_tpu.ops import value as value_ops

        num_slots = max_slot + 1 - self.chosen_watermark
        if num_slots <= 0:
            return []
        num_groups = self.config.num_acceptor_groups
        n_cols = num_groups * self._row_size
        padded = 1
        while padded < num_slots:
            padded *= 2
        vote_rounds = np.full((padded, n_cols), value_ops.NO_VOTE,
                              dtype=np.int32)
        value_ids = np.zeros((padded, n_cols), dtype=np.int32)
        values_by_id: list = []
        id_by_value: dict = {}
        for group_index, group in enumerate(phase1.phase1bs):
            for acceptor_index, phase1b in group.items():
                col = group_index * self._row_size + acceptor_index
                for info in phase1b.info:
                    if not (self.chosen_watermark <= info.slot <= max_slot):
                        continue
                    # Slot-partitioning filter only in non-flexible
                    # mode (see the host path above).
                    if (not self.config.flexible
                            and info.slot % num_groups != group_index):
                        continue
                    vid = id_by_value.get(info.vote_value)
                    if vid is None:
                        vid = len(values_by_id)
                        id_by_value[info.vote_value] = vid
                        values_by_id.append(info.vote_value)
                    row = info.slot - self.chosen_watermark
                    vote_rounds[row, col] = info.vote_round
                    value_ids[row, col] = vid
        has_vote, chosen = value_ops.safe_values(vote_rounds, value_ids)
        has_vote = np.asarray(has_vote)[:num_slots]
        chosen = np.asarray(chosen)[:num_slots]
        return [values_by_id[int(vid)] if hit else NOOP
                for hit, vid in zip(has_vote, chosen)]

    def _account_sent_slots(self, dst: Address, k: int) -> None:
        """Rotate proxy leaders every `chunk` slots (>= the flush batch,
        so a no-flush run never strands bytes on a just-left dst). The
        ONE place the rotation schedule lives -- shared by the per-slot
        and run proposal paths."""
        self._chunk_sent += k
        chunk = max(self.options.proxy_leader_chunk,
                    self.options.flush_phase2as_every_n)
        if self._chunk_sent >= chunk:
            if self._unflushed_phase2as:
                self.flush(dst)
                self._unflushed_phase2as = 0
            self._advance_proxy_leader()
            self._chunk_sent = 0
        elif (self._unflushed_phase2as
              >= self.options.flush_phase2as_every_n):
            self.flush(dst)
            self._unflushed_phase2as = 0

    def _note_path(self, path, place: int) -> None:
        """The frame being handled carries a sampled command's commit
        path from its client or its batcher, and the command is in the
        ``place``-th of the values about to be proposed from
        ``next_slot`` (-1: not among them, and the path ends here)."""
        if path.kind in ("client", "batcher") and place >= 0:
            self._path = path.onward("leader", self.next_slot + place)

    def _path_onto(self, start: int, end: int) -> None:
        """Hand the noted commit path to the next send if the slots
        ``[start, end)`` it proposes hold the path's."""
        path = self._path
        if path is not None and start <= path.slot < end:
            self.transport.path_out = path
            self._path = None

    def _send_phase2a(self, phase2a: Phase2a,
                      force_flush: bool = False) -> None:
        dst = self._proxy_leader_address()
        self._path_onto(phase2a.slot, phase2a.slot + 1)
        if self.options.flush_phase2as_every_n <= 1:
            self.send(dst, phase2a)
        else:
            self.send_no_flush(dst, phase2a)
            self._unflushed_phase2as += 1
        self._account_sent_slots(dst, 1)
        if force_flush and self._unflushed_phase2as:
            self.flush(dst)
            self._unflushed_phase2as = 0

    @property
    def _epoch_tagging(self) -> bool:
        """Whether proposals carry epoch tags: always once a real
        reconfiguration committed (the proxy must never mis-route a
        run across the handover), or forced by ``epoch_tag_runs`` for
        the steady-state overhead A/B."""
        return self.epochs is not None and (
            self.epochs.multi_epoch or self.options.epoch_tag_runs)

    def _epoch_buffering(self) -> "Optional[list]":
        """The pending-change buffer while an epoch change awaits its
        activation quorum (the handover window), else None."""
        change = self._epoch_change
        if change is not None and not change.activated:
            return change.pending
        return None

    def _send_epoch_runs(self, values: tuple) -> None:
        """Propose ``values`` at contiguous slots from ``next_slot`` as
        epoch-tagged runs, SPLIT at epoch activation boundaries -- a
        proposal run never spans two acceptor sets (each segment's
        quorum is one epoch's)."""
        k = len(values)
        at = 0
        while at < k:
            slot = self.next_slot + at
            config = self.epochs.epoch_of_slot(slot)
            end = k
            nxt = self.epochs.config(config.epoch + 1)
            if nxt is not None:
                end = min(k, nxt.start_slot - self.next_slot)
            dst = self._proxy_leader_address()
            self._path_onto(slot, self.next_slot + end)
            self.send(dst, EpochPhase2aRun(
                epoch=config.epoch, start_slot=slot, round=self.round,
                values=tuple(values[at:end])))
            self._account_sent_slots(dst, end - at)
            at = end
        self.next_slot += k

    def _process_client_request_batch(self, batch: ClientRequestBatch) -> None:
        if not isinstance(self.state, _Phase2):
            self.logger.fatal(
                f"leader processing a batch outside Phase2: {self.state}")
        pending = self._epoch_buffering()
        if pending is not None:
            pending.append(batch.batch)
            return
        if self._epoch_tagging:
            self._send_epoch_runs((batch.batch,))
            return
        self._send_phase2a(Phase2a(slot=self.next_slot, round=self.round,
                                   value=batch.batch))
        self.next_slot += 1

    # --- phase 1 ----------------------------------------------------------
    def _phase1_epochs(self) -> list:
        """The epochs a Phase1 recovering ``[chosen_watermark, inf)``
        must hold a read quorum in -- Phase1-with-both-configs across a
        handover (the Flexible-Paxos intersection condition)."""
        return self.epochs.epochs_covering(self.chosen_watermark)

    def _start_phase1(self, round: int, chosen_watermark: int) -> _Phase1:
        phase1a = Phase1a(round=round, chosen_watermark=chosen_watermark)
        if self.epochs is not None:
            # Thrifty f+1 sample per covered epoch (a majority is both
            # the read and write quorum); resend widens to every member.
            # dict.fromkeys, not a set: iteration must stay
            # deterministic (sim replay, golden traces) under string
            # hash randomization.
            targets: dict = {}
            for config in self._phase1_epochs():
                targets.update(dict.fromkeys(self.rng.sample(
                    list(config.members), config.quorum_size)))
            for acceptor in targets:
                self.send(acceptor, phase1a)
        elif not self.config.flexible:
            # self.epochs is None on this path: multi-group striping
            # is epoch-frozen (docs/RECONFIG.md "Supported shapes").
            # paxlint: disable=PAX110
            for group in self.config.acceptor_addresses:
                for acceptor in self.rng.sample(list(group),
                                                self.config.f + 1):
                    self.send(acceptor, phase1a)
        else:
            for flat in self.grid.random_read_quorum(self.rng):
                self.send(self._acceptor_address(flat), phase1a)

        def resend():
            if self.epochs is not None:
                targets: dict = {}
                for config in self._phase1_epochs():
                    targets.update(dict.fromkeys(config.members))
                for acceptor in targets:
                    self.send(acceptor, phase1a)
            else:
                for group in self.config.acceptor_addresses:
                    for acceptor in group:
                        self.send(acceptor, phase1a)
            timer.start()

        timer = self.timer("resendPhase1as",
                           self.options.resend_phase1as_period_s, resend)
        timer.start()
        # Fresh Phase1 = fresh (empty) pending backlog.
        self._admitted_backlog = 0
        return _Phase1(
            phase1bs=[{} for _ in range(self.config.num_acceptor_groups)],
            phase1b_acceptors=set(),
            pending_batches=[],
            resend_phase1as=timer,
            phase1a=phase1a)

    def _make_noop_flush_timer(self) -> Optional[object]:
        """In non-flexible mode with multiple groups, periodically propose
        noops so no acceptor group starves (Leader.scala:240-280)."""
        if self.config.flexible or self.options.noop_flush_period_s <= 0:
            return None

        def flush_noop():
            if not isinstance(self.state, _Phase2):
                self.logger.fatal("noop flush outside Phase2")
            # force_flush: an anti-starvation noop must reach its
            # acceptor group NOW, not sit in a no-flush buffer; and
            # rotation is _send_phase2a's job (an extra advance here
            # would split the proxy-leader chunk and strand buffered
            # Phase2as on the just-left dst).
            self._send_phase2a(Phase2a(slot=self.next_slot, round=self.round,
                                       value=NOOP), force_flush=True)
            self.next_slot += 1
            timer.start()

        timer = self.timer("noopFlush", self.options.noop_flush_period_s,
                           flush_noop)
        timer.start()
        return timer

    def _stop_state_timers(self) -> None:
        if isinstance(self.state, _Phase1):
            self.state.resend_phase1as.stop()
        elif isinstance(self.state, _Phase2) and self.state.noop_flush:
            self.state.noop_flush.stop()

    def _abort_epoch_change(self) -> None:
        """Round churn aborts an in-flight change: the commit was
        round-tagged, so its acks are dead; a successor leader adopting
        the (possibly partially acked) entry from Phase1bs supersedes
        or re-drives it. Buffered proposals are dropped -- clients
        resend, and the replica client table keeps that exactly-once."""
        change = self._epoch_change
        if change is None:
            return
        change.resend.stop()
        if change.pending:
            self.logger.debug(
                f"epoch change aborted with {len(change.pending)} "
                f"buffered proposals (clients will resend)")
        self._epoch_change = None

    def leader_change(self, is_new_leader: bool) -> None:
        """Election callback (Leader.scala:432-459)."""
        self._stop_state_timers()
        self._abort_epoch_change()
        self._stop_epoch_sync()
        if not is_new_leader:
            self.state = _Inactive()
            return
        self.round = self.round_system.next_classic_round(self.index,
                                                          self.round)
        self.state = self._start_phase1(self.round, self.chosen_watermark)

    # --- handlers ---------------------------------------------------------
    def receive(self, src: Address, message) -> None:
        # timed(label) handler latency summaries (Leader.scala:281-293).
        if self.options.measure_latencies:
            with self.receive_timer(self.metrics_latency, message):
                self._receive_impl(src, message)
        else:
            self._receive_impl(src, message)

    def _receive_impl(self, src: Address, message) -> None:
        handlers = [
            (Phase1b, "Phase1b", self._handle_phase1b),
            (ClientRequest, "ClientRequest", self._handle_client_request),
            (ClientRequestArray, "ClientRequestArray",
             self._handle_client_request_array),
            (ClientRequestBatch, "ClientRequestBatch",
             self._handle_client_request_batch),
            (ClientRequestBatchRun, "ClientRequestBatchRun",
             self._handle_client_request_batch_run),
            (IngestRun, "IngestRun", self._handle_ingest_run),
            (LeaderInfoRequestClient, "LeaderInfoRequestClient",
             self._handle_leader_info_request_client),
            (LeaderInfoRequestBatcher, "LeaderInfoRequestBatcher",
             self._handle_leader_info_request_batcher),
            (Nack, "Nack", self._handle_nack),
            (ChosenWatermark, "ChosenWatermark",
             self._handle_chosen_watermark),
            (Recover, "Recover", self._handle_recover),
            (Reconfigure, "Reconfigure", self._handle_reconfigure),
            (EpochAck, "EpochAck", self._handle_epoch_ack),
            (EpochCommit, "EpochCommit", self._handle_epoch_commit),
        ]
        for klass, label, handler in handlers:
            if isinstance(message, klass):
                self.metrics_requests.labels(label).inc()
                handler(src, message)
                return
        self.logger.fatal(f"unexpected leader message {message!r}")

    def _adopt_epochs(self, commits) -> bool:
        """Merge epoch entries discovered in a Phase1b into the store
        (highest round per epoch id wins); returns True when coverage
        changed (the caller extends Phase1a to the new members)."""
        changed = False
        for commit in sorted(commits, key=lambda c: (c.epoch, c.round)):
            try:
                outcome = self.epochs.offer(
                    EpochConfig(epoch=commit.epoch,
                                start_slot=commit.start_slot,
                                f=commit.f, members=commit.members),
                    commit.round)
            except ValueError as e:
                self.logger.warn(f"discovered epoch rejected: {e}")
                continue
            changed = changed or outcome in ("new", "replaced")
        return changed

    def _handle_phase1b(self, src: Address, phase1b: Phase1b) -> None:
        if not isinstance(self.state, _Phase1):
            self.logger.debug("Phase1b outside Phase1; ignoring")
            return
        phase1 = self.state
        if phase1b.round != self.round:
            self.logger.debug(
                f"Phase1b in round {phase1b.round} != {self.round}; ignoring")
            self.logger.check_lt(phase1b.round, self.round)
            return

        phase1.by_addr[src] = phase1b
        if self.epochs is not None and phase1b.epochs \
                and self._adopt_epochs(phase1b.epochs):
            # Coverage grew mid-Phase1: the newly discovered epochs'
            # members must answer too before recovery may finish.
            members: dict = {}
            for config in self._phase1_epochs():
                members.update(dict.fromkeys(config.members))
            for acceptor in members:
                if acceptor not in phase1.by_addr:
                    self.send(acceptor, phase1.phase1a)
        if self.epochs is not None and self.epochs.multi_epoch:
            # Phase1-with-both-configs: a read quorum in EVERY epoch
            # still covering undecided slots (quorum intersection per
            # epoch is what makes crossing the handover safe).
            answered = set(phase1.by_addr)
            for config in self._phase1_epochs():
                if not config.has_read_quorum(answered):
                    return
        else:
            phase1.phase1bs[phase1b.group_index][phase1b.acceptor_index] \
                = phase1b
            if not self.config.flexible:
                if any(len(group) < self.config.f + 1
                       for group in phase1.phase1bs):
                    return
            else:
                phase1.phase1b_acceptors.add(
                    (phase1b.group_index, phase1b.acceptor_index))
                flat = {g * self._row_size + i
                        for g, i in phase1.phase1b_acceptors}
                if not self.grid.is_superset_of_read_quorum(flat):
                    return

        max_slot = max(
            (info.slot
             for p1b in phase1.by_addr.values()
             for info in p1b.info),
            default=-1)
        values = self._recover_values(phase1, max_slot)
        for slot, value in zip(range(self.chosen_watermark, max_slot + 1),
                               values):
            if self._epoch_tagging:
                # Route recovery proposals by their slot's epoch so the
                # proxy fans each to the right acceptor set.
                config = self.epochs.epoch_of_slot(slot)
                dst = self._proxy_leader_address()
                self.send(dst, EpochPhase2aRun(
                    epoch=config.epoch, start_slot=slot,
                    round=self.round, values=(value,)))
                self._account_sent_slots(dst, 1)
            else:
                self._send_phase2a(Phase2a(slot=slot, round=self.round,
                                           value=value))
        # next_slot must clear the chosen watermark, not just the voted
        # max: Phase1bs report nothing below the watermark (every slot
        # there is already chosen), so with no votes ABOVE it,
        # ``max_slot + 1`` alone would re-propose fresh commands into
        # already-chosen slots -- choosing a second value for a slot
        # (found by the WAL chaos soak's partition + leader-churn
        # schedules). Any CHOSEN slot >= the watermark is covered by
        # quorum intersection: some Phase1b carries its vote, so
        # max_slot clears it.
        self.next_slot = max(max_slot + 1, self.chosen_watermark)

        phase1.resend_phase1as.stop()
        self.state = _Phase2(self._make_noop_flush_timer())
        if self.epochs is not None and self.epochs.multi_epoch:
            newest_epoch = self.epochs.current().epoch
            reporters = {
                addr for addr, p1b in phase1.by_addr.items()
                if any(c.epoch == newest_epoch for c in p1b.epochs)}
            self._ensure_epoch_durability(reporters)
        for batch in phase1.pending_batches:
            self._process_client_request_batch(batch)
        # The backlog just moved into the span (next_slot advanced per
        # batch); resync so it isn't double-counted.
        self._admitted_backlog = 0
        if self.admission is not None:
            self._sync_inflight()

    def _sync_inflight(self) -> None:
        """Resync the controller to the LIVE in-flight measure:
        proposed-minus-chosen span (the run pipeline's own count of
        outstanding work) plus the Phase1 backlog of admitted-but-
        unslotted commands. Called only where the measure actually
        changes (watermark advances, Phase1 exit) -- between resyncs
        ``admit()``'s own increments accumulate, so the budget binds
        even while next_slot is frozen in Phase1."""
        self.admission.set_inflight(
            self.next_slot - self.chosen_watermark
            + self._admitted_backlog)

    def _admit(self, message, n: int) -> bool:
        """paxload admission for ``n`` client commands (serve/): on
        refusal, answer with explicit Rejected wire replies so clients
        back off instead of re-sending into the congestion.
        Control-plane messages never pass through here -- only the
        three client-request shapes do."""
        admission = self.admission
        if admission is None:
            return True
        if admission.admit(n):
            return True
        from frankenpaxos_tpu.serve.admission import reject_replies_for

        for client, reply in reject_replies_for(
                message, admission.retry_after_ms(),
                admission.last_reason):
            self.send(client, reply)
        return False

    def _admit_prefix(self, commands: tuple) -> tuple:
        """Partial admission for a coalesced array: serve the prefix
        the budget allows, explicitly reject the suffix (one Rejected
        -- all commands in an array come from ONE client)."""
        admission = self.admission
        if admission is None:
            return commands
        k = admission.admit_up_to(len(commands))
        if k < len(commands):
            from frankenpaxos_tpu.serve.admission import reject_replies_for

            for address, reply in reject_replies_for(
                    ClientRequestArray(commands=commands[k:]),
                    retry_after_ms=admission.retry_after_ms(),
                    reason=admission.last_reason):
                self.send(address, reply)
        return commands[:k]

    def _handle_client_request(self, src: Address,
                               request: ClientRequest) -> None:
        if isinstance(self.state, _Inactive):
            self.send(src, NotLeaderClient())
        elif not self._admit(request, 1):
            pass
        elif isinstance(self.state, _Phase1):
            self._admitted_backlog += 1
            self.state.pending_batches.append(
                ClientRequestBatch(CommandBatch((request.command,))))
        else:
            path = self.transport.path_in
            if path is not None:
                self._note_path(path, _place(path, (request.command,)))
            self._process_client_request_batch(
                ClientRequestBatch(CommandBatch((request.command,))))
            self._path = None

    def _handle_client_request_array(self, src: Address,
                                     array: ClientRequestArray) -> None:
        """A drain's worth of independent requests: assign each its own
        slot from a CONTIGUOUS block and propose the whole block as one
        Phase2aRun (the per-drain shape of Leader.scala:331-408's
        per-slot processClientRequestBatch)."""
        if not array.commands:
            return
        if isinstance(self.state, _Inactive):
            self.send(src, NotLeaderClient())
            return
        commands = self._admit_prefix(array.commands)
        if not commands:
            return
        if len(commands) < len(array.commands):
            array = ClientRequestArray(commands=commands)
        if isinstance(self.state, _Phase1):
            self._admitted_backlog += len(array.commands)
            for command in array.commands:
                self.state.pending_batches.append(
                    ClientRequestBatch(CommandBatch((command,))))
            return
        path = self.transport.path_in
        if path is not None:
            self._note_path(path, _place(path, array.commands))
        self._propose_value_run(
            tuple(CommandBatch((c,)) for c in array.commands))
        self._path = None

    def _propose_value_run(self, values) -> None:
        """Post-admission Phase2 proposal of one-value-per-slot
        ``values`` -- a tuple, or a LazyValueArray whose raw segment is
        forwarded without a parse (the ingest fast path). The shared
        tail of the array / wire-column / IngestRun paths."""
        if self.config.num_acceptor_groups > 1 and not self.config.flexible:
            # Slots stripe over acceptor groups (slot % G) in this mode,
            # so a contiguous run has no single acceptor audience; fall
            # back to per-slot proposals (iterating decodes a lazy
            # array -- this config is off the zero-object path).
            for value in values:
                self._send_phase2a(Phase2a(slot=self.next_slot,
                                           round=self.round,
                                           value=value))
                self.next_slot += 1
            return
        pending = self._epoch_buffering()
        if pending is not None:
            # Handover window: the epoch change has not reached its
            # activation quorum yet, and these commands' slots belong
            # to the NEW epoch -- hold them so in-flight runs drain in
            # the old epoch while the commit settles.
            pending.extend(values)
            return
        if self._epoch_tagging:
            self._send_epoch_runs(tuple(values))
            return
        run = Phase2aRun(
            start_slot=self.next_slot, round=self.round, values=values)
        k = len(values)
        self._path_onto(self.next_slot, self.next_slot + k)
        self.next_slot += k
        dst = self._proxy_leader_address()
        self.send(dst, run)
        # A run counts as k slots toward the proxy-leader chunk
        # rotation (runs never use the no-flush buffer).
        self._account_sent_slots(dst, k)

    # --- paxingest (ingest/, docs/TRANSPORT.md) ---------------------------
    def _note_ingest(self, cmds: int, nbytes: int) -> None:
        metrics = self.transport.runtime_metrics
        if metrics is not None:
            metrics.ingest_batch(cmds, nbytes)

    def on_drain(self) -> None:
        """Flush accumulated pipelining credits: ONE watermark-granular
        IngestCredit per batcher per drain, regardless of how many runs
        this pass consumed. Control-lane (serve/lanes.py), so shedding
        never wedges the batchers' windows."""
        if self._ingest_credit_hw:
            credits, self._ingest_credit_hw = self._ingest_credit_hw, {}
            for src, hw in credits.items():
                self.send(src, IngestCredit(group_index=0,
                                            watermark_seq=hw))

    def _handle_client_columns(self, src: Address, colrun) -> None:
        """Wire-sink handler: a whole ClientFrameBatch as SoA columns.
        The hot branch proposes the frame as ONE Phase2aRun whose value
        bytes are the clients' own wire bytes (LazyValueArray over the
        scanned segment -- re-encoding is a raw copy); inactive /
        Phase1 / refused-suffix conditions keep per-message
        semantics on the cold path."""
        n = len(colrun)
        if n == 0:
            return
        if isinstance(self.state, _Inactive):
            # One bounce per frame: every segment shares the sending
            # connection, and redirect discovery is per-client anyway.
            self.send(src, NotLeaderClient())
            return
        k = n
        admission = self.admission
        if admission is not None:
            k = admission.admit_up_to(n)
            if k < n:
                for address, reply in colrun.reject_entries(
                        k, admission.retry_after_ms(),
                        admission.last_reason):
                    self.send(address, reply)
            if k == 0:
                return
        if isinstance(self.state, _Phase1):
            self._admitted_backlog += k
            for command in colrun.commands(k):  # cold: Phase1 only
                self.state.pending_batches.append(
                    ClientRequestBatch(CommandBatch((command,))))
            return
        values = colrun.lazy_values(k)
        self._note_ingest(k, len(values.raw))
        path = self.transport.path_in
        if path is not None:
            cols = colrun.cols[:k]
            rows = np.flatnonzero(
                (cols[:, COL_PSEUDONYM] == path.pseudonym)
                & (cols[:, COL_ID] == path.client_id))
            self._note_path(path, int(rows[0]) if len(rows) else -1)
        self._propose_value_run(values)
        self._path = None

    def _handle_ingest_run(self, src: Address, run: IngestRun) -> None:
        """A disseminator's pre-batched run descriptor: assign a
        contiguous slot block and forward the pre-encoded values as one
        Phase2aRun -- the leader touches only run metadata (count, raw
        bytes). ``src`` is the batcher, so the inactive bounce returns
        the RUN for re-routing after leader discovery."""
        values = run.values
        n = len(values)
        if n == 0:
            return
        if isinstance(self.state, _Inactive):
            self.send(src, NotLeaderIngest(group_index=0, run=run))
            return
        # Credit the batcher's pipelining window: this run is consumed
        # on every non-bounce path below (proposed, Phase1-buffered, or
        # fully rejected back to clients). Accumulated per batcher,
        # flushed once in on_drain.
        hw = self._ingest_credit_hw.get(src)
        if hw is None or run.seq > hw:
            self._ingest_credit_hw[src] = run.seq
        k = n
        admission = self.admission
        if admission is not None:
            k = admission.admit_up_to(n)
            if k < n:
                reject_value_suffix(self.send, values, k, admission)
                if k == 0:
                    return
                view = value_view(values)
                values = (view.lazy_values(k) if view is not None
                          else tuple(values)[:k])
        if isinstance(self.state, _Phase1):
            self._admitted_backlog += k
            for value in tuple(values)[:k]:  # cold: Phase1 only
                self.state.pending_batches.append(
                    ClientRequestBatch(value))
            return
        self._note_ingest(k, len(getattr(values, "raw", b"")))
        self._propose_value_run(values)

    def _handle_client_request_batch(self, src: Address,
                                     batch: ClientRequestBatch) -> None:
        if isinstance(self.state, _Inactive):
            # Bounce the batch back so the batcher can re-route it
            # (Leader.scala:606-634).
            self.send(src, NotLeaderBatcher(client_request_batch=batch))
        elif not self._admit(batch, len(batch.batch.commands)):
            pass
        elif isinstance(self.state, _Phase1):
            self._admitted_backlog += len(batch.batch.commands)
            self.state.pending_batches.append(batch)
        else:
            self._process_client_request_batch(batch)

    def _handle_client_request_batch_run(
            self, src: Address, run: ClientRequestBatchRun) -> None:
        """A batcher's pass of batches: one slot a batch, proposed as
        one run (the ``_propose_value_run`` the arrays take). Off
        Phase 2 each batch goes the way a ClientRequestBatch goes: back
        to the batcher, or into Phase 1's pending batches."""
        values = run.values
        if not len(values):
            return
        if isinstance(self.state, _Inactive):
            for value in values:
                self.send(src, NotLeaderBatcher(
                    client_request_batch=ClientRequestBatch(value)))
            return
        if self.admission is not None and not self._admit(
                ClientRequestBatch(CommandBatch(tuple(
                    command for value in values
                    for command in value.commands))),
                sum(len(value.commands) for value in values)):
            return
        if isinstance(self.state, _Phase1):
            for value in values:
                self._admitted_backlog += len(value.commands)
                self.state.pending_batches.append(ClientRequestBatch(value))
            return
        path = self.transport.path_in
        if path is not None:
            # A batcher's path holds its command's place among the
            # batches in ``slot`` (batcher.py).
            self._note_path(path, path.slot if path.kind == "batcher"
                            else -1)
        self._propose_value_run(values)
        self._path = None

    def _handle_leader_info_request_client(self, src: Address, _) -> None:
        if not isinstance(self.state, _Inactive):
            self.send(src, LeaderInfoReplyClient(round=self.round))

    def _handle_leader_info_request_batcher(self, src: Address, _) -> None:
        if not isinstance(self.state, _Inactive):
            self.send(src, LeaderInfoReplyBatcher(round=self.round))

    def _handle_nack(self, src: Address, nack: Nack) -> None:
        if nack.round <= self.round:
            self.logger.debug(f"stale Nack in round {nack.round}; ignoring")
            return
        if isinstance(self.state, _Inactive):
            self.round = nack.round
        else:
            self.round = self.round_system.next_classic_round(self.index,
                                                              nack.round)
            self.leader_change(is_new_leader=True)

    def _handle_chosen_watermark(self, src: Address,
                                 msg: ChosenWatermark) -> None:
        self.chosen_watermark = max(self.chosen_watermark, msg.slot)
        if self.admission is not None:
            # Drain-granular release: the watermark advance IS the
            # signal that in-flight slots completed their quorums.
            self._sync_inflight()

    def _handle_recover(self, src: Address, recover: Recover) -> None:
        # Re-running Phase1 recovers every unchosen slot below some chosen
        # one (Leader.scala:698-722).
        if not isinstance(self.state, _Inactive):
            self.leader_change(is_new_leader=True)

    # --- reconfiguration (reconfig/, docs/RECONFIG.md) --------------------
    def _handle_reconfigure(self, src: Address,
                            msg: Reconfigure) -> None:
        """Start the leader-driven config-change flow: define epoch
        e+1 over ``msg.members`` with activation watermark ``next_slot``
        (in-flight runs below it drain in the old epoch), broadcast the
        round-tagged EpochCommit, and buffer new proposals until a
        write quorum of OLD-epoch acceptors has durably acked it."""
        if self.epochs is None:
            self.logger.warn(
                "Reconfigure ignored: epochs need a single non-flexible "
                "acceptor group")
            return
        if not isinstance(self.state, _Phase2):
            self.logger.debug("Reconfigure ignored outside Phase2 "
                              "(admin should retry at the leader)")
            return
        if self._epoch_change is not None:
            if not self._epoch_change.activated:
                self.logger.debug(
                    "Reconfigure ignored: a change is mid-activation")
                return
            # The previous change is ACTIVE and only chasing straggler
            # acks (possibly of dead members); the new change's commit
            # flow supersedes those resends.
            self._abort_epoch_change()
        current = self.epochs.current()
        members = tuple(msg.members)
        if members == current.members:
            return
        if self.next_slot < current.start_slot:
            # This leader adopted the current epoch but has not
            # proposed up to its activation watermark yet; a successor
            # epoch must start at or above it (epoch starts are
            # monotone). Let the admin retry once caught up.
            self.logger.debug("Reconfigure ignored: next_slot below "
                              "the current epoch's start")
            return
        try:
            config = EpochConfig(epoch=current.epoch + 1,
                                 start_slot=self.next_slot,
                                 f=self.config.f, members=members)
        except ValueError as e:
            self.logger.warn(f"Reconfigure rejected: {e}")
            return
        self._drive_epoch_change(config, predecessor=current,
                                 recommit=False)

    def _drive_epoch_change(self, config: EpochConfig,
                            predecessor: "EpochConfig | None",
                            recommit: bool) -> None:
        """Broadcast + resend one epoch's commit until the activation
        gate (f+1 of the PREDECESSOR's acceptors durably acked) opens;
        proposals buffer meanwhile (the handover window). Targets:
        both acceptor sets (old = the matchmakers, new = the set that
        must know its own era), every proxy leader (they route and
        count -- and their acks release stashed epoch-tagged runs),
        every peer leader (so a failover has the map before its Phase1
        even asks)."""
        commit = EpochCommit(epoch=config.epoch,
                             start_slot=config.start_slot,
                             f=config.f, round=self.round,
                             members=config.members)
        targets: dict = dict.fromkeys(
            predecessor.members if predecessor else ())
        targets.update(dict.fromkeys(config.members))
        targets.update(dict.fromkeys(self.config.proxy_leader_addresses))
        targets.update(dict.fromkeys(
            a for a in self.config.leader_addresses if a != self.address))

        def resend():
            change = self._epoch_change
            if change is None or change.config is not config:
                return
            for dst in change.targets:
                if dst not in change.acks:
                    self.send(dst, change.commit)
            timer.start()

        timer = self.timer("resendEpochCommit",
                           self.options.resend_epoch_commit_period_s,
                           resend)
        timer.start()
        self._epoch_change = _EpochChange(
            config=config, commit=commit, targets=set(targets),
            acks=set(), resend=timer, pending=[],
            started=time.perf_counter(), recommit=recommit)
        for dst in targets:
            self.send(dst, commit)

    def _ensure_epoch_durability(self, reporters) -> None:
        """Before this leader proposes into an ADOPTED newest epoch,
        its commit must be provably durable at f+1 of its
        predecessor's acceptors (else a future Phase1 could miss it
        and re-propose its slots under the old quorums). ``reporters``
        are the acceptors whose Phase1bs carried the epoch. Two proofs
        stand: the reporters already form the predecessor write quorum,
        or the chosen watermark is STRICTLY past the epoch's activation
        slot -- a slot chosen UNDER the epoch implies, inductively,
        that some gate-compliant leader activated it with the durable
        quorum (whose WALs outlive any crash). Proven: only the proxies
        need a gateless resync. Unproven: drive a GATED re-commit that
        buffers proposals until the predecessor quorum acks."""
        newest = self.epochs.current()
        pred = self.epochs.config(newest.epoch - 1)
        if pred is None or pred.has_write_quorum(reporters) \
                or self.chosen_watermark > newest.start_slot:
            self._start_epoch_sync()
            return
        self._drive_epoch_change(newest, predecessor=pred,
                                 recommit=True)

    def _start_epoch_sync(self) -> None:
        sync_commits = [
            EpochCommit(epoch=c.epoch, start_slot=c.start_slot, f=c.f,
                        round=self.round, members=c.members)
            for c in self.epochs.known()[1:]]
        pending = set(self.config.proxy_leader_addresses)

        def resend():
            sync = self._epoch_sync
            if sync is None or sync["commits"] is not sync_commits:
                return
            for dst in sync["pending"]:
                for commit in sync_commits:
                    self.send(dst, commit)
            timer.start()

        timer = self.timer("resendEpochSync",
                           self.options.resend_epoch_commit_period_s,
                           resend)
        timer.start()
        self._epoch_sync = {"epoch": sync_commits[-1].epoch,
                            "commits": sync_commits,
                            "pending": pending, "timer": timer}
        for dst in self.config.proxy_leader_addresses:
            for commit in sync_commits:
                self.send(dst, commit)

    def _stop_epoch_sync(self) -> None:
        if self._epoch_sync is not None:
            self._epoch_sync["timer"].stop()
            self._epoch_sync = None

    def _handle_epoch_ack(self, src: Address, ack: EpochAck) -> None:
        sync = self._epoch_sync
        if sync is not None and ack.epoch == sync["epoch"] \
                and ack.round == self.round:
            sync["pending"].discard(src)
            if not sync["pending"]:
                self._stop_epoch_sync()
        change = self._epoch_change
        if change is None or ack.epoch != change.config.epoch \
                or ack.round != self.round:
            return
        change.acks.add(src)
        if not change.activated:
            pred = self.epochs.config(change.config.epoch - 1)
            if pred is None or pred.has_write_quorum(change.acks):
                # COMMIT POINT: f+1 predecessor-epoch acceptors hold
                # the epoch WAL-durably -- any future leader's
                # old-epoch read quorum will discover it. Activate:
                # the buffered proposals open the new epoch's slots.
                try:
                    self.epochs.offer(change.config, self.round)
                except ValueError as e:
                    # The store moved under the change (a concurrent
                    # adoption): abort; clients resend the buffer.
                    self.logger.warn(f"epoch activation aborted: {e}")
                    self._abort_epoch_change()
                    return
                change.activated = True
                # Stage ``epoch-handover``: from the commit's first
                # send to here, the wait in which proposals buffer.
                metrics = self.transport.runtime_metrics
                if metrics is not None:
                    metrics.observe_stage(
                        "epoch-handover",
                        time.perf_counter() - change.started)
                self.metrics_epoch_changes.inc()
                self.metrics_epoch_buffered.inc(len(change.pending))
                # Post-activation the resends only need to reach the
                # parties that ROUTE by the epoch (proxies) and the
                # new members; stop chasing old-epoch/peer-leader
                # stragglers -- in the canonical repair the
                # reconfigured-OUT member is dead and would be pinged
                # forever.
                change.targets &= (
                    set(self.config.proxy_leader_addresses)
                    | set(change.config.members))
                pending, change.pending = change.pending, []
                if pending:
                    self._send_epoch_runs(tuple(pending))
        if change.activated and change.targets <= change.acks:
            change.resend.stop()
            self._epoch_change = None

    def _handle_epoch_commit(self, src: Address,
                             commit: EpochCommit) -> None:
        """A peer leader's commit broadcast: adopt the entry (so this
        leader's next Phase1 covers it without discovery) and ack so
        the committer's resends stop."""
        if self.epochs is None:
            return
        try:
            outcome = self.epochs.offer(
                EpochConfig(epoch=commit.epoch,
                            start_slot=commit.start_slot,
                            f=commit.f, members=commit.members),
                commit.round)
        except ValueError as e:
            self.logger.warn(f"peer EpochCommit rejected: {e}")
            return
        if outcome in ("new", "replaced", "dup"):
            self.send(src, EpochAck(epoch=commit.epoch,
                                    round=commit.round))
        if outcome in ("new", "replaced") \
                and isinstance(self.state, _Phase2) \
                and self._epoch_change is None:
            # An ACTIVE leader adopting a peer's epoch mid-Phase2: it
            # must not propose into the adopted epoch on the peer's
            # word alone -- gate on its own durable predecessor-quorum
            # proof exactly like the post-Phase1 path.
            self._ensure_epoch_durability(reporters=())


def _place(path, commands) -> int:
    """Where among ``commands`` the command of a commit path is, or
    -1."""
    for k, command in enumerate(commands):
        cid = command.command_id
        if (cid.client_pseudonym == path.pseudonym
                and cid.client_id == path.client_id):
            return k
    return -1

"""Mencius Batcher, Leader, ProxyLeader, and Acceptor.

Reference behavior: mencius/Batcher.scala:85-190, Leader.scala:130-870,
ProxyLeader.scala:31-420, Acceptor.scala:103-300.
"""

from __future__ import annotations

import dataclasses
import random

from sortedcontainers import SortedDict  # type: ignore[import-untyped]

from frankenpaxos_tpu.election.basic import (
    ElectionOptions,
    ElectionParticipant,
)
from frankenpaxos_tpu.protocols.mencius.common import (
    Chosen,
    ChosenNoopRange,
    ChosenRun,
    ChosenWatermark,
    ClientRequest,
    ClientRequestArray,
    ClientRequestBatch,
    CommandBatch,
    DistributionScheme,
    HighWatermark,
    LeaderInfoReplyBatcher,
    LeaderInfoReplyClient,
    LeaderInfoRequestBatcher,
    LeaderInfoRequestClient,
    MenciusConfig,
    Nack,
    NOOP,
    NotLeaderBatcher,
    NotLeaderClient,
    Phase1a,
    Phase1b,
    Phase1bSlotInfo,
    Phase2a,
    Phase2aNoopRange,
    Phase2aRun,
    Phase2b,
    Phase2bNoopRange,
    Phase2bRun,
    Recover,
)
from frankenpaxos_tpu.protocols.multipaxos.wire import (
    decode_value,
    decode_value_array,
    encode_value,
    encode_value_array,
)
from frankenpaxos_tpu.reconfig import (
    decode_epoch_config,
    encode_epoch_config,
    EpochAck,
    EpochCommit,
    EpochConfig,
    EpochStore,
    Reconfigure,
)
from frankenpaxos_tpu.roundsystem import ClassicRoundRobin
from frankenpaxos_tpu.runtime import Actor, Logger
from frankenpaxos_tpu.runtime.transport import Address, Transport
from frankenpaxos_tpu.wal import (
    DurableRole,
    WalEpoch,
    WalNoopRange,
    WalPromise,
    WalSnapshot,
    WalVote,
    WalVoteRun,
)


class MenciusBatcher(Actor):
    """(Batcher.scala:85-190): batch, then send to the current round's
    leader of a random leader group (Hash) or the colocated group."""

    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MenciusConfig,
                 batch_size: int = 1, seed: int = 0):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        self.batch_size = batch_size
        self.rng = random.Random(seed)
        self.index = (list(config.batcher_addresses).index(address)
                      if address in config.batcher_addresses else 0)
        # Known round per leader group.
        self.rounds = [0] * config.num_leader_groups
        self.growing_batch: list = []
        self.pending_resend_batches: list = []

    def _group_leader(self, group: int) -> Address:
        rs = ClassicRoundRobin(len(self.config.leader_addresses[group]))
        return self.config.leader_addresses[group][
            rs.leader(self.rounds[group])]

    def receive(self, src: Address, message) -> None:
        if isinstance(message, ClientRequest):
            self.growing_batch.append(message.command)
            if len(self.growing_batch) >= self.batch_size:
                if (self.config.distribution_scheme
                        == DistributionScheme.HASH):
                    group = self.rng.randrange(
                        self.config.num_leader_groups)
                else:
                    group = self.index % self.config.num_leader_groups
                self.send(self._group_leader(group), ClientRequestBatch(
                    CommandBatch(tuple(self.growing_batch))))
                self.growing_batch.clear()
        elif isinstance(message, NotLeaderBatcher):
            self.pending_resend_batches.append(
                (message.leader_group_index, message.client_request_batch))
            for leader in self.config.leader_addresses[
                    message.leader_group_index]:
                self.send(leader, LeaderInfoRequestBatcher())
        elif isinstance(message, LeaderInfoReplyBatcher):
            if message.round > self.rounds[message.leader_group_index]:
                self.rounds[message.leader_group_index] = message.round
            still_pending = []
            for group, batch in self.pending_resend_batches:
                if group == message.leader_group_index:
                    self.send(self._group_leader(group), batch)
                else:
                    still_pending.append((group, batch))
            self.pending_resend_batches = still_pending
        else:
            self.logger.fatal(f"unexpected batcher message {message!r}")


@dataclasses.dataclass
class _Phase1:
    # One dict per acceptor group of this leader group.
    phase1bs: list[dict[int, Phase1b]]
    pending_batches: list[ClientRequestBatch]
    # Slot to force-recover through phase 1, or -1 (Leader.scala:160-172).
    recover_slot: int
    resend_phase1as: object
    # Address-keyed Phase1bs + the in-flight Phase1a (reconfig: across
    # epochs, (group, index) coordinates can collide; addresses cannot).
    by_addr: dict = dataclasses.field(default_factory=dict)
    phase1a: object = None


@dataclasses.dataclass
class _EpochChange:
    """A Mencius epoch change in flight. Unlike MultiPaxos (whose
    proposals carry epoch tags and stash at a lagging proxy), Mencius
    runs stay untagged, so activation additionally gates on EVERY
    proxy leader's ack -- a proxy can then never mis-route a new-epoch
    run to the old set. The trade-off: a dead proxy blocks
    reconfiguration here, where MultiPaxos rides through
    (docs/RECONFIG.md)."""

    config: EpochConfig
    commit: EpochCommit
    targets: set
    acks: set
    resend: object
    pending: list  # buffered ClientRequestBatch
    activated: bool = False
    # True when re-driving an adopted epoch (post-failover / peer
    # broadcast); targets and gating then depend on whether the
    # predecessor-quorum durability was already PROVEN by Phase1bs.
    recommit: bool = False
    # Activation must (re-)establish f+1 predecessor-epoch durable
    # acks unless Phase1 already proved them (chaos-found: proposing
    # into an adopted-but-undurable epoch lets a later leader that
    # misses it re-propose its slots under the old quorums -- a second
    # chosen value).
    need_old_quorum: bool = True


class MenciusLeader(Actor):
    """(mencius/Leader.scala:130-870)."""

    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MenciusConfig,
                 resend_phase1as_period_s: float = 5.0,
                 send_high_watermark_every_n: int = 100,
                 send_noop_range_if_lagging_by: int = 100,
                 election_options: ElectionOptions = ElectionOptions(),
                 seed: int = 0,
                 admission_token_rate: float = 0.0,
                 admission_token_burst: float = 0.0,
                 admission_inflight_limit: int = 0,
                 admission_inbox_capacity: int = 0,
                 admission_inbox_policy: str = "reject",
                 admission_codel_target_s: float = 0.0,
                 admission_codel_interval_s: float = 0.1,
                 admission_retry_after_ms: int = 0):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        # paxload admission (serve/): built only when armed; the
        # in-flight measure is this group's owned-slot span
        # (next_slot - chosen_watermark) / stride, refreshed on
        # proposals and ChosenWatermark advances.
        from frankenpaxos_tpu.serve.admission import (
            AdmissionController,
            AdmissionOptions,
        )

        admission_options = AdmissionOptions(
            token_rate=admission_token_rate,
            token_burst=admission_token_burst,
            inflight_limit=admission_inflight_limit,
            inbox_capacity=admission_inbox_capacity,
            inbox_policy=admission_inbox_policy,
            codel_target_s=admission_codel_target_s,
            codel_interval_s=admission_codel_interval_s,
            retry_after_ms=admission_retry_after_ms)
        if admission_options.any_enabled():
            self.admission = AdmissionController(
                admission_options, role="mencius_leader",
                metrics=transport.runtime_metrics)
            transport.note_admission(address, self)
        self.rng = random.Random(seed)
        self.send_high_watermark_every_n = send_high_watermark_every_n
        self.send_noop_range_if_lagging_by = send_noop_range_if_lagging_by
        self.resend_phase1as_period_s = resend_phase1as_period_s
        self.group_index = next(
            g for g, group in enumerate(config.leader_addresses)
            if address in group)
        self.index = list(
            config.leader_addresses[self.group_index]).index(address)
        self.round_system = ClassicRoundRobin(
            len(config.leader_addresses[self.group_index]))
        # Which leader group owns which slot (Leader.scala:208-213).
        self.slot_system = ClassicRoundRobin(config.num_leader_groups)
        self.round = self.round_system.next_classic_round(0, -1)
        self.next_slot = self.group_index
        self.high_watermark = self.next_slot
        self.chosen_watermark = 0
        # Commands admitted while in _Phase1 (pending_batches, no slot
        # yet) -- counted by the in-flight resyncs (see the multipaxos
        # leader's _sync_inflight).
        self._admitted_backlog = 0
        self._commands_since_watermark_send = 0
        self._current_proxy_leader = self.rng.randrange(
            config.num_proxy_leaders)
        # paxfan descriptor pipelining: per-batcher drained-seq
        # high-water, flushed as ONE IngestCredit per batcher per
        # drain (the multipaxos leader's twin).
        self._ingest_credit_hw: dict = {}

        self.election = ElectionParticipant(
            config.leader_election_addresses[self.group_index][self.index],
            transport, logger,
            config.leader_election_addresses[self.group_index],
            initial_leader_index=0, options=election_options, seed=seed)
        self.election.register(
            lambda leader_index: self.leader_change(
                leader_index == self.index, recover_slot=-1))

        # Live reconfiguration (reconfig/): one epoch store per leader
        # group, over ITS owned slots -- supported when the group has
        # exactly one 2f+1 acceptor group (the run-pipeline shape).
        self.epochs: object = None
        if len(config.acceptor_addresses[self.group_index]) == 1:
            self.epochs = EpochStore.from_members(
                tuple(config.acceptor_addresses[self.group_index][0]),
                config.f)
        self._epoch_change: object = None

        self.state: object = ("inactive",)
        if self.index == 0:
            self.state = self._start_phase1(self.round,
                                            self.chosen_watermark, -1)

    # --- helpers ----------------------------------------------------------
    # Multi-acceptor-group striping is epoch-frozen (reconfig swaps
    # members within the single group; see the PAX110 pragmas on the
    # striping helpers below).
    @property
    def _my_acceptor_groups(self) -> tuple:  # paxlint: disable=PAX110
        return self.config.acceptor_addresses[self.group_index]

    def _acceptor_group_index_by_slot(self, slot: int) -> int:
        self.logger.check_eq(self.slot_system.leader(slot), self.group_index)
        return ((slot // self.config.num_leader_groups)
                % len(self._my_acceptor_groups))

    def _proxy_leader(self) -> Address:
        if self.config.distribution_scheme == DistributionScheme.HASH:
            return self.config.proxy_leader_addresses[
                self._current_proxy_leader]
        return self.config.proxy_leader_addresses[self.group_index]

    def _advance_proxy_leader(self) -> None:
        self._current_proxy_leader = (
            (self._current_proxy_leader + 1) % self.config.num_proxy_leaders)

    @staticmethod
    def _safe_value(phase1bs, slot: int):
        best_round, best_value = -1, None
        for phase1b in phase1bs:
            for info in phase1b.info:
                if info.slot == slot and info.vote_round > best_round:
                    best_round, best_value = info.vote_round, info.vote_value
        return NOOP if best_value is None else best_value

    def _phase1_epochs(self) -> list:
        return self.epochs.epochs_covering(self.chosen_watermark)

    def _start_phase1(self, round: int, chosen_watermark: int,
                      recover_slot: int) -> _Phase1:
        phase1a = Phase1a(round=round, chosen_watermark=chosen_watermark)
        if self.epochs is not None:
            # Per covered epoch, a thrifty read-quorum sample; resend
            # widens to every member (dict.fromkeys: deterministic
            # iteration under hash randomization).
            targets: dict = {}
            for config in self._phase1_epochs():
                targets.update(dict.fromkeys(self.rng.sample(
                    list(config.members), config.quorum_size)))
            for acceptor in targets:
                self.send(acceptor, phase1a)
        else:
            for group in self._my_acceptor_groups:
                for acceptor in self.rng.sample(list(group),
                                                self.config.quorum_size):
                    self.send(acceptor, phase1a)

        def resend():
            if self.epochs is not None:
                targets: dict = {}
                for config in self._phase1_epochs():
                    targets.update(dict.fromkeys(config.members))
                for acceptor in targets:
                    self.send(acceptor, phase1a)
            else:
                for group in self._my_acceptor_groups:
                    for acceptor in group:
                        self.send(acceptor, phase1a)
            timer.start()

        timer = self.timer("resendPhase1as", self.resend_phase1as_period_s,
                           resend)
        timer.start()
        # Fresh Phase1 = fresh (empty) pending backlog.
        self._admitted_backlog = 0
        return _Phase1(
            phase1bs=[{} for _ in self._my_acceptor_groups],
            pending_batches=[], recover_slot=recover_slot,
            resend_phase1as=timer, phase1a=phase1a)

    def _abort_epoch_change(self) -> None:
        change = self._epoch_change
        if change is None:
            return
        change.resend.stop()
        if change.pending:
            self.logger.debug(
                f"epoch change aborted with {len(change.pending)} "
                f"buffered batches (clients will resend)")
        self._epoch_change = None

    def leader_change(self, is_new_leader: bool, recover_slot: int) -> None:
        if isinstance(self.state, _Phase1):
            self.state.resend_phase1as.stop()
        self._abort_epoch_change()
        if not is_new_leader:
            self.state = ("inactive",)
            return
        self.round = self.round_system.next_classic_round(self.index,
                                                          self.round)
        self.state = self._start_phase1(self.round, self.chosen_watermark,
                                        recover_slot)

    def _process_batch(self, batch: ClientRequestBatch) -> None:
        self.logger.check_eq(self.state, ("phase2",))
        change = self._epoch_change
        if change is not None and not change.activated:
            change.pending.append(batch)
            return
        self.send(self._proxy_leader(),
                  Phase2a(slot=self.next_slot, round=self.round,
                          value=batch.batch))
        self._advance_proxy_leader()
        self.next_slot += self.config.num_leader_groups
        self._gossip_watermark(1)

    def _gossip_watermark(self, commands: int) -> None:
        # Periodically gossip our nextSlot so laggards can skip
        # (Leader.scala:455-480). A k-command run counts k commands.
        self._commands_since_watermark_send += commands
        if (self._commands_since_watermark_send
                >= self.send_high_watermark_every_n):
            self.send(self._proxy_leader(),
                      HighWatermark(next_slot=self.next_slot))
            self._commands_since_watermark_send = 0

    # --- paxingest (ingest/, docs/TRANSPORT.md) ---------------------------
    def _note_ingest(self, cmds: int, nbytes: int) -> None:
        metrics = self.transport.runtime_metrics
        if metrics is not None:
            metrics.ingest_batch(cmds, nbytes)

    def _propose_value_run(self, values) -> None:
        """Post-admission Phase2 proposal of one-value-per-OWNED-slot
        ``values`` (tuple or LazyValueArray forwarded raw): the shared
        tail of the array / wire-column / IngestRun paths."""
        self.logger.check_eq(self.state, ("phase2",))
        if len(self._my_acceptor_groups) > 1:
            # Strided runs need a single acceptor audience; per-slot
            # fallback (iterating decodes a lazy array -- this config
            # is off the zero-object path).
            for value in values:
                self._process_batch(ClientRequestBatch(value))
            return
        change = self._epoch_change
        if change is not None and not change.activated:
            change.pending.extend(
                ClientRequestBatch(value) for value in values)
            return
        stride = self.config.num_leader_groups
        k = len(values)
        self.send(self._proxy_leader(), Phase2aRun(
            start_slot=self.next_slot, stride=stride, round=self.round,
            values=values))
        self._advance_proxy_leader()
        self.next_slot += k * stride
        self._gossip_watermark(k)

    def _handle_ingest_run(self, src: Address, run) -> None:
        """A disseminator's pre-batched run descriptor: one strided
        Phase2aRun from pre-encoded values -- this leader touches only
        run metadata (see the multipaxos twin)."""
        from frankenpaxos_tpu.ingest.columns import (
            reject_value_suffix,
            value_view,
        )
        from frankenpaxos_tpu.ingest.messages import NotLeaderIngest

        values = run.values
        n = len(values)
        if n == 0:
            return
        if self.state == ("inactive",):
            self.send(src, NotLeaderIngest(group_index=self.group_index,
                                           run=run))
            return
        # Credit the batcher's pipelining window (see the multipaxos
        # twin): consumed on every non-bounce path below.
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

    def on_drain(self) -> None:
        """Flush accumulated pipelining credits: ONE watermark-granular
        IngestCredit per batcher per drain. Control-lane, so shedding
        never wedges the batchers' windows."""
        if self._ingest_credit_hw:
            from frankenpaxos_tpu.ingest.messages import IngestCredit

            credits, self._ingest_credit_hw = self._ingest_credit_hw, {}
            for src, hw in credits.items():
                self.send(src, IngestCredit(
                    group_index=self.group_index, watermark_seq=hw))

    def _process_request_array(self, array: ClientRequestArray) -> None:
        """A drain's worth of independent requests: assign each its own
        OWNED slot (next_slot, next_slot + G, ...) and propose the whole
        strided block as ONE Phase2aRun carrying the stride.

        Slots within one leader group also stripe over its acceptor
        groups ((slot // G) % num_acceptor_groups), so a strided run has
        a single acceptor audience only with one acceptor group; with
        more, fall back to per-slot proposals."""
        self.logger.check_eq(self.state, ("phase2",))
        if len(self._my_acceptor_groups) > 1:
            for command in array.commands:
                self._process_batch(
                    ClientRequestBatch(CommandBatch((command,))))
            return
        change = self._epoch_change
        if change is not None and not change.activated:
            # Handover window: buffer until the commit's activation
            # quorum (old-epoch write quorum + every proxy) is in.
            change.pending.extend(
                ClientRequestBatch(CommandBatch((c,)))
                for c in array.commands)
            return
        stride = self.config.num_leader_groups
        k = len(array.commands)
        self.send(self._proxy_leader(), Phase2aRun(
            start_slot=self.next_slot, stride=stride, round=self.round,
            values=tuple(CommandBatch((c,)) for c in array.commands)))
        self._advance_proxy_leader()
        self.next_slot += k * stride
        self._gossip_watermark(k)

    # --- handlers ---------------------------------------------------------
    def receive(self, src: Address, message) -> None:
        if isinstance(message, Phase1b):
            self._handle_phase1b(src, message)
        elif isinstance(message, ClientRequest):
            self._handle_client_request_batch(
                src, ClientRequestBatch(CommandBatch((message.command,))),
                from_client=True)
        elif isinstance(message, ClientRequestArray):
            self._handle_client_request_array(src, message)
        elif type(message).__name__ == "IngestRun":
            self._handle_ingest_run(src, message)
        elif isinstance(message, ClientRequestBatch):
            self._handle_client_request_batch(src, message,
                                              from_client=False)
        elif isinstance(message, HighWatermark):
            self._handle_high_watermark(src, message)
        elif isinstance(message, LeaderInfoRequestClient):
            if self.state != ("inactive",):
                self.send(src, LeaderInfoReplyClient(self.group_index,
                                                     self.round))
        elif isinstance(message, LeaderInfoRequestBatcher):
            if self.state != ("inactive",):
                self.send(src, LeaderInfoReplyBatcher(self.group_index,
                                                      self.round))
        elif isinstance(message, Nack):
            self._handle_nack(src, message)
        elif isinstance(message, ChosenWatermark):
            self.chosen_watermark = max(self.chosen_watermark, message.slot)
            if self.admission is not None:
                # Drain-granular release (see the multipaxos leader).
                self._sync_inflight()
        elif isinstance(message, Recover):
            self._handle_recover(src, message)
        elif isinstance(message, Reconfigure):
            self._handle_reconfigure(src, message)
        elif isinstance(message, EpochAck):
            self._handle_epoch_ack(src, message)
        elif isinstance(message, EpochCommit):
            self._handle_epoch_commit(src, message)
        else:
            self.logger.fatal(f"unexpected leader message {message!r}")

    def _adopt_epochs(self, commits) -> bool:
        """Merge Phase1b-discovered epoch entries (highest round per
        id); True when coverage changed."""
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
            return
        phase1 = self.state
        if phase1b.round != self.round:
            self.logger.check_lt(phase1b.round, self.round)
            return
        phase1.by_addr[src] = phase1b
        if self.epochs is not None and phase1b.epochs \
                and self._adopt_epochs(phase1b.epochs):
            members: dict = {}
            for config in self._phase1_epochs():
                members.update(dict.fromkeys(config.members))
            for acceptor in members:
                if acceptor not in phase1.by_addr:
                    self.send(acceptor, phase1.phase1a)
        if self.epochs is not None and self.epochs.multi_epoch:
            # Phase1-with-both-configs over this group's epochs.
            answered = set(phase1.by_addr)
            for config in self._phase1_epochs():
                if not config.has_read_quorum(answered):
                    return
        else:
            phase1.phase1bs[phase1b.group_index][phase1b.acceptor_index] \
                = phase1b
            if any(len(g) < self.config.quorum_size
                   for g in phase1.phase1bs):
                return

        max_slot = max(
            (info.slot for p1b in phase1.by_addr.values()
             for info in p1b.info),
            default=-1)
        max_slot = max(max_slot, phase1.recover_slot)
        self.logger.check(
            max_slot == -1
            or self.slot_system.leader(max_slot) == self.group_index)

        # Fill only the slots this group owns (Leader.scala:624-647).
        start = self.slot_system.next_classic_round(
            self.group_index, self.chosen_watermark - 1)
        multi = self.epochs is not None and self.epochs.multi_epoch
        for slot in range(start, max_slot + 1,
                          self.config.num_leader_groups):
            if multi:
                # Scan every answering acceptor: non-members of the
                # slot's epoch hold no votes for it, so this is a
                # superset of the right epoch's read quorum.
                voters = phase1.by_addr.values()
            else:
                voters = phase1.phase1bs[
                    self._acceptor_group_index_by_slot(slot)].values()
            self.send(self._proxy_leader(),
                      Phase2a(slot=slot, round=self.round,
                              value=self._safe_value(voters, slot)))
        # next_slot must clear the chosen watermark as well as the
        # voted max: Phase1bs report nothing below the watermark (all
        # chosen -- e.g. a predecessor's ChosenNoopRange), so with no
        # votes above it this would re-propose a pending command into
        # an already-Noop-chosen slot -- a second chosen value (found
        # by the WAL chaos soak's partition + leader-churn schedules).
        # Chosen slots >= the watermark are covered by quorum
        # intersection: some Phase1b carries their vote.
        self.next_slot = self.slot_system.next_classic_round(
            self.group_index, max(max_slot, self.chosen_watermark - 1))
        phase1.resend_phase1as.stop()
        self.state = ("phase2",)
        if multi:
            # Re-drive the newest epoch's commit before proposing into
            # it: untagged runs may only flow once every proxy provably
            # routes by the current epoch map, and the epoch's durable
            # predecessor-quorum must exist (proven by Phase1bs, or
            # re-established by the gated acks below). Pending batches
            # buffer through the activation window.
            newest = self.epochs.current()
            pred = self.epochs.config(newest.epoch - 1)
            reporters = {
                addr for addr, p1b in phase1.by_addr.items()
                if any(c.epoch == newest.epoch for c in p1b.epochs)}
            # Proof of durable commitment: a predecessor write quorum
            # among the reporters, or a slot chosen STRICTLY past the
            # activation watermark (chosen under the epoch => some
            # gate-compliant leader activated it; WALs outlive
            # crashes).
            proven = (pred is None
                      or pred.has_write_quorum(reporters)
                      or self.chosen_watermark > newest.start_slot)
            self._start_epoch_commit(newest, recommit=True,
                                     need_old_quorum=not proven)
        for batch in phase1.pending_batches:
            self._process_batch(batch)
        # The backlog just moved into the span; resync so it isn't
        # double-counted.
        self._admitted_backlog = 0
        if self.admission is not None:
            self._sync_inflight()

    def _sync_inflight(self) -> None:
        """Resync to the live in-flight measure: this group's
        owned-slot span plus the Phase1 backlog (see the multipaxos
        leader's _sync_inflight for why the backlog must count)."""
        stride = self.config.num_leader_groups
        self.admission.set_inflight(
            (self.next_slot - self.chosen_watermark) // stride
            + self._admitted_backlog)

    def _admit(self, message, n: int) -> bool:
        """paxload admission (the multipaxos leader's _admit, with
        this group's owned-slot span as the in-flight measure)."""
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

    def _handle_client_request_batch(self, src: Address,
                                     batch: ClientRequestBatch,
                                     from_client: bool) -> None:
        if self.state == ("inactive",):
            if from_client:
                self.send(src, NotLeaderClient(self.group_index))
            else:
                self.send(src, NotLeaderBatcher(self.group_index, batch))
        elif not self._admit(batch, len(batch.batch.commands)):
            pass
        elif isinstance(self.state, _Phase1):
            self._admitted_backlog += len(batch.batch.commands)
            self.state.pending_batches.append(batch)
        else:
            self._process_batch(batch)

    def _handle_client_request_array(self, src: Address,
                                     array: ClientRequestArray) -> None:
        """The client edge of the drain-granular run pipeline: every
        command gets its OWN owned slot (transport-level coalescing,
        not slot sharing -- see multipaxos ClientRequestArray)."""
        if not array.commands:
            return
        if self.state == ("inactive",):
            self.send(src, NotLeaderClient(self.group_index))
            return
        commands = array.commands
        if self.admission is not None:
            commands = self._admit_prefix(commands)
            if not commands:
                return
            if len(commands) < len(array.commands):
                array = ClientRequestArray(commands=commands)
        if isinstance(self.state, _Phase1):
            self._admitted_backlog += len(commands)
            for command in commands:
                self.state.pending_batches.append(
                    ClientRequestBatch(CommandBatch((command,))))
        else:
            self._process_request_array(array)

    def _admit_prefix(self, commands: tuple) -> tuple:
        """Partial admission for a coalesced array (see the multipaxos
        leader's _admit_prefix)."""
        admission = self.admission
        k = admission.admit_up_to(len(commands))
        if k < len(commands):
            from frankenpaxos_tpu.serve.admission import reject_replies_for

            for address, reply in reject_replies_for(
                    ClientRequestArray(commands=commands[k:]),
                    retry_after_ms=admission.retry_after_ms(),
                    reason=admission.last_reason):
                self.send(address, reply)
        return commands[:k]

    def _handle_high_watermark(self, src: Address,
                               message: HighWatermark) -> None:
        """Skip our slots if we're lagging (Leader.scala:717-770)."""
        self.high_watermark = max(self.next_slot, self.high_watermark)
        if message.next_slot <= self.high_watermark:
            return
        self.high_watermark = message.next_slot
        if self.state != ("phase2",):
            return
        if self.high_watermark - self.next_slot \
                < self.send_noop_range_if_lagging_by:
            return
        change = self._epoch_change
        if change is not None and not change.activated:
            # Mid-handover: don't skip slots whose epoch is still
            # committing; a later HighWatermark re-triggers.
            return
        end = self.slot_system.next_classic_round(self.group_index,
                                                  self.high_watermark)
        at = self.next_slot
        while at < end:
            seg_end = end
            if self.epochs is not None:
                # Split the skip range at epoch activation boundaries:
                # each segment's noop quorum is one epoch's.
                config = self.epochs.epoch_of_slot(at)
                nxt = self.epochs.config(config.epoch + 1)
                if nxt is not None:
                    seg_end = min(end, nxt.start_slot)
            self.send(self._proxy_leader(),
                      Phase2aNoopRange(slot_start_inclusive=at,
                                       slot_end_exclusive=seg_end,
                                       round=self.round))
            at = seg_end
        self.next_slot = end

    def _handle_nack(self, src: Address, nack: Nack) -> None:
        if nack.round <= self.round:
            return
        if self.state == ("inactive",):
            self.round = nack.round
        else:
            self.round = self.round_system.next_classic_round(self.index,
                                                              nack.round)
            self.leader_change(is_new_leader=True, recover_slot=-1)

    def _handle_recover(self, src: Address, recover: Recover) -> None:
        # A hole in one group's slots can only be fixed by that group
        # (Leader.scala:845-869); recover_slot threads through phase 1.
        if self.slot_system.leader(recover.slot) != self.group_index:
            return
        if self.state != ("inactive",):
            self.leader_change(is_new_leader=True,
                               recover_slot=recover.slot)

    # --- reconfiguration (reconfig/, docs/RECONFIG.md) --------------------
    def _start_epoch_commit(self, config: EpochConfig, recommit: bool,
                            need_old_quorum: bool = True) -> None:
        """Drive one EpochCommit to quorum: broadcast + resend until
        the activation set (f+1 PREDECESSOR-epoch members -- unless
        Phase1 already proved that durability -- and, because Mencius
        runs are untagged, EVERY proxy leader) has acked. ``recommit``
        re-drives an adopted epoch after failover: the store already
        holds it, but this leader must not propose into it before the
        proxies provably route by it and the durable discovery quorum
        provably exists."""
        commit = EpochCommit(epoch=config.epoch,
                             start_slot=config.start_slot,
                             f=config.f, round=self.round,
                             members=config.members)
        old = (self.epochs.config(config.epoch - 1)
               if need_old_quorum else None)
        targets: dict = dict.fromkeys(old.members if old else ())
        targets.update(dict.fromkeys(config.members))
        targets.update(dict.fromkeys(self.config.proxy_leader_addresses))
        targets.update(dict.fromkeys(
            a for a in self.config.leader_addresses[self.group_index]
            if a != self.address))

        def resend():
            change = self._epoch_change
            if change is None or change.config is not config:
                return
            for dst in change.targets:
                if dst not in change.acks:
                    self.send(dst, change.commit)
            timer.start()

        timer = self.timer("resendEpochCommit", 1.0, resend)
        timer.start()
        self._epoch_change = _EpochChange(
            config=config, commit=commit, targets=set(targets),
            acks=set(), resend=timer, pending=[], recommit=recommit,
            need_old_quorum=need_old_quorum)
        if recommit:
            self.epochs.offer(config, self.round)
        for dst in targets:
            self.send(dst, commit)

    def _handle_reconfigure(self, src: Address,
                            msg: Reconfigure) -> None:
        if self.epochs is None:
            self.logger.warn(
                "Reconfigure ignored: this leader group has multiple "
                "acceptor groups (epoch-frozen)")
            return
        if self.state != ("phase2",):
            self.logger.debug("Reconfigure ignored outside phase2")
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
        self._start_epoch_commit(config, recommit=False)

    def _epoch_activation_ready(self, change) -> bool:
        proxies = set(self.config.proxy_leader_addresses)
        if not proxies <= change.acks:
            return False
        if not change.need_old_quorum:
            return True  # durability already proven via Phase1bs
        old = self.epochs.config(change.config.epoch - 1)
        return old is None or old.has_write_quorum(change.acks)

    def _handle_epoch_ack(self, src: Address, ack: EpochAck) -> None:
        change = self._epoch_change
        if change is None or ack.epoch != change.config.epoch \
                or ack.round != self.round:
            return
        change.acks.add(src)
        if not change.activated and self._epoch_activation_ready(change):
            try:
                self.epochs.offer(change.config, self.round)
            except ValueError as e:
                self.logger.warn(f"epoch activation aborted: {e}")
                self._abort_epoch_change()
                return
            change.activated = True
            # Stop chasing old-epoch/peer-leader stragglers once
            # activated (the reconfigured-OUT member may be dead
            # forever); proxies and new members still matter.
            change.targets &= (set(self.config.proxy_leader_addresses)
                               | set(change.config.members))
            pending, change.pending = change.pending, []
            for batch in pending:
                self._process_batch(batch)
        if change.activated and change.targets <= change.acks:
            change.resend.stop()
            self._epoch_change = None

    def _handle_epoch_commit(self, src: Address,
                             commit: EpochCommit) -> None:
        """A peer leader's commit: adopt and ack."""
        if self.epochs is None:
            return
        if self.slot_system.leader(commit.start_slot) != self.group_index:
            return  # another group's epoch space
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
        if outcome in ("new", "replaced") and self.state == ("phase2",):
            # An active leader adopting a peer's epoch mid-phase2:
            # gate its own proposals on the durable-commit proof, as
            # in the post-Phase1 path (no Phase1b reporters here).
            newest = self.epochs.current()
            self._abort_epoch_change()
            self._start_epoch_commit(
                newest, recommit=True,
                need_old_quorum=(
                    self.chosen_watermark <= newest.start_slot))


class MenciusProxyLeader(Actor):
    """(mencius/ProxyLeader.scala:31-420)."""

    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MenciusConfig, seed: int = 0):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        self.rng = random.Random(seed)
        self.slot_system = ClassicRoundRobin(config.num_leader_groups)
        # (start, end, round) -> pending state; None once Done.
        self.states: dict[tuple, object] = {}
        # Pending strided Phase2aRuns: start -> [round, stride, values,
        # acks set]. One O(1) record per run; round-monotone (a
        # same-start higher-round run evicts its predecessor).
        self._runs: dict[int, list] = {}
        # Retired / evicted run rounds: start -> set of rounds, for the
        # stray-ack check.
        self._done_runs: dict[int, set] = {}
        # Reconfiguration (reconfig/): one epoch store per
        # single-acceptor-group leader group; quorums for its slots
        # resolve through it (PAX110) and acks count by ADDRESS
        # membership in the slot's epoch.
        self.epochs: dict[int, EpochStore] = {}
        for lg, groups in enumerate(config.acceptor_addresses):
            if len(groups) == 1:
                self.epochs[lg] = EpochStore.from_members(
                    tuple(groups[0]), config.f)

    # A GROUP-COUNT read for the striping arithmetic, not a membership
    # read; group counts are structural (reconfig swaps members within
    # the single group).
    def _acceptor_group_index_by_slot(self, leader_group: int,  # paxlint: disable=PAX110
                                      slot: int) -> int:
        return ((slot // self.config.num_leader_groups)
                % len(self.config.acceptor_addresses[leader_group]))

    def _epoch_for_slot(self, slot: int) -> "EpochConfig | None":
        store = self.epochs.get(self.slot_system.leader(slot))
        return store.epoch_of_slot(slot) if store is not None else None

    def _handle_epoch_commit(self, src: Address,
                             commit: EpochCommit) -> None:
        store = self.epochs.get(self.slot_system.leader(commit.start_slot))
        if store is None:
            return
        try:
            outcome = store.offer(
                EpochConfig(epoch=commit.epoch,
                            start_slot=commit.start_slot,
                            f=commit.f, members=commit.members),
                commit.round)
        except ValueError as e:
            self.logger.warn(f"EpochCommit rejected: {e}")
            return
        if outcome == "stale":
            return
        self.send(src, EpochAck(epoch=commit.epoch, round=commit.round))

    def receive(self, src: Address, message) -> None:
        if isinstance(message, HighWatermark):
            # Relay to every leader of every group
            # (ProxyLeader.scala:207-214).
            for leader in self.config.all_leaders():
                self.send(leader, message)
        elif isinstance(message, EpochCommit):
            self._handle_epoch_commit(src, message)
        elif isinstance(message, Phase2a):
            self._handle_phase2a(src, message)
        elif isinstance(message, Phase2b):
            self._handle_phase2b(src, message)
        elif isinstance(message, Phase2aRun):
            self._handle_phase2a_run(src, message)
        elif isinstance(message, Phase2bRun):
            self._handle_phase2b_run(src, message)
        elif isinstance(message, Phase2aNoopRange):
            self._handle_phase2a_noop_range(src, message)
        elif isinstance(message, Phase2bNoopRange):
            self._handle_phase2b_noop_range(src, message)
        else:
            self.logger.fatal(f"unexpected proxy leader message {message!r}")

    def _handle_phase2a(self, src: Address, phase2a: Phase2a) -> None:
        key = (phase2a.slot, phase2a.slot + 1, phase2a.round)
        if key in self.states:
            return
        config = self._epoch_for_slot(phase2a.slot)
        if config is not None:
            quorum = self.rng.sample(list(config.members),
                                     config.quorum_size)
        else:
            leader_group = self.slot_system.leader(phase2a.slot)
            # Multi-acceptor-group striping is epoch-frozen.
            # paxlint: disable=PAX110
            group = self.config.acceptor_addresses[leader_group][
                self._acceptor_group_index_by_slot(leader_group,
                                                   phase2a.slot)]
            quorum = self.rng.sample(list(group),
                                     self.config.quorum_size)
        for acceptor in quorum:
            self.send(acceptor, phase2a)
        self.states[key] = {"phase2a": phase2a, "phase2bs": {}}

    def _handle_phase2b(self, src: Address, phase2b: Phase2b) -> None:
        key = (phase2b.slot, phase2b.slot + 1, phase2b.round)
        state = self.states.get(key)
        if key not in self.states:
            self.logger.fatal(f"Phase2b for unknown {key}")
        if state is None or "phase2a" not in state:
            return  # Done or a noop-range entry
        config = self._epoch_for_slot(phase2b.slot)
        if config is not None:
            # Address-keyed membership counting: a replacement can
            # reuse a dead member's (group, index) coordinates, its
            # address it cannot.
            if src not in config.members:
                return
            state["phase2bs"][src] = phase2b
        else:
            state["phase2bs"][phase2b.acceptor_index] = phase2b
        if len(state["phase2bs"]) < self.config.quorum_size:
            return
        for replica in self.config.replica_addresses:
            self.send(replica, Chosen(slot=phase2b.slot,
                                      value=state["phase2a"].value))
        self.states[key] = None  # Done

    def _handle_phase2a_run(self, src: Address, run: Phase2aRun) -> None:
        """One write quorum for the whole strided run (one thrifty f+1
        sample, one forwarded message per member, one O(1) record).
        Slots of a strided leader-group run all live in ONE acceptor
        group only when that group is alone; otherwise decompose to the
        per-slot path (the leader already avoids sending runs then)."""
        k = len(run.values)
        if k == 0:
            return
        leader_group = self.slot_system.leader(run.start_slot)
        # paxlint: disable=PAX110 -- group-COUNT read (structural):
        # multi-group striping decomposes to the per-slot path.
        if len(self.config.acceptor_addresses[leader_group]) > 1:
            for i, value in enumerate(run.values):
                self._handle_phase2a(src, Phase2a(
                    slot=run.start_slot + i * run.stride,
                    round=run.round, value=value))
            return
        pending = self._runs.get(run.start_slot)
        if pending is not None:
            if run.round <= pending[0]:
                return  # duplicate (same or stale round)
            # Round-monotone eviction, mirroring the acceptor: the
            # higher-round re-proposal wins; remember the evicted round
            # so its straggler acks are recognized.
            self._done_runs.setdefault(run.start_slot,
                                       set()).add(pending[0])
        config = self._epoch_for_slot(run.start_slot)
        if config is not None:
            # A run never spans epochs (the leader buffers through the
            # handover), so the start slot's epoch covers it all.
            quorum = self.rng.sample(list(config.members),
                                     config.quorum_size)
        else:
            # paxlint: disable=PAX110 -- multi-group striping is frozen
            group = self.config.acceptor_addresses[leader_group][0]
            quorum = self.rng.sample(list(group),
                                     self.config.quorum_size)
        for acceptor in quorum:
            self.send(acceptor, run)  # encode the values ONCE
        self._runs[run.start_slot] = [run.round, run.stride,
                                      run.values, set()]

    def _handle_phase2b_run(self, src: Address,
                            phase2b: Phase2bRun) -> None:
        """Acceptors vote runs atomically, so quorum tracking is
        run-granular: count distinct acceptors, emit ONE ChosenRun per
        replica when f+1 acked."""
        run = self._runs.get(phase2b.start_slot)
        if run is None or run[0] != phase2b.round:
            if phase2b.round in self._done_runs.get(phase2b.start_slot,
                                                    ()):
                return  # straggler ack of a retired/evicted run
            if run is None:
                self.logger.fatal(
                    f"Phase2bRun for unknown run at {phase2b.start_slot}")
            return  # stale-round ack of a live re-proposed run
        round, stride, values, acks = run
        config = self._epoch_for_slot(phase2b.start_slot)
        if config is not None:
            if src not in config.members:
                return  # not this epoch's vote
            acks.add(src)
        else:
            acks.add(phase2b.acceptor_index)
        if len(acks) < self.config.quorum_size:
            return
        for replica in self.config.replica_addresses:
            self.send(replica, ChosenRun(start_slot=phase2b.start_slot,
                                         stride=stride, values=values))
        del self._runs[phase2b.start_slot]
        self._done_runs.setdefault(phase2b.start_slot, set()).add(round)

    def _handle_phase2a_noop_range(self, src: Address,
                                   phase2a: Phase2aNoopRange) -> None:
        key = (phase2a.slot_start_inclusive, phase2a.slot_end_exclusive,
               phase2a.round)
        if key in self.states:
            return
        leader_group = self.slot_system.leader(phase2a.slot_start_inclusive)
        config = self._epoch_for_slot(phase2a.slot_start_inclusive)
        if config is not None:
            # The leader splits skip ranges at epoch boundaries, so the
            # start slot's epoch covers the whole range.
            for acceptor in self.rng.sample(list(config.members),
                                            config.quorum_size):
                self.send(acceptor, phase2a)
            self.states[key] = {"noop_range": phase2a,
                                "phase2bs_per_group": [{}]}
            return
        # paxlint: disable=PAX110 -- multi-group striping is frozen
        for group in self.config.acceptor_addresses[leader_group]:
            for acceptor in self.rng.sample(list(group),
                                            self.config.quorum_size):
                self.send(acceptor, phase2a)
        self.states[key] = {
            "noop_range": phase2a,
            "phase2bs_per_group": [
                {} for _ in self.config.acceptor_addresses[leader_group]],
        }

    def _handle_phase2b_noop_range(self, src: Address,
                                   phase2b: Phase2bNoopRange) -> None:
        key = (phase2b.slot_start_inclusive, phase2b.slot_end_exclusive,
               phase2b.round)
        state = self.states.get(key)
        if key not in self.states:
            self.logger.fatal(f"Phase2bNoopRange for unknown {key}")
        if state is None or "noop_range" not in state:
            return
        config = self._epoch_for_slot(phase2b.slot_start_inclusive)
        if config is not None:
            if src not in config.members:
                return
            state["phase2bs_per_group"][0][src] = phase2b
        else:
            state["phase2bs_per_group"][phase2b.acceptor_group_index][
                phase2b.acceptor_index] = phase2b
        if any(len(g) < self.config.quorum_size
               for g in state["phase2bs_per_group"]):
            return
        for replica in self.config.replica_addresses:
            self.send(replica, ChosenNoopRange(
                slot_start_inclusive=phase2b.slot_start_inclusive,
                slot_end_exclusive=phase2b.slot_end_exclusive))
        self.states[key] = None  # Done


@dataclasses.dataclass
class _VoteState:
    vote_round: int
    vote_value: object


class MenciusAcceptor(Actor, DurableRole):
    """(mencius/Acceptor.scala:103-300)."""

    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MenciusConfig, wal=None):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        self.leader_group_index, self.acceptor_group_index, self.index = next(
            (lg, ag, i)
            for lg, groups in enumerate(config.acceptor_addresses)
            for ag, group in enumerate(groups)
            for i, a in enumerate(group)
            if a == address)
        self.round_system = ClassicRoundRobin(
            len(config.leader_addresses[self.leader_group_index]))
        self.slot_system = ClassicRoundRobin(config.num_leader_groups)
        self.round = -1
        self.states: SortedDict = SortedDict()
        # Run-voted state (Phase2aRun): start -> (count, stride, round,
        # values) -- one O(1) record per strided run. A slot's
        # authoritative vote is the HIGHEST round across both stores
        # (see _voted_info); the acceptor's monotone ``round`` makes
        # max-round resolution exact.
        self._voted_runs: SortedDict = SortedDict()
        self.max_voted_slot = -1
        # Committed reconfiguration epochs (reconfig/): epoch id ->
        # EpochCommit, round-monotone; WAL'd before the ack leaves and
        # reported in every Phase1b (the matchmaker role -- see the
        # multipaxos acceptor).
        self._epoch_commits: dict[int, EpochCommit] = {}
        # Durability (wal/): the multipaxos acceptor's group-commit
        # contract, strided -- promises/votes/runs/noop-ranges append
        # to the WAL and every dependent ack holds back until
        # on_drain's single fsync releases it (DurableRole).
        self._wal_init(wal)
        if wal is not None:
            self._wal_recover()

    # --- durability -------------------------------------------------------
    def _recover_from_wal(self) -> None:
        for record in self.wal.recover(self.logger):
            if isinstance(record, WalSnapshot):
                self.round = -1
                self.states.clear()
                self._voted_runs.clear()
                self.max_voted_slot = -1
            elif isinstance(record, WalPromise):
                self.round = max(self.round, record.round)
            elif isinstance(record, WalVote):
                self.round = max(self.round, record.round)
                self.states[record.slot] = _VoteState(
                    record.round, decode_value(record.value))
                self.max_voted_slot = max(self.max_voted_slot,
                                          record.slot)
            elif isinstance(record, WalVoteRun):
                self.round = max(self.round, record.round)
                self._store_run(record.start_slot, record.stride,
                                record.round,
                                decode_value_array(record.values))
            elif isinstance(record, WalNoopRange):
                self.round = max(self.round, record.round)
                self._store_noop_range(record.slot_start_inclusive,
                                       record.slot_end_exclusive,
                                       record.round)
            elif isinstance(record, WalEpoch):
                epoch, start, f, rnd, members = decode_epoch_config(
                    record.payload)
                known = self._epoch_commits.get(epoch)
                if known is None or rnd > known.round:
                    self._epoch_commits[epoch] = EpochCommit(
                        epoch=epoch, start_slot=start, f=f, round=rnd,
                        members=members)
            else:
                self.logger.fatal(
                    f"unexpected acceptor WAL record {record!r}")

    def _wal_compact(self) -> None:
        records = [WalPromise(round=self.round)]
        for epoch in sorted(self._epoch_commits):
            c = self._epoch_commits[epoch]
            records.append(WalEpoch(payload=encode_epoch_config(
                c.epoch, c.start_slot, c.f, c.round, c.members)))
        for start, (count, stride, rnd, values) in \
                self._voted_runs.items():
            records.append(WalVoteRun(
                start_slot=start, stride=stride, round=rnd,
                values=encode_value_array(values)))
        for slot, vs in self.states.items():
            records.append(WalVote(
                slot=slot, round=vs.vote_round,
                value=encode_value(vs.vote_value)))
        self.wal.compact(WalSnapshot(payload=b""), records)

    def on_drain(self) -> None:
        self._wal_drain()  # group commit, then release the held acks

    def _nack_leader(self, round: int, slot: int) -> Address:
        return self.config.leader_addresses[self.slot_system.leader(slot)][
            self.round_system.leader(round)]

    def receive(self, src: Address, message) -> None:
        if isinstance(message, Phase1a):
            self._handle_phase1a(src, message)
        elif isinstance(message, Phase2a):
            self._handle_phase2a(src, message)
        elif isinstance(message, Phase2aRun):
            self._handle_phase2a_run(src, message)
        elif isinstance(message, Phase2aNoopRange):
            self._handle_phase2a_noop_range(src, message)
        elif isinstance(message, EpochCommit):
            self._handle_epoch_commit(src, message)
        else:
            self.logger.fatal(f"unexpected acceptor message {message!r}")

    def _handle_epoch_commit(self, src: Address,
                             commit: EpochCommit) -> None:
        """The matchmaker write (see the multipaxos acceptor): store
        round-monotonically, WAL, ack after the group commit."""
        if commit.round < self.round:
            self.send(src, Nack(round=self.round))
            return
        known = self._epoch_commits.get(commit.epoch)
        if known is None or commit.round > known.round:
            self._epoch_commits[commit.epoch] = commit
            if self.wal is not None and known != commit:
                self.wal.append(WalEpoch(payload=encode_epoch_config(
                    commit.epoch, commit.start_slot, commit.f,
                    commit.round, commit.members)))
        elif known is not None and commit.round == known.round \
                and known != commit:
            self.logger.fatal(
                f"conflicting EpochCommits at one round: {known!r} "
                f"vs {commit!r}")
        self._wal_send(src, EpochAck(epoch=commit.epoch,
                                     round=commit.round))

    def _handle_phase1a(self, src: Address, phase1a: Phase1a) -> None:
        if phase1a.round < self.round:
            self.send(src, Nack(round=self.round))
            return
        if self.wal is not None and phase1a.round > self.round:
            self.wal.append(WalPromise(round=phase1a.round))
        self.round = phase1a.round
        self._wal_send(src, Phase1b(group_index=self.acceptor_group_index,
                                    acceptor_index=self.index,
                                    round=self.round,
                                    info=self._voted_info(
                                        phase1a.chosen_watermark),
                                    epochs=tuple(
                                        self._epoch_commits[e]
                                        for e in sorted(
                                            self._epoch_commits))))

    def _voted_info(self, minimum: int) -> tuple:
        """Every voted slot >= ``minimum`` with its HIGHEST-round vote,
        merging the per-slot store and the strided run store (a
        failover that ignored run votes would recover Noop over
        accepted values -- data loss). Recovery-only cold path: runs
        expand per slot here and nowhere else."""
        best: dict[int, tuple] = {
            slot: (self.states[slot].vote_round,
                   self.states[slot].vote_value)
            for slot in self.states.irange(minimum=minimum)}
        for start, (count, stride, rnd, values) in \
                self._voted_runs.items():
            if start + (count - 1) * stride < minimum:
                continue
            for i in range(count):
                slot = start + i * stride
                if slot < minimum:
                    continue
                cur = best.get(slot)
                if cur is None or rnd > cur[0]:
                    best[slot] = (rnd, values[i])
        return tuple(
            Phase1bSlotInfo(slot=slot, vote_round=rnd, vote_value=value)
            for slot, (rnd, value) in sorted(best.items()))

    def _handle_phase2a(self, src: Address, phase2a: Phase2a) -> None:
        if phase2a.round < self.round:
            self.send(self._nack_leader(phase2a.round, phase2a.slot),
                      Nack(round=self.round))
            return
        self.round = phase2a.round
        self.states[phase2a.slot] = _VoteState(self.round, phase2a.value)
        self.max_voted_slot = max(self.max_voted_slot, phase2a.slot)
        if self.wal is not None:
            self.wal.append(WalVote(
                slot=phase2a.slot, round=self.round,
                value=encode_value(phase2a.value)))
        self._wal_send(src, Phase2b(group_index=self.acceptor_group_index,
                                    acceptor_index=self.index,
                                    slot=phase2a.slot, round=self.round))

    def _handle_phase2a_run(self, src: Address, run: Phase2aRun) -> None:
        """A whole strided proposal run in one O(1) update: one round
        check, one run record, one Phase2bRun ack -- the per-drain
        shape of the per-slot _handle_phase2a."""
        if run.round < self.round:
            self.send(self._nack_leader(run.round, run.start_slot),
                      Nack(round=self.round))
            return
        self.round = run.round
        count = self._store_run(run.start_slot, run.stride, run.round,
                                run.values)
        if self.wal is not None:
            # A raw copy of the inbound lazy value segment, never a
            # re-materialization.
            self.wal.append(WalVoteRun(
                start_slot=run.start_slot, stride=run.stride,
                round=run.round,
                values=encode_value_array(run.values)))
        self._wal_send(src, Phase2bRun(
            acceptor_group_index=self.acceptor_group_index,
            acceptor_index=self.index, start_slot=run.start_slot,
            count=count, stride=run.stride, round=run.round))

    def _store_run(self, start_slot: int, stride: int, round: int,
                   values) -> int:
        """Merge one strided voted run into the run store; returns the
        run's count. Shared by the live Phase2aRun handler and WAL
        replay so truncation-tail semantics cannot drift."""
        count = len(values)
        old = self._voted_runs.get(start_slot)
        self._voted_runs[start_slot] = (count, stride, round, values)
        if old is not None and old[1] == stride and old[0] > count:
            # Same-start truncation (the multipaxos acceptor's tail
            # fix, strided): reinsert the longer predecessor's
            # non-overlapped voted tail so Phase1 recovery keeps it.
            old_count, old_stride, old_round, old_values = old
            tail_start = start_slot + count * stride
            if self._voted_runs.get(tail_start) is None:
                self._voted_runs[tail_start] = (
                    old_count - count, stride, old_round,
                    old_values[count:])
            else:
                for i in range(count, old_count):
                    slot = start_slot + i * stride
                    cur = self.states.get(slot)
                    if cur is None or cur.vote_round < old_round:
                        self.states[slot] = _VoteState(old_round,
                                                       old_values[i])
        self.max_voted_slot = max(
            self.max_voted_slot,
            start_slot + (count - 1) * stride)
        return count

    def _handle_phase2a_noop_range(self, src: Address,
                                   phase2a: Phase2aNoopRange) -> None:
        """Vote noop for every slot in the range owned by this acceptor
        group (Acceptor.scala:237-293)."""
        if phase2a.round < self.round:
            self.send(self._nack_leader(phase2a.round,
                                        phase2a.slot_start_inclusive),
                      Nack(round=self.round))
            return
        self.round = phase2a.round
        self._store_noop_range(phase2a.slot_start_inclusive,
                               phase2a.slot_end_exclusive, self.round)
        if self.wal is not None:
            # One O(1) record for the whole range; replay re-derives
            # the owned slots from the (restart-stable) config.
            self.wal.append(WalNoopRange(
                slot_start_inclusive=phase2a.slot_start_inclusive,
                slot_end_exclusive=phase2a.slot_end_exclusive,
                round=self.round))
        self._wal_send(src, Phase2bNoopRange(
            acceptor_group_index=self.acceptor_group_index,
            acceptor_index=self.index,
            slot_start_inclusive=phase2a.slot_start_inclusive,
            slot_end_exclusive=phase2a.slot_end_exclusive,
            round=self.round))

    def _store_noop_range(self, start_inclusive: int, end_exclusive: int,
                          round: int) -> None:
        """Vote noop for every slot this acceptor group owns in the
        range. Shared by the live handler and WAL replay."""
        num_groups = len(
            self.config.acceptor_addresses[self.leader_group_index])
        stride = self.config.num_leader_groups * num_groups
        start = start_inclusive
        while (start < end_exclusive
               and ((start // self.config.num_leader_groups) % num_groups)
               != self.acceptor_group_index):
            start += self.config.num_leader_groups
        for slot in range(start, end_exclusive, stride):
            self.states[slot] = _VoteState(round, NOOP)

"""Mencius Replica, ProxyReplica, and Client.

Reference behavior: mencius/Replica.scala:151-560 (BufferMap log,
Chosen + ChosenNoopRange, in-order executeLog, recover timer on holes),
mencius/ProxyReplica.scala, mencius/Client.scala (per-leader-group round
tracking).
"""

from __future__ import annotations

import dataclasses
import random
import struct
from typing import Callable, Optional

from frankenpaxos_tpu.protocols.mencius.common import (
    Chosen,
    ChosenNoopRange,
    ChosenRun,
    ChosenWatermark,
    ClientReply,
    ClientReplyArray,
    ClientReplyBatch,
    ClientRequest,
    ClientRequestArray,
    Command,
    CommandBatch,
    CommandId,
    DistributionScheme,
    LeaderInfoReplyClient,
    LeaderInfoRequestClient,
    MenciusConfig,
    Noop,
    NotLeaderClient,
    Recover,
)
from frankenpaxos_tpu.protocols.multipaxos.wire import (
    _put_address,
    _put_bytes,
    _take_address,
    _take_bytes,
    decode_value_array,
    encode_value_array,
)
from frankenpaxos_tpu.roundsystem import ClassicRoundRobin
from frankenpaxos_tpu.runs.client import RetryAdmissionMixin, StagedWriteMixin
from frankenpaxos_tpu.runs.records import log_chosen_values, wal_log_chosen_run
from frankenpaxos_tpu.runs.routing import (
    pick_array_destination,
    pick_request_destination,
)
from frankenpaxos_tpu.runtime import Actor, FakeCollectors, Logger
from frankenpaxos_tpu.runtime.transport import Address, Transport
from frankenpaxos_tpu.serve.messages import Rejected
from frankenpaxos_tpu.statemachine import StateMachine
from frankenpaxos_tpu.utils import BufferMap
from frankenpaxos_tpu.wal import (
    DurableRole,
    WalChosenRun,
    WalNoopRange,
    WalSnapshot,
)


class MenciusReplica(Actor, DurableRole):
    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, state_machine: StateMachine,
                 config: MenciusConfig, log_grow_size: int = 5000,
                 send_chosen_watermark_every_n: int = 100,
                 recover_min_period_s: float = 5.0,
                 recover_max_period_s: float = 10.0,
                 unsafe_dont_recover: bool = False, seed: int = 0,
                 wal=None, collectors=None):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        # Slots newly chosen here, by what they hold: a command batch
        # (of a ChosenRun or a Chosen) or a noop (a ChosenNoopRange, or
        # a recovered Noop).
        chosen = (collectors or FakeCollectors()).counter(
            "mencius_replica_chosen_slots_total", labels=("kind",))
        self.metrics_chosen_commands = chosen.labels("command")
        self.metrics_chosen_noops = chosen.labels("noop")
        self.state_machine = state_machine
        self.rng = random.Random(seed)
        self.send_chosen_watermark_every_n = send_chosen_watermark_every_n
        self.index = list(config.replica_addresses).index(address)
        self.slot_system = ClassicRoundRobin(config.num_leader_groups)
        self.log_grow_size = log_grow_size
        self.log: BufferMap = BufferMap(log_grow_size)
        self.executed_watermark = 0
        self._wm_dirty = False  # executed advanced since last drain
        self.num_chosen = 0
        self.high_watermark = -1
        self.client_table: dict[tuple, tuple[int, bytes]] = {}
        self.recovering_slot: Optional[int] = None
        # Durability (wal/): the multipaxos replica's group-commit
        # contract, strided (see protocols/multipaxos/replica.py).
        self._wal_init(wal)
        self.recover_timer = None
        if wal is not None:
            self._wal_recover()
        if not unsafe_dont_recover:
            self.recover_timer = self.timer(
                "recover",
                self.rng.uniform(recover_min_period_s, recover_max_period_s),
                self._recover)
            if wal is not None and self.executed_watermark < self.num_chosen:
                self.recovering_slot = self.executed_watermark
                self.recover_timer.start()

    # --- durability -------------------------------------------------------
    def _snapshot_payload(self) -> bytes:
        out = bytearray()
        out += struct.pack("<qq", self.executed_watermark,
                           self.high_watermark)
        _put_bytes(out, self.state_machine.to_bytes())
        out += struct.pack("<i", len(self.client_table))
        for (address, pseudonym), (client_id, result) in \
                self.client_table.items():
            _put_address(out, address)
            out += struct.pack("<qq", pseudonym, client_id)
            _put_bytes(out, result)
        return bytes(out)

    def _restore_snapshot(self, payload: bytes) -> None:
        watermark, high = struct.unpack_from("<qq", payload, 0)
        sm_bytes, at = _take_bytes(payload, 16)
        (n,) = struct.unpack_from("<i", payload, at)
        at += 4
        table: dict = {}
        for _ in range(n):
            address, at = _take_address(payload, at)
            pseudonym, client_id = struct.unpack_from("<qq", payload, at)
            result, at = _take_bytes(payload, at + 16)
            table[(address, pseudonym)] = (client_id, result)
        self.state_machine.from_bytes(sm_bytes)
        self.executed_watermark = watermark
        self.num_chosen = watermark
        self.high_watermark = high
        self.client_table = table
        self.log.garbage_collect(watermark)

    def _recover_from_wal(self) -> None:
        for record in self.wal.recover(self.logger):
            if isinstance(record, WalSnapshot):
                self.log = BufferMap(self.log_grow_size)
                self.executed_watermark = 0
                self.num_chosen = 0
                self.high_watermark = -1
                self.client_table = {}
                self._restore_snapshot(record.payload)
            elif isinstance(record, WalChosenRun):
                self._log_chosen(
                    record.start_slot, record.stride,
                    decode_value_array(record.values))
            elif isinstance(record, WalNoopRange):
                self._log_noop_range(record.slot_start_inclusive,
                                     record.slot_end_exclusive)
            else:
                self.logger.fatal(
                    f"unexpected replica WAL record {record!r}")
        self._execute_log()  # replies discarded; clients resend

    def _log_chosen(self, start_slot: int, stride: int, values) -> int:
        """Put a strided run of chosen values into the log
        (runs/records.py); returns how many were new. Shared by the
        live handlers and WAL replay."""
        new, high = log_chosen_values(self.log, self.executed_watermark,
                                      start_slot, stride, values)
        if high >= 0:
            self.high_watermark = max(self.high_watermark, high)
        self.num_chosen += new
        return new

    def _log_noop_range(self, start_inclusive: int,
                        end_exclusive: int) -> int:
        new = 0
        for slot in range(start_inclusive, end_exclusive,
                          self.config.num_leader_groups):
            if slot >= self.executed_watermark \
                    and self.log.get(slot) is None:
                self.log.put(slot, Noop())
                new += 1
        self.num_chosen += new
        return new

    def _wal_compact(self) -> None:
        records = []
        for slot, value in self.log.items(start=self.executed_watermark):
            records.append(WalChosenRun(
                start_slot=slot, stride=1,
                values=encode_value_array((value,))))
        self.wal.compact(WalSnapshot(payload=self._snapshot_payload()),
                         records)
        self.log.garbage_collect(self.executed_watermark)

    def on_drain(self) -> None:
        # Drain-granular watermark tail (paxload; see the multipaxos
        # replica): without it, a quiet pipeline leaves the leaders'
        # watermark view up to N-1 slots stale and a watermark-tied
        # admission budget wedges shut.
        if (self._wm_dirty
                and self.executed_watermark
                % self.send_chosen_watermark_every_n
                and self.executed_watermark % self.config.num_replicas
                == self.index):
            self._send_chosen_watermark()
        self._wm_dirty = False
        self._wal_drain()  # group commit, then release the held replies

    def _send_chosen_watermark(self) -> None:
        watermark = ChosenWatermark(slot=self.executed_watermark)
        proxy = self._proxy_replica()
        if proxy is not None:
            self._wal_send(proxy, watermark)
        else:
            for group in self.config.leader_addresses:
                for leader in group:
                    self._wal_send(leader, watermark)

    def _proxy_replica(self) -> Optional[Address]:
        if not self.config.proxy_replica_addresses:
            return None
        if self.config.distribution_scheme == DistributionScheme.HASH:
            return self.config.proxy_replica_addresses[
                self.rng.randrange(self.config.num_proxy_replicas)]
        return self.config.proxy_replica_addresses[
            self.index % self.config.num_proxy_replicas]

    def _send_to_owning_leaders(self, message, slot: int) -> None:
        proxy = self._proxy_replica()
        if proxy is not None:
            self.send(proxy, message)
            return
        for leader in self.config.leader_addresses[
                self.slot_system.leader(slot)]:
            self.send(leader, message)

    def _recover(self) -> None:
        self.send_recover(self.executed_watermark)
        self.recover_timer.start()

    def send_recover(self, slot: int) -> None:
        self._send_to_owning_leaders(Recover(slot=slot), slot)

    def _execute_command(self, slot: int, command: Command,
                         replies: list[ClientReply]) -> None:
        cid = command.command_id
        key = (cid.client_address, cid.client_pseudonym)
        cached = self.client_table.get(key)
        if cached is not None:
            largest_id, cached_result = cached
            if cid.client_id < largest_id:
                return
            if cid.client_id == largest_id:
                replies.append(ClientReply(cid, slot, cached_result))
                return
        result = self.state_machine.run(command.command)
        self.client_table[key] = (cid.client_id, result)
        if slot % self.config.num_replicas == self.index:
            replies.append(ClientReply(cid, slot, result))

    def _execute_log(self) -> list[ClientReply]:
        replies: list[ClientReply] = []
        while True:
            value = self.log.get(self.executed_watermark)
            if value is None:
                return replies
            slot = self.executed_watermark
            if isinstance(value, CommandBatch):
                for command in value.commands:
                    self._execute_command(slot, command, replies)
            self.executed_watermark += 1
            self._wm_dirty = True
            every_n = self.send_chosen_watermark_every_n
            if (self.executed_watermark % every_n == 0
                    and (self.executed_watermark // every_n)
                    % self.config.num_replicas == self.index):
                self._send_chosen_watermark()

    def _after_choose(self, coalesce_replies: bool = False) -> None:
        replies = self._execute_log()
        if replies:
            proxy = self._proxy_replica()
            if proxy is not None:
                self._wal_send(proxy,
                               ClientReplyBatch(batch=tuple(replies)))
            elif coalesce_replies and len(replies) > 1:
                # Run-pipeline drains ship each client ONE reply array
                # instead of one ClientReply per command.
                by_client: dict = {}
                for r in replies:
                    cid = r.command_id
                    by_client.setdefault(cid.client_address, []).append(
                        (cid.client_pseudonym, cid.client_id, r.slot,
                         r.result))
                for address, entries in by_client.items():
                    self._wal_send(address,
                                   ClientReplyArray(entries=tuple(entries)))
            else:
                for reply in replies:
                    self._wal_send(reply.command_id.client_address, reply)
        # Hole-recovery timer management (Replica.scala:432-462).
        if self.recover_timer is None:
            return
        has_hole = self.num_chosen != self.executed_watermark
        if self.recovering_slot is None and has_hole:
            self.recovering_slot = self.executed_watermark
            self.recover_timer.start()
        elif self.recovering_slot is not None and has_hole:
            if self.recovering_slot != self.executed_watermark:
                self.recovering_slot = self.executed_watermark
                self.recover_timer.reset()
        elif self.recovering_slot is not None and not has_hole:
            self.recovering_slot = None
            self.recover_timer.stop()

    def receive(self, src: Address, message) -> None:
        if isinstance(message, Chosen):
            if self._log_chosen(message.slot, 1, (message.value,)) == 0:
                return
            (self.metrics_chosen_noops if isinstance(message.value, Noop)
             else self.metrics_chosen_commands).inc()
            if self.wal is not None:
                self.wal.append(WalChosenRun(
                    start_slot=message.slot, stride=1,
                    values=encode_value_array((message.value,))))
            self._after_choose()
        elif isinstance(message, ChosenRun):
            self._handle_chosen_run(message)
        elif isinstance(message, ChosenNoopRange):
            new = self._log_noop_range(message.slot_start_inclusive,
                                       message.slot_end_exclusive)
            if new:
                self.metrics_chosen_noops.inc(new)
            if new and self.wal is not None:
                self.wal.append(WalNoopRange(
                    slot_start_inclusive=message.slot_start_inclusive,
                    slot_end_exclusive=message.slot_end_exclusive,
                    round=0))
            self._after_choose()
        else:
            self.logger.fatal(f"unexpected replica message {message!r}")

    def _handle_chosen_run(self, run: ChosenRun) -> None:
        """A strided drain of chosen values in one message: log the
        whole run, execute once, coalesce replies per client."""
        new = self._log_chosen(run.start_slot, run.stride, run.values)
        if new == 0:
            return
        self.metrics_chosen_commands.inc(new)
        if self.wal is not None:
            # Common case: every slot new -> one raw-copy segment
            # record; partial overlap falls back to per-new-slot
            # records (runs/records.py).
            wal_log_chosen_run(self.wal, self.log.get, run.start_slot,
                               run.stride, run.values,
                               all_new=(new == len(run.values)),
                               encode=encode_value_array)
        self._after_choose(coalesce_replies=True)


class MenciusProxyReplica(Actor):
    """(mencius/ProxyReplica.scala): unbatch replies; route watermarks to
    all leaders and Recovers to the owning group."""

    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MenciusConfig):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        self.slot_system = ClassicRoundRobin(config.num_leader_groups)

    def receive(self, src: Address, message) -> None:
        if isinstance(message, ClientReplyBatch):
            for reply in message.batch:
                self.send(reply.command_id.client_address, reply)
        elif isinstance(message, ChosenWatermark):
            for leader in self.config.all_leaders():
                self.send(leader, message)
        elif isinstance(message, Recover):
            for leader in self.config.leader_addresses[
                    self.slot_system.leader(message.slot)]:
                self.send(leader, message)
        else:
            self.logger.fatal(f"unexpected proxy replica message {message!r}")


@dataclasses.dataclass
class _PendingWrite:
    id: int
    command: bytes
    callback: Callable[[bytes], None]
    resend: object
    attempts: int = 0
    backoff_pending: bool = False


class MenciusClient(RetryAdmissionMixin, StagedWriteMixin, Actor):
    """(mencius/Client.scala): like the MultiPaxos client, but tracks a
    round per leader group and targets a random group per request."""

    def __init__(self, address: Address, transport: Transport,
                 logger: Logger, config: MenciusConfig,
                 resend_period_s: float = 10.0,
                 coalesce_writes: bool = False, seed: int = 0,
                 retry_budget: int = 0, backoff=None):
        super().__init__(address, transport, logger)
        config.check_valid()
        self.config = config
        self.rng = random.Random(seed)
        self.resend_period_s = resend_period_s
        # runs/ retry discipline (serve/backoff.py): 0 = unlimited
        # resends, the pre-paxload behavior; see multipaxos
        # ClientOptions.retry_budget for the contract.
        from frankenpaxos_tpu.serve.backoff import Backoff

        self._retry_budget = retry_budget
        self._retry_backoff = backoff or Backoff()
        # Coalesce this event-loop pass's writes into ONE
        # ClientRequestArray to a random group's leader (each command
        # still gets its own owned slot there). Flushed by on_drain /
        # flush_writes (runs/client.py); resends still go per-request.
        self.coalesce_writes = coalesce_writes
        self.rounds = [0] * config.num_leader_groups
        self.ids: dict[int, int] = {}
        self.states: dict[int, _PendingWrite] = {}
        self._init_staging()
        # paxfan: consistent ring over the ingest-batcher tier (see
        # the multipaxos client) -- sessions pin to shards; timeouts
        # suspect one shard; Rejected floors backoff per shard.
        from frankenpaxos_tpu.runs.routing import make_fan_router

        self._fan = make_fan_router(config,
                                    revive_after_s=resend_period_s)

    def _random_group_leader(self) -> Address:
        group = self.rng.randrange(self.config.num_leader_groups)
        return self._leader_of_group(group)

    def _send_request(self, request: ClientRequest) -> None:
        # runs/routing ladder (ingest batchers, ring-pinned per
        # session > batchers > a random group's leader: any group can
        # sequence any command).
        dst = pick_request_destination(
            self.config, self.rng, self._random_group_leader,
            fan=self._fan,
            key=(self.address, request.command.command_id.client_pseudonym))
        self.send(dst, request)

    def _note_shed_source(self, src: Address, rejected) -> float:
        if self._fan is None:
            return 0.0
        from frankenpaxos_tpu.ingest.fan import shard_of_address

        shard = shard_of_address(self.config, src)
        if shard < 0:
            return 0.0
        self._fan.note_shed(shard, rejected.retry_after_ms)
        return self._fan.floor_delay_s(shard)

    def _leader_of_group(self, group: int) -> Address:
        rs = ClassicRoundRobin(len(self.config.leader_addresses[group]))
        return self.config.leader_addresses[group][
            rs.leader(self.rounds[group])]

    def _flush_staged(self, staged: list) -> None:
        """Ship writes staged by ``coalesce_writes`` as one array to a
        random leader group (any group can sequence any command); the
        array rides the client-scoped ring key (pseudonym -1)."""
        dst = pick_array_destination(self.config, self.rng,
                                     self._random_group_leader,
                                     fan=self._fan,
                                     key=(self.address, -1))
        self.send(dst, ClientRequestArray(commands=tuple(staged)))

    def write(self, pseudonym: int, command: bytes,
              callback: Optional[Callable[[bytes], None]] = None) -> None:
        if pseudonym in self.states:
            raise RuntimeError(
                f"pseudonym {pseudonym} already has a pending operation")
        id = self.ids.get(pseudonym, 0)
        request = ClientRequest(Command(
            CommandId(self.address, pseudonym, id), command))
        if self.coalesce_writes:
            self._stage_write(request.command)
        else:
            self._send_request(request)

        def resend():
            state = self.states.get(pseudonym)
            if not isinstance(state, _PendingWrite) or state.id != id \
                    or not self._consume_retry(pseudonym, state,
                                               "failover"):
                return
            if self._fan is not None:
                # paxfan: suspect this key's shard so the resend
                # routes past it; other keys stay pinned.
                self._fan.suspect_key(self.address, pseudonym)
            self._send_request(request)
            timer.start()

        timer = self.timer(f"resendWrite{pseudonym}", self.resend_period_s,
                           resend)
        timer.start()
        self.states[pseudonym] = _PendingWrite(
            id, command, callback or (lambda _: None), timer)
        self.ids[pseudonym] = id + 1

    def read(self, pseudonym: int, command: bytes,
             callback: Optional[Callable[[bytes], None]] = None) -> None:
        """A linearizable read ordered through the log: ``command`` (a
        state machine read, such as a ``GetRequest``) is proposed in a
        slot like a write, and ``callback`` gets what the state machine
        answered when a replica executed that slot. Mencius has no read
        path beside the log (docs/MENCIUS.md)."""
        self.write(pseudonym, command, callback)

    # Rejected handling + backoff/reissue scheduling live in
    # RetryAdmissionMixin (runs/client.py); only the re-send is ours.
    def _reissue(self, pseudonym: int, state) -> None:
        request = ClientRequest(Command(
            CommandId(self.address, pseudonym, state.id), state.command))
        if self.coalesce_writes:
            # Coalesce backoff expiries back into one array instead of
            # a retry storm of singles.
            self._stage_write(request.command)
        else:
            self._send_request(request)

    def receive(self, src: Address, message) -> None:
        if isinstance(message, ClientReply):
            pseudonym = message.command_id.client_pseudonym
            state = self.states.get(pseudonym)
            if state is None or message.command_id.client_id != state.id:
                return
            state.resend.stop()
            del self.states[pseudonym]
            state.callback(message.result)
        elif isinstance(message, ClientReplyArray):
            # A replica's whole drain of replies to this client in one
            # message; per-entry resolution mirrors ClientReply.
            for pseudonym, client_id, _slot, result in message.entries:
                state = self.states.get(pseudonym)
                if state is None or client_id != state.id:
                    continue
                state.resend.stop()
                del self.states[pseudonym]
                state.callback(result)
        elif isinstance(message, NotLeaderClient):
            for leader in self.config.leader_addresses[
                    message.leader_group_index]:
                self.send(leader, LeaderInfoRequestClient())
        elif isinstance(message, Rejected):
            self._handle_rejected(src, message)
        elif isinstance(message, LeaderInfoReplyClient):
            if message.round > self.rounds[message.leader_group_index]:
                self.rounds[message.leader_group_index] = message.round
                for pseudonym, state in self.states.items():
                    self._send_request(ClientRequest(Command(
                        CommandId(self.address, pseudonym, state.id),
                        state.command)))
        else:
            self.logger.fatal(f"unexpected client message {message!r}")

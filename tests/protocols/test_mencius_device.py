"""Mencius with every vote counted on the device vote board
(``quorum_backend="tpu"``) against the host tally, over one SimTransport
(CPU XLA under the tests' pin): the same writes give the same chosen
log, noop ranges included, with a leader group that never gets a request
and has to skip, and with votes dropped and duplicated on the way to the
proxy leaders."""

import hashlib
import random

import pytest

from frankenpaxos_tpu.protocols.mencius.common import (
    ChosenNoopRange,
    ChosenRun,
    Noop,
    Phase2b,
    Phase2bNoopRange,
    Phase2bRun,
)
from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
from tests.protocols.mencius_harness import executed_prefix, make_mencius

VOTES = (Phase2b, Phase2bRun, Phase2bNoopRange)


def fate(message, drop: float, duplicate: float) -> str:
    """Whether a vote on its way to a proxy leader is dropped,
    duplicated or delivered: decided by its bytes, so both backends
    lose and repeat the same votes whatever order they send them in."""
    digest = hashlib.sha256(repr((message.src, message.dst)).encode()
                            + message.data).digest()
    draw = int.from_bytes(digest[:8], "little") / 2 ** 64
    if draw < drop:
        return "drop"
    if draw < drop + duplicate:
        return "duplicate"
    return "deliver"


def pump(sim, drop: float, duplicate: float, seen: set) -> None:
    """Deliver FIFO until quiet, collecting the device tracker's
    dispatches as they are armed; a vote to a proxy leader may be
    dropped or sent twice (once per distinct frame)."""
    transport = sim.transport
    proxies = set(sim.config.proxy_leader_addresses)
    while True:
        transport._compact_messages()
        if transport.messages:
            message = transport.messages[0]
            key = (message.src, message.dst, message.data)
            if (message.dst in proxies and key not in seen
                    and isinstance(DEFAULT_SERIALIZER.from_bytes(
                        message.data), VOTES)):
                seen.add(key)
                what = fate(message, drop, duplicate)
                if what == "drop":
                    transport._remove_buffered(message)
                    continue
                if what == "duplicate":
                    transport.send(message.src, message.dst, message.data)
            transport.deliver_message(message)
            continue
        flushes = [t for t in transport.running_timers()
                   if t.name == "tpuDrainFlush"]
        if not flushes:
            return
        for timer in flushes:
            transport.trigger_timer(timer.id)


def run(groups: int, f: int, backend: str, drop: float = 0.0,
        duplicate: float = 0.0) -> tuple:
    """40 writes in waves through coalescing clients that never pick the
    last leader group. Returns both replicas' executed logs and what
    the clients were answered."""
    sim = make_mencius(f=f, num_leader_groups=groups, num_clients=2,
                       coalesced=True, lag_threshold=1, seed=3,
                       quorum_backend=backend)
    rng = random.Random(11)
    for client in sim.clients:
        # The last group is held back: it must skip its slots.
        client._random_group_leader = (
            lambda client=client: client._leader_of_group(
                rng.randrange(groups - 1)))
    got: list = []
    seen: set = set()
    for wave in range(5):
        for c, client in enumerate(sim.clients):
            for p in range(4):
                client.write(p, b"w%d-%d-%d" % (wave, c, p), got.append)
            client.flush_writes()
        pump(sim, drop, duplicate, seen)
        for _ in range(40):
            if len(got) == 8 * (wave + 1):
                break
            for timer in sim.transport.running_timers():
                if timer.name == "recover" \
                        or timer.name.startswith("resendWrite"):
                    sim.transport.trigger_timer(timer.id)
            pump(sim, drop, duplicate, seen)
    return [executed_prefix(r) for r in sim.replicas], got, sim


@pytest.mark.parametrize("f", [1, 2])
@pytest.mark.parametrize("groups", [2, 3])
def test_the_board_chooses_the_host_tallys_log(groups, f):
    dict_logs, dict_got, _ = run(groups, f, "dict")
    tpu_logs, tpu_got, sim = run(groups, f, "tpu")
    assert len(tpu_got) == len(dict_got) == 40
    assert tpu_logs == dict_logs
    log = tpu_logs[0]
    assert any(isinstance(v, Noop) for v in log), log
    assert sum(len(v.commands) for v in log
               if not isinstance(v, Noop)) >= 40
    proxies = sim.proxy_leaders
    # Every vote went to the board, and nothing is left proposed.
    assert sum(p.tracker.device_votes for p in proxies) > 0
    assert all(not p._records and not p._slot_values for p in proxies)


@pytest.mark.parametrize("groups", [2, 3])
def test_dropped_and_repeated_votes_leave_the_logs_equal(groups):
    """A lost vote stalls its slots until a replica's recover timer has
    the owning group re-propose them in a higher round; a repeated one
    is counted once. Both backends lose and repeat the same votes."""
    dict_logs, dict_got, _ = run(groups, 1, "dict", drop=0.05,
                                 duplicate=0.2)
    tpu_logs, tpu_got, _ = run(groups, 1, "tpu", drop=0.05,
                               duplicate=0.2)
    assert len(tpu_got) == len(dict_got) == 40
    assert tpu_logs == dict_logs
    assert tpu_logs[0] == tpu_logs[1]


def test_a_chosen_batch_goes_out_as_one_run_and_one_noop_range():
    """Three groups' runs decided in one device batch: each run leaves
    as ONE ChosenRun, each noop range as ONE ChosenNoopRange, to every
    replica."""
    from frankenpaxos_tpu.protocols.mencius.common import (
        NOOP,
        Phase2aNoopRange,
        Phase2aRun,
    )

    sim = make_mencius(f=1, num_leader_groups=3, quorum_backend="tpu")
    proxy = sim.proxy_leaders[0]
    leader = sim.config.leader_addresses[0][0]
    acceptors = sim.config.acceptor_addresses
    sent = []
    proxy.broadcast = lambda dsts, message, **kw: sent.append(
        (tuple(dsts), message))
    proxy.receive(leader, Phase2aRun(start_slot=0, stride=3, round=0,
                                     values=(NOOP,) * 5))
    proxy.receive(leader, Phase2aRun(start_slot=1, stride=3, round=0,
                                     values=(NOOP,) * 4))
    proxy.receive(leader, Phase2aNoopRange(slot_start_inclusive=2,
                                           slot_end_exclusive=14,
                                           round=0))
    for index in (0, 1):
        proxy.receive(acceptors[0][0][index], Phase2bRun(
            acceptor_group_index=0, acceptor_index=index, start_slot=0,
            count=5, stride=3, round=0))
        proxy.receive(acceptors[1][0][index], Phase2bRun(
            acceptor_group_index=0, acceptor_index=index, start_slot=1,
            count=4, stride=3, round=0))
        proxy.receive(acceptors[2][0][index], Phase2bNoopRange(
            acceptor_group_index=0, acceptor_index=index,
            slot_start_inclusive=2, slot_end_exclusive=14, round=0))
    proxy.on_drain()
    assert proxy.tracker.device_launches == 1
    proxy.driver.collect_all()
    replicas = tuple(sim.config.replica_addresses)
    assert sorted(sent, key=lambda s: repr(s[1])) == sorted([
        (replicas, ChosenRun(start_slot=0, stride=3, values=(NOOP,) * 5)),
        (replicas, ChosenRun(start_slot=1, stride=3, values=(NOOP,) * 4)),
        (replicas, ChosenNoopRange(slot_start_inclusive=2,
                                   slot_end_exclusive=12)),
    ], key=lambda s: repr(s[1]))
    assert not proxy._records

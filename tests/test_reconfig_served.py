"""The epoch path as a served deployment drives it (ISSUE 37): the device
epoch tracker against the dict oracle over dozens of epochs with
thousands of slots mid-collection across every boundary and the ring
wrapping; the switch from the single-epoch board (adopted whole, or what
it strands counted); and that deployments which never reconfigure never
leave the single-epoch path."""

from __future__ import annotations

import json
import os
import random

import numpy as np
import pytest

from frankenpaxos_tpu.protocols.multipaxos.messages import (
    NOOP,
    Phase2aRun,
    Phase2b,
)
from frankenpaxos_tpu.protocols.multipaxos.proxy_leader import (
    ProxyLeader,
    ProxyLeaderOptions,
)
from frankenpaxos_tpu.reconfig import (
    EpochCommit,
    EpochConfig,
    EpochQuorumTracker,
    EpochStore,
)
from frankenpaxos_tpu.runtime import (
    FakeCollectors,
    FakeLogger,
    LogLevel,
    SimTransport,
)
from tests.protocols.multipaxos_harness import (
    deliver_and_flush,
    make_multipaxos,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs",
                       "mp_f1_majority_reconfig.json")) as f:
    CELL_CONFIG = json.load(f)
WINDOW = int(CELL_CONFIG["options"]["tpu_window"])
POOL = tuple(f"a{n}" for n in range(CELL_CONFIG["acceptor_pool"]))
IN_FLIGHT = 4096


def _feed(tracker, how: str, slot: int, voter) -> None:
    """One vote, through the entry point a kind of message takes."""
    if how == "record":
        tracker.record(slot, 0, voter)
    elif how == "record_range":
        tracker.record_range(slot, slot + 1, 0, voter)
    else:
        tracker.record_votes(np.asarray([slot]),
                             np.zeros(1, dtype=np.int32), voter)


# Seed, entry point, slots in flight. With 4096 in flight a drain is two
# runs of 512 slots 3585 apart, a cluster too thin for a block: the
# scatter. With 8192 it is two blocks, with 96 one block of 608 slots
# that crosses an epoch's boundary every tenth drain.
@pytest.mark.parametrize("seed,how,in_flight", [
    (37, "record", IN_FLIGHT), (2_147_483_659, "record", IN_FLIGHT),
    (38, "record_range", 2 * IN_FLIGHT), (39, "record_votes", 96)])
def test_device_epoch_tracker_equals_the_oracle_over_many_epochs_in_flight(
        seed, how, in_flight):
    """The cell's shape: 3-of-6 draws, the configuration's window,
    ``in_flight`` slots holding one of their two votes at every instant,
    so at every boundary; slots start 60,000 below the ring's end, so it
    wraps. The device backend reports what the dict oracle reports, in
    its order."""
    rng = random.Random(seed)
    members = POOL[:3]
    stores = {b: EpochStore.from_members(members, f=1)
              for b in ("dict", "tpu")}
    trackers = {b: EpochQuorumTracker(stores[b], backend=b, window=WINDOW)
                for b in ("dict", "tpu")}
    reported = {b: [] for b in trackers}
    base = WINDOW - 60_000
    epochs, every = 25, 5000
    first_voter: dict = {}

    def members_of(slot: int) -> tuple:
        return stores["dict"].epoch_of_slot(slot).members

    def vote(slot: int, voter) -> None:
        for tracker in trackers.values():
            _feed(tracker, how, slot, voter)

    def drain() -> None:
        for b, tracker in trackers.items():
            reported[b].extend(tracker.drain())

    for step in range(epochs * every + in_flight):
        opening = base + step
        if step and step % every == 0 and step < epochs * every:
            # A reconfiguration: the next slot to open starts the epoch;
            # the ``in_flight`` below it hold one vote each.
            current = stores["dict"].current()
            drawn = current.members
            while drawn == current.members:
                drawn = tuple(rng.sample(POOL, 3))
            for b in trackers:
                stores[b].add(EpochConfig(epoch=current.epoch + 1,
                                          start_slot=opening, f=1,
                                          members=drawn))
                trackers[b].note_epochs()
        if step < epochs * every:
            one, _ = rng.sample(members_of(opening), 2)
            first_voter[opening] = one
            vote(opening, one)
            if rng.random() < 0.05:      # a vote that must never count
                outsiders = [a for a in POOL
                             if a not in members_of(opening)]
                vote(opening, rng.choice(outsiders + ["stranger"]))
        closing = opening - in_flight
        if closing >= base:
            first = first_voter.pop(closing)
            if rng.random() < 0.05:      # the same acceptor again
                vote(closing, first)
            vote(closing, rng.choice([a for a in members_of(closing)
                                      if a != first]))
        if step % 512 == 511:
            drain()
    drain()
    assert stores["tpu"].current().epoch == epochs - 1 >= 24
    assert trackers["tpu"].planes == epochs
    # In the oracle's order, but for the 63 slots or fewer below the
    # ring's end that a straddling block leaves to the scatter, which
    # takes them acceptor by acceptor (the planner's, before ISSUE 38).
    def away_from_the_ring_end(keys: list) -> list:
        return [key for key in keys if not WINDOW - 64 <= key[0] < WINDOW]

    assert away_from_the_ring_end(reported["tpu"]) \
        == away_from_the_ring_end(reported["dict"])
    assert sorted(reported["tpu"]) == sorted(reported["dict"])
    assert len(reported["dict"]) == len(set(reported["dict"]))
    assert set(reported["dict"]) == {
        (slot, 0) for slot in range(base, base + epochs * every)}
    # The ring wrapped, and the board is the configuration's.
    assert base + epochs * every > WINDOW
    # ... all six rows of it in use, of the eight it is allocated by.
    tracker = trackers["tpu"]
    board = tracker._checker.board.votes
    assert len(stores["tpu"].universe()) == CELL_CONFIG["board"]["nodes"]
    assert board.shape == (8, CELL_CONFIG["board"]["window"])
    assert tracker.votes >= 2 * epochs * every
    assert tracker._checker.window_violations == 0
    if in_flight == IN_FLIGHT:
        # Dense only while nothing closes yet, and at the end.
        assert tracker.dense_votes < 0.1 * tracker.votes
        assert tracker.launches >= 0.9 * tracker.votes / 256
    else:
        # A block or two a drain (one more where the ring ends).
        assert tracker.dense_votes >= 0.99 * tracker.votes
        assert tracker.launches <= 2 * (epochs * every + in_flight) / 512 + 4


def _epoch_tracker(members=POOL[:3], window=1 << 12):
    store = EpochStore.from_members(tuple(members), f=1)
    return store, EpochQuorumTracker(store, backend="tpu", window=window)


def test_a_contiguous_single_round_drain_is_one_dense_launch():
    """The cell's drain: two acceptors' ranges over 350 slots in one
    round are 700 votes in ONE jitted call, across an epoch's boundary
    or not."""
    store, tracker = _epoch_tracker()
    store.add(EpochConfig(epoch=1, start_slot=1200, f=1,
                          members=(POOL[1], POOL[2], POOL[4])))
    tracker.note_epochs()
    for start, voters in ((100, POOL[:2]), (1000, POOL[1:3])):
        before = (tracker.launches, tracker.dense_votes, tracker.votes)
        for voter in voters:
            tracker.record_range(start, start + 350, 2, voter)
        assert tracker.drain() == [(slot, 2)
                                   for slot in range(start, start + 350)]
        assert (tracker.launches, tracker.dense_votes, tracker.votes) == (
            before[0] + 1, before[1] + 700, before[2] + 700)


def test_a_drain_of_two_rounds_reports_the_older_rounds_quorum_first():
    store, tracker = _epoch_tracker()
    tracker.record(5, 0, POOL[0])
    assert tracker.drain() == []
    # Slot 5's completing round-0 vote, then a wave in round 1 over it.
    tracker.record(5, 0, POOL[1])
    for voter in POOL[:2]:
        tracker.record_range(3, 9, 1, voter)
    launches = tracker.launches
    out = tracker.drain()
    assert out[0] == (5, 0)
    assert out[1:] == [(slot, 1) for slot in (3, 4, 6, 7, 8)]
    assert tracker.launches == launches + 2      # the scatter, the block


def _program_cache_sizes() -> dict:
    from frankenpaxos_tpu.ops import quorum

    return {name: fn._cache_size() for name, fn in vars(quorum).items()
            if hasattr(fn, "_cache_size")}


def test_no_program_is_compiled_after_an_epoch_trackers_construction():
    """Forty reconfigurations and drains of every shape (every width
    from 1 to 5000, thin ones, two rounds, the ring's end inside a
    block) find every program in the jitted functions' caches, as the
    constructor left them."""
    from frankenpaxos_tpu.ops.quorum import TpuQuorumChecker
    from frankenpaxos_tpu.quorums import SimpleMajority

    window = 1 << 14
    store, tracker = _epoch_tracker(window=window)
    # As a served proxy leader builds it: the single-epoch board taken
    # over (the one gather of the switch is a program of its own).
    old = TpuQuorumChecker(SimpleMajority(range(3)).write_spec(),
                           window=window)
    old.record_and_check([window - 9000], [0], [0])
    tracker.adopt_board(old)
    built = _program_cache_sizes()
    rng = random.Random(38)
    widths = list(range(1, 130)) + [rng.randrange(130, 5001)
                                    for _ in range(40)] + [255, 256, 257,
                                                           1024, 1025, 4096,
                                                           4097, 5000]
    rng.shuffle(widths)
    cursor, chosen = window - 9000, 0
    for n, width in enumerate(widths):
        members = store.current().members
        if n % (len(widths) // 40) == 0 and store.current().epoch < 40:
            drawn = members
            while drawn == members:
                drawn = tuple(rng.sample(POOL, 3))
            store.add(EpochConfig(epoch=store.current().epoch + 1,
                                  start_slot=cursor + width // 2, f=1,
                                  members=drawn))
            tracker.note_epochs()
        for slot in range(cursor, cursor + width):
            voters = store.epoch_of_slot(slot).members[:2]
            step = 1 if n % 7 else 9           # a thin drain now and then
            if (slot - cursor) % step == 0:
                for voter in voters:
                    tracker.record(slot, n % 3 == 0 and slot % 2, voter)
        chosen += len(tracker.drain())
        cursor += width
    assert store.current().epoch == 40 and tracker.planes == 41
    assert cursor > 2 * window and chosen > 30_000
    assert _program_cache_sizes() == built


def test_a_drain_that_straddles_the_ring_end_reports_every_slot_once():
    window = 1 << 12
    store, tracker = _epoch_tracker(window=window)
    store.add(EpochConfig(epoch=1, start_slot=window - 10, f=1,
                          members=(POOL[0], POOL[3], POOL[5])))
    tracker.note_epochs()
    built = _program_cache_sizes()
    for voter in (POOL[0], POOL[1], POOL[3]):
        tracker.record_range(window - 300, window + 300, 0, voter)
    # Below the boundary acceptors 0 and 1 decide, from it on 0 and 3.
    assert tracker.drain() == [(slot, 0)
                               for slot in range(window - 300,
                                                 window + 300)]
    assert tracker.launches > 1 and tracker.dense_votes > 1000
    assert _program_cache_sizes() == built
    for voter in (POOL[1], POOL[3]):        # re-acks: nothing again
        tracker.record_range(window - 300, window + 300, 0, voter)
    assert tracker.drain() == []


def test_a_superseded_definition_drops_what_was_buffered_of_every_kind():
    store, tracker = _epoch_tracker()
    store.offer(EpochConfig(epoch=1, start_slot=50, f=1,
                            members=(POOL[0], POOL[3], POOL[4])), round=1)
    tracker.note_epochs()
    tracker.record(60, 1, POOL[3])
    tracker.record_range(61, 70, 1, POOL[3])
    tracker.record_votes(np.arange(70, 75), np.ones(5, dtype=np.int32),
                         POOL[4])
    assert tracker.has_votes()
    # A higher round's definition of epoch 1 with other members: the
    # universe's ids are rebuilt, so the buffered rows mean nothing.
    assert store.offer(EpochConfig(epoch=1, start_slot=55, f=1,
                                   members=(POOL[0], POOL[5], POOL[4])),
                       round=2) == "replaced"
    tracker.note_epochs()
    assert not tracker.has_votes() and tracker.drain() == []
    for voter in (POOL[5], POOL[4]):
        tracker.record_range(60, 64, 2, voter)
    assert tracker.drain() == [(slot, 2) for slot in range(60, 64)]


def proxy_leader_of(sim, **options) -> ProxyLeader:
    """A proxy leader of ``sim``'s configuration on a transport of its
    own, with collectors a test can read."""
    logger = FakeLogger(LogLevel.FATAL)
    collectors = FakeCollectors()
    proxy = ProxyLeader(sim.config.proxy_leader_addresses[0],
                        SimTransport(logger), logger, sim.config,
                        ProxyLeaderOptions(tpu_window=1 << 12, **options),
                        collectors=collectors)
    proxy.collectors = collectors
    return proxy


def half_voted(proxy, sim) -> tuple:
    """Two slots proposed; slot 0 holds one vote on the single-epoch
    tracker, slot 1 is chosen there. Returns the acceptors."""
    acceptors = list(sim.config.acceptor_addresses[0])
    leader = sim.config.leader_addresses[0]
    proxy.receive(leader, Phase2aRun(start_slot=0, round=0,
                                     values=(NOOP, NOOP)))
    proxy.receive(acceptors[0], Phase2b(slot=0, round=0, group_index=0,
                                        acceptor_index=0))
    for index in (0, 1):
        proxy.receive(acceptors[index], Phase2b(
            slot=1, round=0, group_index=0, acceptor_index=index))
    proxy.on_drain()
    proxy.driver.collect_all()
    assert proxy.chosen_count == 1
    return acceptors


def commit_of(acceptors: list, start_slot: int) -> EpochCommit:
    return EpochCommit(epoch=1, start_slot=start_slot, f=1, round=0,
                       members=(acceptors[1], acceptors[2], "acceptor-new"))


def test_the_device_board_is_adopted_and_a_straddling_slot_completes():
    sim = make_multipaxos(f=1)
    proxy = proxy_leader_of(sim, quorum_backend="tpu")
    acceptors = half_voted(proxy, sim)
    leader = sim.config.leader_addresses[0]
    proxy.receive(leader, commit_of(acceptors, start_slot=2))
    tracker = proxy._epoch_tracker
    assert tracker is not None and tracker.backend == "tpu"
    # The whole window, and the old board's slot-axis state as it stood.
    assert tracker._checker.window == 1 << 12
    assert tracker._checker.board.votes.shape == (8, 1 << 12)
    assert proxy.collectors.metrics[
        "multipaxos_proxy_leader_epoch_switch_stranded_votes_total"
    ].get() == 0
    assert proxy.collectors.metrics[
        "multipaxos_proxy_leader_epoch_planes"]._root.value == 2
    # The second vote of slot 0 arrives after the switch, by address.
    proxy.receive(acceptors[1], Phase2b(slot=0, round=0, group_index=0,
                                        acceptor_index=1))
    # A vote more for the slot that was chosen before it: not again.
    proxy.receive(acceptors[2], Phase2b(slot=1, round=0, group_index=0,
                                        acceptor_index=2))
    proxy.on_drain()
    assert proxy.chosen_count == 2
    votes = proxy.collectors.metrics[
        "multipaxos_proxy_leader_epoch_votes_total"].get()
    launches = proxy.collectors.metrics[
        "multipaxos_proxy_leader_epoch_launches_total"].get()
    dense = proxy.collectors.metrics[
        "multipaxos_proxy_leader_epoch_dense_votes_total"].get()
    # Both through one dense block, on the board that was adopted.
    assert (votes, launches, dense) == (2, 1, 2)


def test_votes_buffered_for_the_old_board_reach_it_before_it_is_adopted():
    sim = make_multipaxos(f=1)
    proxy = proxy_leader_of(sim, quorum_backend="tpu")
    acceptors = list(sim.config.acceptor_addresses[0])
    leader = sim.config.leader_addresses[0]
    proxy.receive(leader, Phase2aRun(start_slot=0, round=0, values=(NOOP,)))
    # Recorded and not yet drained when the commit arrives.
    proxy.receive(acceptors[0], Phase2b(slot=0, round=0, group_index=0,
                                        acceptor_index=0))
    proxy.receive(leader, commit_of(acceptors, start_slot=1))
    proxy.receive(acceptors[2], Phase2b(slot=0, round=0, group_index=0,
                                        acceptor_index=2))
    proxy.on_drain()
    proxy.driver.collect_all()
    assert proxy.chosen_count == 1


def test_what_a_dict_epoch_tracker_cannot_take_from_the_board_is_counted():
    sim = make_multipaxos(f=1)
    proxy = proxy_leader_of(sim, quorum_backend="tpu",
                            epoch_backend="dict")
    acceptors = half_voted(proxy, sim)
    proxy.receive(sim.config.leader_addresses[0],
                  commit_of(acceptors, start_slot=2))
    assert proxy._epoch_tracker.backend == "dict"
    # Slot 0's one vote stays on the board; slot 1's two are chosen.
    assert proxy.collectors.metrics[
        "multipaxos_proxy_leader_epoch_switch_stranded_votes_total"
    ].get() == 1
    proxy.receive(acceptors[1], Phase2b(slot=0, round=0, group_index=0,
                                        acceptor_index=1))
    proxy.on_drain()
    assert proxy.chosen_count == 1       # stranded: a resend has to free it


def benchmark_configs() -> list:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out = []
    for entry in manifest["configs"]:
        with open(os.path.join(REPO, entry["file"])) as f:
            out.append(pytest.param(json.load(f), id=entry["name"]))
    return out


@pytest.mark.parametrize("config", benchmark_configs())
def test_a_deployment_that_is_never_reconfigured_stays_on_the_board(config):
    """Every configuration of the benchmark, at its acceptor shape, under
    writes and no ``Reconfigure``: a grid has no epoch store at all, the
    others keep a single-epoch one, and none builds an epoch tracker or
    tags a proposal."""
    sim = make_multipaxos(
        f=config["f"], quorum_backend="tpu", flexible=config["flexible"],
        grid_shape=((config["acceptor_groups"],
                     config["acceptors_per_group"])
                    if config["flexible"] else None))
    done: list = []
    for n in range(12):
        sim.clients[0].write(n, b"w%d" % n, done.append)
        deliver_and_flush(sim)
    assert len(done) == 12
    for proxy in sim.proxy_leaders:
        assert proxy._epoch_tracker is None
        assert not proxy._stashed_epoch_runs
        if config["flexible"]:
            assert proxy.epochs is None
        else:
            assert not proxy.epochs.multi_epoch
    for leader in sim.leaders:
        assert not leader._epoch_tagging and leader._epoch_change is None
    assert sum(p.tracker.device_votes for p in sim.proxy_leaders) >= 24

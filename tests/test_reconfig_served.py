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


@pytest.mark.parametrize("seed", [37, 2_147_483_659])
def test_device_epoch_tracker_equals_the_oracle_over_many_epochs_in_flight(
        seed):
    """The cell's shape: 3-of-6 draws, the configuration's window, 4096
    slots holding one of their two votes at every instant, so at every
    boundary; slots start 60,000 below the ring's end, so it wraps."""
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
            tracker.record(slot, 0, voter)

    def drain() -> None:
        for b, tracker in trackers.items():
            reported[b].extend(tracker.drain())

    for step in range(epochs * every + IN_FLIGHT):
        opening = base + step
        if step and step % every == 0 and step < epochs * every:
            # A reconfiguration: the next slot to open starts the epoch;
            # the 4096 below it hold one vote each.
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
        closing = opening - IN_FLIGHT
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
    for b, got in reported.items():
        assert len(got) == len(set(got)), b
    assert set(reported["tpu"]) == set(reported["dict"])
    assert set(reported["dict"]) == {
        (slot, 0) for slot in range(base, base + epochs * every)}
    # The ring wrapped, and the board is the configuration's.
    assert base + epochs * every > WINDOW
    # ... all six rows of it in use, of the eight it is allocated by.
    board = trackers["tpu"]._checker.board.votes
    assert len(stores["tpu"].universe()) == CELL_CONFIG["board"]["nodes"]
    assert board.shape == (8, CELL_CONFIG["board"]["window"])
    assert trackers["tpu"].votes >= 2 * epochs * every
    assert trackers["tpu"].launches >= trackers["tpu"].votes / 256


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
    proxy._collect_all()
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
    assert (votes, launches) == (2, 1)


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
    proxy._collect_all()
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

"""The hybrid serializer and the MultiPaxos fixed-layout wire codecs.

Reference parity: every reference message is a schema'd protobuf
(ProtoSerializer.scala:3-11); here the hot-path messages get
fixed-layout binary codecs behind the Serializer seam, with pickle for
the long tail and first-byte discrimination between the two.
"""

import dataclasses
import pickle

import pytest

import frankenpaxos_tpu.protocols.multipaxos  # noqa: F401 - registers codecs
from frankenpaxos_tpu.protocols.multipaxos.messages import (
    Chosen,
    ChosenWatermark,
    ClientReply,
    ClientRequest,
    ClientRequestBatch,
    Command,
    CommandBatch,
    CommandId,
    NOOP,
    Phase2a,
    Phase2b,
)
from frankenpaxos_tpu.runtime.serializer import (
    DEFAULT_SERIALIZER,
    PickleSerializer,
)

HOT_MESSAGES = [
    Phase2b(group_index=1, acceptor_index=2, slot=1 << 40, round=3),
    Phase2b(group_index=0, acceptor_index=0, slot=0, round=-1),
    Phase2a(slot=5, round=0, value=CommandBatch((Command(
        CommandId(("10.0.0.1", 5000), 2, 7), b"hello"),))),
    Phase2a(slot=5, round=2, value=NOOP),
    Chosen(slot=9, value=NOOP),
    Chosen(slot=9, value=CommandBatch((
        Command(CommandId("sim-client", 0, 0), b""),
        Command(CommandId(("h", 80), 1, 2), b"\x00\xff" * 64)))),
    ClientRequest(Command(CommandId("client-1", 0, 1), b"x" * 100)),
    ClientRequestBatch(CommandBatch((Command(
        CommandId("c", 1, 2), b"p"),))),
    ClientReply(CommandId(("h", 1), 0, 4), 17, b"result"),
    ChosenWatermark(slot=42),
]


def test_read_path_codecs_round_trip():
    """The read hot path (a read request -> ReadReplyBatch; its max-slot
    round is among the batch shapes below) and the proxied
    ClientReplyBatch ride fixed layouts, not pickle."""
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        ClientReplyBatch,
        EventualReadRequest,
        ReadReply,
        ReadReplyBatch,
        ReadRequest,
        SequentialReadRequest,
    )

    cid = CommandId(("10.0.0.1", 9000), 3, 44)
    sim_cid = CommandId("Client 1", 0, 7)
    command = Command(cid, b"get-k")
    for message in [
        ReadRequest(slot=5, command=command),
        ReadRequest(slot=5, command=Command(sim_cid, b"get-k")),
        SequentialReadRequest(slot=-1, command=command),
        EventualReadRequest(command=command),
        ReadReplyBatch(batch=(ReadReply(cid, 9, b"r1"),
                              ReadReply(sim_cid, 10, b""))),
        ReadReplyBatch(batch=()),
        ClientReplyBatch(batch=(ClientReply(cid, 11, b"x" * 100),)),
    ]:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


@pytest.mark.parametrize("message", HOT_MESSAGES,
                         ids=lambda m: type(m).__name__)
def test_binary_round_trip(message):
    data = DEFAULT_SERIALIZER.to_bytes(message)
    # Registered types must take the binary path (tag byte < 0x80).
    assert data[0] < 128
    assert DEFAULT_SERIALIZER.from_bytes(data) == message


@dataclasses.dataclass(frozen=True)
class _NotOnAnyWire:
    """A type no protocol sends -- the pickle fallback's remaining
    clientele now that the COD301 baseline is empty."""

    x: int


def test_unregistered_types_fall_back_to_pickle():
    # Every protocol-sent message now has a fixed layout (the COD301
    # baseline burned to zero with SnapshotRequest/CommitSnapshot,
    # tags 206-207); the pickle fallback survives only for types that
    # never cross a protocol wire.
    message = _NotOnAnyWire(7)
    data = DEFAULT_SERIALIZER.to_bytes(message)
    assert data[0] >= 128  # pickle PROTO opcode
    assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_pickled_stream_from_legacy_sender_decodes():
    message = HOT_MESSAGES[0]
    legacy = PickleSerializer().to_bytes(message)
    assert DEFAULT_SERIALIZER.from_bytes(legacy) == message


def test_binary_encoding_is_compact_and_stable():
    """The Phase2b layout is part of the wire contract: 1 tag byte +
    two i64 + two i32, little-endian."""
    data = DEFAULT_SERIALIZER.to_bytes(
        Phase2b(group_index=3, acceptor_index=4, slot=258, round=7))
    assert len(data) == 25
    assert data[0] == 1  # Phase2bCodec.tag
    assert data[1:9] == (258).to_bytes(8, "little")
    assert data[9:17] == (7).to_bytes(8, "little")
    # And it is several times smaller than the pickle it replaces.
    assert len(data) < len(pickle.dumps(
        Phase2b(group_index=3, acceptor_index=4, slot=258, round=7))) / 3


def test_mencius_codecs_round_trip():
    """Mencius-specific hot messages (its inner MultiPaxos machinery
    reuses the multipaxos codecs): Chosen, HighWatermark gossip, and
    the noop-range skip triplet."""
    import frankenpaxos_tpu.protocols.mencius  # noqa: F401 - registers
    from frankenpaxos_tpu.protocols.mencius.common import (
        Chosen as MChosen,
        ChosenNoopRange,
        HighWatermark,
        Phase2aNoopRange,
        Phase2bNoopRange,
    )

    messages = [
        MChosen(slot=7, value=NOOP),
        MChosen(slot=7, value=CommandBatch((Command(
            CommandId(("h", 9), 0, 1), b"x"),))),
        HighWatermark(next_slot=1 << 33),
        Phase2aNoopRange(slot_start_inclusive=3, slot_end_exclusive=99,
                         round=2),
        Phase2bNoopRange(acceptor_group_index=1, acceptor_index=2,
                         slot_start_inclusive=3, slot_end_exclusive=99,
                         round=2),
        ChosenNoopRange(slot_start_inclusive=0, slot_end_exclusive=50),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message
    # mencius.Chosen and multipaxos.Chosen are DIFFERENT types and must
    # decode to their own classes.
    mp = DEFAULT_SERIALIZER.to_bytes(Chosen(slot=7, value=NOOP))
    mn = DEFAULT_SERIALIZER.to_bytes(MChosen(slot=7, value=NOOP))
    assert mp[0] != mn[0]
    assert type(DEFAULT_SERIALIZER.from_bytes(mp)) is Chosen
    assert type(DEFAULT_SERIALIZER.from_bytes(mn)) is MChosen


def test_epaxos_codecs_round_trip():
    """EPaxos command-path messages carry an InstancePrefixSet on every
    hop; the binary layout packs each column as watermark + sparse
    values (the DepSetBatch factorization)."""
    import frankenpaxos_tpu.protocols.epaxos  # noqa: F401 - registers
    from frankenpaxos_tpu.protocols.epaxos.instance_prefix_set import (
        Instance,
        InstancePrefixSet,
    )
    from frankenpaxos_tpu.protocols.epaxos.messages import (
        NOOP as ENOOP,
        Accept,
        AcceptOk,
        ClientReply as EClientReply,
        ClientRequest as EClientRequest,
        Command as ECommand,
        Commit,
        PreAccept,
        PreAcceptOk,
    )

    deps = InstancePrefixSet(3)
    for leader in range(3):
        for i in range(5):
            deps.add(Instance(leader, i))
    deps.add(Instance(1, 9))  # sparse tail above the watermark
    messages = [
        PreAccept(Instance(0, 4), (1, 0),
                  ECommand("c", 0, 1, b"xyz"), 7, deps),
        PreAcceptOk(Instance(0, 4), (1, 0), 2, 7, deps),
        Accept(Instance(0, 4), (1, 0), ENOOP, 7, deps),
        AcceptOk(Instance(0, 4), (1, 0), 2),
        Commit(Instance(0, 4), ECommand(("h", 1), 0, 1, b""), 7, deps),
        EClientRequest(ECommand("c", 0, 1, b"xyz")),
        EClientReply(0, 1, b"r"),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_bpaxos_codecs_round_trip():
    """SimpleBPaxos / SimpleGcBPaxos command-path messages, including
    the GcBPaxos SnapshotMarker sentinel riding the command escape
    hatch."""
    import frankenpaxos_tpu.protocols.simplebpaxos  # noqa: F401
    from frankenpaxos_tpu.protocols.simplebpaxos.messages import (
        NOOP as BNOOP,
        ClientReply as BClientReply,
        ClientRequest as BClientRequest,
        Command as BCommand,
        Commit as BCommit,
        DependencyReply,
        DependencyRequest,
        Phase2a as BPhase2a,
        Phase2b as BPhase2b,
        Propose,
        VertexId,
        VertexIdPrefixSet,
        VoteValue,
    )
    from frankenpaxos_tpu.protocols.simplegcbpaxos import SnapshotMarker

    deps = VertexIdPrefixSet(2)
    for leader in range(2):
        for i in range(4):
            deps.add(VertexId(leader, i))
    command = BCommand("client-0", 1, 2, b"payload")
    messages = [
        BClientRequest(command),
        DependencyRequest(VertexId(0, 3), command),
        DependencyReply(VertexId(0, 3), 1, deps),
        Propose(VertexId(1, 0), command, deps),
        BPhase2a(VertexId(1, 0), 4, VoteValue(command, deps)),
        BPhase2a(VertexId(1, 0), 4, VoteValue(BNOOP, deps)),
        BPhase2b(VertexId(1, 0), 2, 4),
        BCommit(VertexId(1, 0), command, deps),
        BCommit(VertexId(1, 0), BNOOP, deps),
        BClientReply(1, 2, b"result"),
        # The GcBPaxos SnapshotMarker sentinel rides the command escape
        # hatch on EVERY hop that can carry it (the leader proposes
        # SNAPSHOT through the same path as commands).
        DependencyRequest(VertexId(0, 3), SnapshotMarker()),
        Propose(VertexId(1, 0), SnapshotMarker(), deps),
        BPhase2a(VertexId(1, 0), 4, VoteValue(SnapshotMarker(), deps)),
        BCommit(VertexId(1, 0), SnapshotMarker(), deps),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_unanimousbpaxos_codecs_round_trip():
    """UnanimousBPaxos messages: frozenset dependency packing + the
    shared BPaxos command helper."""
    import frankenpaxos_tpu.protocols.unanimousbpaxos as m
    from frankenpaxos_tpu.protocols.simplebpaxos.messages import (
        NOOP as BNOOP,
        Command as BCommand,
        VertexId,
    )

    deps = frozenset({VertexId(0, 1), VertexId(1, 5)})
    command = BCommand("c", 0, 1, b"x")
    value = m.VoteValue(command, deps)
    messages = [
        m.ClientRequest(command),
        m.DependencyRequest(VertexId(0, 2), command),
        m.FastProposal(VertexId(0, 2), value),
        m.Phase2bFast(VertexId(0, 2), 1, value),
        m.Phase2a(VertexId(0, 2), 3, m.VoteValue(BNOOP, deps)),
        m.Phase2bClassic(VertexId(0, 2), 1, 3),
        m.Commit(VertexId(0, 2), value),
        m.ClientReply(0, 1, b"r"),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_scalog_codecs_round_trip():
    """Scalog's shard-write/backup/gossip/cut/execute path, including
    watermark-vector packing."""
    import frankenpaxos_tpu.protocols.scalog as m

    command = m.Command(m.CommandId(("h", 5), 3), b"x")
    messages = [
        m.ClientRequest(command),
        m.Backup(1, 7, command),
        m.ShardInfo(0, 1, (3, 5)),
        m.CutChosen(2, m.GlobalCut((3, 5))),
        m.Chosen(2, (command, m.Command(m.CommandId("sim", 0), b""))),
        m.ClientReply(m.CommandId(("h", 5), 3), 9, b"r"),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_horizontal_codecs_round_trip():
    """Horizontal's write path; Configuration values (one per
    reconfiguration) ride the pickled escape hatch in the value slot."""
    import frankenpaxos_tpu.protocols.horizontal as m

    command = m.Command(m.CommandId(("h", 5), 1, 3), b"x")
    config = m.Configuration({"kind": "simple", "members": [0, 1, 2]})
    messages = [
        m.ClientRequest(command),
        m.Phase2a(slot=5, round=1, first_slot=0, value=command),
        m.Phase2a(slot=5, round=1, first_slot=0, value=m.NOOP),
        m.Phase2a(slot=5, round=1, first_slot=0, value=config),
        m.Phase2b(slot=5, round=1, acceptor_index=2),
        m.Chosen(slot=5, value=command),
        m.Chosen(slot=5, value=config),
        m.ClientReply(m.CommandId("c", 0, 1), b"r"),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_matchmakermultipaxos_codecs_round_trip():
    """MatchmakerMultiPaxos' steady-state write path (matchmaking /
    reconfiguration epochs stay pickled -- per-epoch, not per-command)."""
    import frankenpaxos_tpu.protocols.matchmakermultipaxos as m

    command = m.Command(m.CommandId(("h", 5), 1, 3), b"x")
    messages = [
        m.ClientRequest(command),
        m.Phase2a(slot=5, round=1, value=command),
        m.Phase2a(slot=5, round=1, value=m.NOOP),
        m.Phase2b(slot=5, round=1, acceptor_index=2),
        m.Chosen(slot=5, value=command),
        m.ClientReply(m.CommandId("c", 0, 1), b"r"),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_fasterpaxos_codecs_round_trip():
    """FasterPaxos' steady-state path, including the optional command
    piggybacked on a Phase2b (ackNoopsWithCommands)."""
    import frankenpaxos_tpu.protocols.fasterpaxos as m

    command = m.Command(m.CommandId(("h", 5), 1, 3), b"x")
    messages = [
        m.ClientRequest(2, command),
        m.Phase2a(slot=5, round=1, value=command),
        m.Phase2a(slot=5, round=1, value=m.NOOP),
        m.Phase2b(server_index=0, slot=5, round=1),
        m.Phase2b(server_index=0, slot=5, round=1, command=command),
        m.Phase3a(slot=5, value=command),
        m.Phase3a(slot=5, value=m.NOOP),
        m.ClientReply(m.CommandId("c", 0, 1), b"r"),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_steady_wire_codecs_round_trip():
    """VanillaMencius, CRAQ, and FastMultiPaxos steady-state paths
    (protocols/steady_wire.py)."""
    import frankenpaxos_tpu.protocols.craq as cq
    import frankenpaxos_tpu.protocols.fastmultipaxos as fmp
    import frankenpaxos_tpu.protocols.vanillamencius as vm

    command = vm.Command(vm.CommandId(("h", 5), 1, 3), b"x")
    cid = cq.CommandId(("h", 5), 1, 3)
    fcommand = fmp.Command(fmp.CommandId(("h", 5), 3), b"x")
    messages = [
        vm.ClientRequest(command),
        vm.Phase2a(sending_server=0, slot=5, round=1, value=command),
        vm.Phase2a(sending_server=0, slot=5, round=1, value=vm.NOOP),
        vm.Skip(server_index=1, start_slot_inclusive=3,
                stop_slot_exclusive=9),
        vm.Phase2b(server_index=1, slot=5, round=1),
        vm.Chosen(slot=5, value=command, is_revocation=False),
        vm.Chosen(slot=5, value=vm.NOOP, is_revocation=True),
        vm.ClientReply(vm.CommandId("c", 0, 1), b"r"),
        cq.WriteBatch((cq.Write(cid, "k", "v"),), seq=7),
        cq.ReadBatch((cq.Read(cid, "k"),)),
        cq.TailRead(cq.ReadBatch((cq.Read(cid, "k"),))),
        cq.Ack(cq.WriteBatch((cq.Write(cid, "k", "v"),), seq=7)),
        cq.ClientReply(cid),
        cq.ReadReply(cid, "v"),
        fmp.ProposeRequest(fcommand),
        fmp.ProposeReply(fmp.CommandId(("h", 5), 3), b"r", round=2),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_fastmultipaxos_hot_loop_codecs_round_trip():
    """The leader/acceptor per-command loop: Phase2a with fast-round
    any/anySuffix markers, Phase2b votes, acceptor-drain buffers, and
    chosen-value gossip."""
    import frankenpaxos_tpu.protocols.fastmultipaxos as fmp

    command = fmp.Command(fmp.CommandId(("h", 5), 3), b"x")
    messages = [
        fmp.Phase2a(slot=5, round=1, value=command),
        fmp.Phase2a(slot=5, round=1, value=fmp.NOOP),
        fmp.Phase2a(slot=5, round=1, any=True),
        fmp.Phase2a(slot=5, round=1, any_suffix=True),
        fmp.Phase2a(slot=5, round=1),
        fmp.Phase2b(acceptor_id=0, slot=5, round=1, vote=command),
        fmp.Phase2bBuffer((
            fmp.Phase2b(acceptor_id=0, slot=5, round=1, vote=command),
            fmp.Phase2b(acceptor_id=1, slot=6, round=1, vote=fmp.NOOP))),
        fmp.ValueChosen(slot=5, value=command),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_baseline_protocol_codecs_round_trip():
    """The last seven formerly pickle-only protocols: echo,
    unreplicated, batchedunreplicated (the throughput-ceiling
    baselines), paxos, fastpaxos, caspaxos, matchmakerpaxos. Every
    message type rides a binary codec now."""
    from frankenpaxos_tpu.protocols import (  # noqa: F401 - registers
        batchedunreplicated as bu,
        caspaxos as cp,
        echo as ec,
        fastpaxos as fp,
        matchmakerpaxos as mp,
        paxos as px,
        unreplicated as ur,
    )

    messages = [
        ec.EchoRequest("hello"),
        ec.EchoReply("hello back é"),
        ur.ClientRequest(("10.0.0.1", 9000), 3, 1 << 40, b"cmd"),
        ur.ClientRequest("sim-client", 0, 0, b""),
        ur.ClientReply(3, 1 << 40, b"result"),
        bu.ClientRequest(bu.Command(bu.CommandId(("h", 1), 7), b"x")),
        bu.ClientRequestBatch((
            bu.Command(bu.CommandId("c1", 0), b"a"),
            bu.Command(bu.CommandId(("h", 2), 1), b"b" * 100))),
        bu.ClientReply(bu.CommandId("c1", 0), b"r"),
        bu.ClientReplyBatch((
            bu.ClientReply(bu.CommandId("c1", 0), b"r0"),
            bu.ClientReply(bu.CommandId(("h", 2), 1), b"r1"))),
        px.ProposeRequest("v"), px.ProposeReply("chosen"),
        px.Phase1a(3), px.Phase1b(3, 1, -1, None),
        px.Phase1b(3, 1, 2, "earlier"), px.Phase2a(3, "v"),
        px.Phase2b(1, 3),
        fp.ProposeRequest("v"), fp.ProposeReply("chosen"),
        fp.Phase1a(4), fp.Phase1b(4, 0, 0, "fast"),
        fp.Phase2a(4, None),  # None = the distinguished "any" value
        fp.Phase2a(4, "v"), fp.Phase2b(2, 4),
        cp.ClientRequest(("h", 5), 9, frozenset({1, 5, 9})),
        cp.ClientRequest("sim", 0, frozenset()),
        cp.ClientReply(9, frozenset({2})),
        cp.Phase1a(1), cp.Phase1b(1, 0, -1, None),
        cp.Phase1b(1, 2, 0, frozenset({4})),
        cp.Phase2a(1, frozenset({1, 2})), cp.Phase2b(1, 0),
        cp.Nack(7),
        mp.ClientRequest("v"), mp.ClientReply("chosen"),
        mp.MatchRequest(mp.AcceptorGroup(
            2, {"kind": "simple_majority", "members": [0, 1, 2]})),
        mp.MatchReply(2, 1, (
            mp.AcceptorGroup(0, {"kind": "grid",
                                 "grid": [[1, 0], [2, 3]]}),
            mp.AcceptorGroup(1, {"kind": "unanimous_writes",
                                 "members": [3, 4, 5]}))),
        mp.Phase1a(2), mp.Phase1b(2, 0, None),
        mp.Phase1b(2, 1, mp.Phase1bVote(0, "old")),
        mp.Phase2a(2, "v"), mp.Phase2b(2, 1),
        mp.MatchmakerNack(5), mp.AcceptorNack(6),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        decoded = DEFAULT_SERIALIZER.from_bytes(data)
        assert decoded == message, type(message).__name__
        assert type(decoded) is type(message)

    # paxos and fastpaxos share shapes but NOT classes: same-looking
    # messages must decode to their own types.
    ppx = DEFAULT_SERIALIZER.to_bytes(px.Phase1a(3))
    pfp = DEFAULT_SERIALIZER.to_bytes(fp.Phase1a(3))
    assert ppx[0] != pfp[0]
    assert type(DEFAULT_SERIALIZER.from_bytes(ppx)) is px.Phase1a
    assert type(DEFAULT_SERIALIZER.from_bytes(pfp)) is fp.Phase1a


def test_run_pipeline_codecs_round_trip_and_reject_hostile_counts():
    """The drain-granular run messages (ClientRequestArray, Phase2aRun,
    ChosenRun, ClientReplyArray): SoA round trips, lazy re-encode as a
    raw copy, and decode-time validation of hostile counts (a claimed
    2^30-value array must raise inside codec decode -- the transport's
    corrupt-frame guard -- before any consumer sizes an allocation by
    the count)."""
    import struct

    import pytest

    from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
    from frankenpaxos_tpu.protocols.multipaxos import wire
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        ChosenRun,
        ClientReplyArray,
        ClientRequestArray,
        Command,
        CommandBatch,
        CommandId,
        NOOP,
        Phase2aRun,
    )

    cmd = lambda p, i: Command(  # noqa: E731
        CommandId(("10.0.0.1", 9000), p, i), b"payload-%d" % i)
    messages = [
        ClientRequestArray(commands=(cmd(0, 0), cmd(1, 7))),
        Phase2aRun(start_slot=5, round=2,
                   values=(CommandBatch((cmd(0, 0),)), NOOP,
                           CommandBatch((cmd(1, 1), cmd(2, 2))))),
        ChosenRun(start_slot=9, values=(NOOP, CommandBatch((cmd(3, 3),)))),
        ClientReplyArray(entries=((0, 1, 5, b"r0"), (2, 3, 6, b"r1"))),
    ]
    for message in messages:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128
        decoded = DEFAULT_SERIALIZER.from_bytes(data)
        assert type(decoded) is type(message)
        if hasattr(message, "values"):
            assert tuple(decoded.values) == tuple(message.values)
            # Lazy arrays re-encode as a raw copy, byte-identically,
            # WITHOUT materializing values first.
            assert isinstance(decoded.values, wire.LazyValueArray)
            re_encoded = DEFAULT_SERIALIZER.to_bytes(decoded)
            assert re_encoded == data
        else:
            assert decoded == message

    # Hostile count: n = 2^30 with a 4-byte body must raise at decode.
    run = Phase2aRun(start_slot=0, round=0, values=(NOOP,))
    data = bytearray(DEFAULT_SERIALIZER.to_bytes(run))
    # Layout: tag(1) + start(8) + round(8) + n(4) + nbytes(4) + ...
    struct.pack_into("<i", data, 17, 1 << 30)
    with pytest.raises(ValueError):
        DEFAULT_SERIALIZER.from_bytes(bytes(data))
    # Hostile byte length overrunning the buffer must also raise.
    data = bytearray(DEFAULT_SERIALIZER.to_bytes(run))
    struct.pack_into("<i", data, 21, 1 << 20)
    with pytest.raises(ValueError):
        DEFAULT_SERIALIZER.from_bytes(bytes(data))
    # Length-valid but content-corrupt (an inner command count
    # overrunning the segment): surfaces as ValueError at first ACCESS
    # (the lazy boundary), not a bare struct.error/IndexError.
    payload = (struct.pack("<i", 0)       # empty address table
               + b"\x01"                  # one CommandBatch value...
               + struct.pack("<i", 1000))  # ...claiming 1000 commands
    data = (bytes([wire.Phase2aRunCodec.tag])
            + struct.pack("<qq", 0, 0)
            + struct.pack("<ii", 1, len(payload)) + payload)
    decoded = DEFAULT_SERIALIZER.from_bytes(data)  # lengths check out
    with pytest.raises(ValueError):
        list(decoded.values)


# --- registry-wide corrupt-frame containment --------------------------------
# VERDICT item 8: a malformed frame on ANY protocol must log-and-drop
# at the transport guard, never kill the connection task with an
# uncontrolled exception. The contract enforced here: decoding a
# corrupted registered-codec frame either yields garbage or raises
# ValueError (HybridSerializer normalizes struct.error/IndexError/...),
# including at the lazy value-array boundary. ``all_codec_samples``
# must cover EVERY registered tag -- adding a codec without a sample
# fails test_every_registered_codec_has_a_fuzz_sample.


def all_codec_samples() -> dict:
    """{wire tag: sample message} covering the full codec registry
    (all *_wire.py modules + protocols/*/wire.py + baseline_wire)."""
    # Importing the protocol packages registers every codec.
    import frankenpaxos_tpu.protocols.craq as cq
    import frankenpaxos_tpu.protocols.epaxos  # noqa: F401
    import frankenpaxos_tpu.protocols.fasterpaxos as fsp
    import frankenpaxos_tpu.protocols.fastmultipaxos as fmp
    import frankenpaxos_tpu.protocols.horizontal as hz
    import frankenpaxos_tpu.protocols.matchmakermultipaxos as mmp
    import frankenpaxos_tpu.protocols.mencius  # noqa: F401
    import frankenpaxos_tpu.protocols.scalog as sc
    import frankenpaxos_tpu.protocols.simplebpaxos  # noqa: F401
    import frankenpaxos_tpu.protocols.simplegcbpaxos  # noqa: F401
    import frankenpaxos_tpu.protocols.unanimousbpaxos as ub
    import frankenpaxos_tpu.protocols.vanillamencius as vm
    from frankenpaxos_tpu.protocols import (
        batchedunreplicated as bu,
        caspaxos as cp,
        echo as ec,
        fastpaxos as fp,
        matchmakerpaxos as mkp,
        paxos as px,
        unreplicated as ur,
    )
    from frankenpaxos_tpu.protocols.epaxos.instance_prefix_set import (
        Instance,
        InstancePrefixSet,
    )
    from frankenpaxos_tpu.protocols.epaxos import messages as em
    from frankenpaxos_tpu.protocols.mencius import common as mn
    from frankenpaxos_tpu.protocols.multipaxos import messages as mp
    from frankenpaxos_tpu.protocols.simplebpaxos import messages as bp
    from frankenpaxos_tpu.protocols.simplegcbpaxos import SnapshotMarker
    from frankenpaxos_tpu.runtime import serializer
    from frankenpaxos_tpu import native

    cid = mp.CommandId(("10.0.0.1", 9000), 2, 7)
    command = mp.Command(cid, b"payload")
    batch = mp.CommandBatch((command,))
    edeps = InstancePrefixSet(2)
    edeps.add(Instance(0, 1))
    ecommand = em.Command("c", 0, 1, b"xyz")
    bdeps = bp.VertexIdPrefixSet(2)
    bdeps.add(bp.VertexId(0, 1))
    bcommand = bp.Command("client-0", 1, 2, b"p")
    hcommand = hz.Command(hz.CommandId(("h", 5), 1, 3), b"x")
    mcommand = mmp.Command(mmp.CommandId(("h", 5), 1, 3), b"x")
    fscommand = fsp.Command(fsp.CommandId(("h", 5), 1, 3), b"x")
    vcommand = vm.Command(vm.CommandId(("h", 5), 1, 3), b"x")
    ccid = cq.CommandId(("h", 5), 1, 3)
    fcommand = fmp.Command(fmp.CommandId(("h", 5), 3), b"x")
    scommand = sc.Command(sc.CommandId(("h", 5), 3), b"x")

    samples = [
        # multipaxos hot + read paths
        mp.Phase2b(group_index=1, acceptor_index=2, slot=9, round=3),
        mp.Phase2a(slot=5, round=0, value=batch),
        mp.Chosen(slot=9, value=mp.NOOP),
        mp.ClientRequest(command),
        mp.ClientRequestBatch(batch),
        mp.ClientReply(cid, 17, b"result"),
        mp.ChosenWatermark(slot=42),
        mp.Phase2bRange(group_index=0, acceptor_index=1,
                        slot_start_inclusive=3, slot_end_exclusive=9,
                        round=0),
        mp.Phase2bVotes(group_index=0, acceptor_index=1,
                        packed=native.pack_votes2(
                            __import__("numpy").arange(
                                4, dtype="int64"),
                            __import__("numpy").zeros(
                                4, dtype="int32"))),
        mp.ClientRequestArray(commands=(command,)),
        mp.Phase2aRun(start_slot=5, round=2, values=(batch, mp.NOOP)),
        mp.ChosenRun(start_slot=9, values=(mp.NOOP, batch)),
        mp.ClientReplyArray(entries=((0, 1, 5, b"r0"),)),
        mp.ClientRequestBatchRun(
            values=(batch, mp.CommandBatch((command, command)))),
        mp.ReadRequest(slot=5, command=command),
        mp.SequentialReadRequest(slot=-1, command=command),
        mp.EventualReadRequest(command=command),
        mp.ReadReplyBatch(batch=(mp.ReadReply(cid, 9, b"r1"),)),
        mp.ClientReplyBatch(batch=(mp.ClientReply(cid, 11, b"x"),)),
        # multipaxos read-batcher + leader-change redirects (paxflow
        # COD301 burn-down, extended tags 133-143)
        mp.ReadRequestBatch(slot=5, commands=(command,)),
        mp.SequentialReadRequestBatch(slot=-1, commands=(command,)),
        mp.EventualReadRequestBatch(commands=(command, command)),
        mp.BatchMaxSlotRequest(read_batcher_index=1,
                               read_batcher_id=7),
        mp.BatchMaxSlotReply(read_batcher_index=1, read_batcher_id=7,
                             group_index=0, acceptor_index=2,
                             slot=1 << 40),
        mp.NotLeaderClient(),
        mp.LeaderInfoRequestClient(),
        mp.LeaderInfoReplyClient(round=9),
        mp.NotLeaderBatcher(
            client_request_batch=mp.ClientRequestBatch(batch)),
        mp.LeaderInfoRequestBatcher(),
        mp.LeaderInfoReplyBatcher(round=2),
        # mencius
        mn.Chosen(slot=7, value=mn.NOOP),
        mn.HighWatermark(next_slot=1 << 33),
        mn.Phase2aNoopRange(slot_start_inclusive=3,
                            slot_end_exclusive=99, round=2),
        mn.Phase2bNoopRange(acceptor_group_index=1, acceptor_index=2,
                            slot_start_inclusive=3,
                            slot_end_exclusive=99, round=2),
        mn.ChosenNoopRange(slot_start_inclusive=0,
                           slot_end_exclusive=50),
        mn.Phase2aRun(start_slot=1, stride=2, round=0,
                      values=(batch,)),
        mn.Phase2bRun(acceptor_group_index=0, acceptor_index=1,
                      start_slot=1, count=2, stride=2, round=0),
        mn.ChosenRun(start_slot=1, stride=2, values=(batch,)),
        # mencius leader-change redirects (extended tags 144-149)
        mn.NotLeaderClient(leader_group_index=2),
        mn.LeaderInfoRequestClient(),
        mn.LeaderInfoReplyClient(leader_group_index=1, round=5),
        mn.NotLeaderBatcher(
            leader_group_index=0,
            client_request_batch=mp.ClientRequestBatch(batch)),
        mn.LeaderInfoRequestBatcher(),
        mn.LeaderInfoReplyBatcher(leader_group_index=3, round=9),
        # epaxos
        em.PreAccept(Instance(0, 4), (1, 0), ecommand, 7, edeps),
        em.PreAcceptOk(Instance(0, 4), (1, 0), 2, 7, edeps),
        em.Accept(Instance(0, 4), (1, 0), em.NOOP, 7, edeps),
        em.AcceptOk(Instance(0, 4), (1, 0), 2),
        em.Commit(Instance(0, 4), ecommand, 7, edeps),
        em.ClientRequest(ecommand),
        em.ClientReply(0, 1, b"r"),
        # simplebpaxos (+ the GcBPaxos SnapshotMarker escape hatch)
        bp.ClientRequest(bcommand),
        bp.DependencyRequest(bp.VertexId(0, 3), bcommand),
        bp.DependencyReply(bp.VertexId(0, 3), 1, bdeps),
        bp.Propose(bp.VertexId(1, 0), SnapshotMarker(), bdeps),
        bp.Phase2a(bp.VertexId(1, 0), 4,
                   bp.VoteValue(bcommand, bdeps)),
        bp.Phase2b(bp.VertexId(1, 0), 2, 4),
        bp.Commit(bp.VertexId(1, 0), bcommand, bdeps),
        bp.ClientReply(1, 2, b"result"),
        # unanimousbpaxos
        ub.ClientRequest(bcommand),
        ub.DependencyRequest(bp.VertexId(0, 2), bcommand),
        ub.FastProposal(bp.VertexId(0, 2), ub.VoteValue(
            bcommand, frozenset({bp.VertexId(0, 1)}))),
        ub.Phase2bFast(bp.VertexId(0, 2), 1, ub.VoteValue(
            bcommand, frozenset())),
        ub.Phase2a(bp.VertexId(0, 2), 3, ub.VoteValue(
            bp.NOOP, frozenset())),
        ub.Phase2bClassic(bp.VertexId(0, 2), 1, 3),
        ub.Commit(bp.VertexId(0, 2), ub.VoteValue(
            bcommand, frozenset())),
        ub.ClientReply(0, 1, b"r"),
        # scalog
        sc.ClientRequest(scommand),
        sc.Backup(1, 7, scommand),
        sc.ShardInfo(0, 1, (3, 5)),
        sc.CutChosen(2, sc.GlobalCut((3, 5))),
        sc.Chosen(2, (scommand,)),
        sc.ClientReply(sc.CommandId(("h", 5), 3), 9, b"r"),
        # horizontal
        hz.ClientRequest(hcommand),
        hz.Phase2a(slot=5, round=1, first_slot=0, value=hcommand),
        hz.Phase2b(slot=5, round=1, acceptor_index=2),
        hz.Chosen(slot=5, value=hz.Configuration(
            {"kind": "simple", "members": [0, 1, 2]})),
        hz.ClientReply(hz.CommandId("c", 0, 1), b"r"),
        # matchmakermultipaxos
        mmp.ClientRequest(mcommand),
        mmp.Phase2a(slot=5, round=1, value=mcommand),
        mmp.Phase2b(slot=5, round=1, acceptor_index=2),
        mmp.Chosen(slot=5, value=mcommand),
        mmp.ClientReply(mmp.CommandId("c", 0, 1), b"r"),
        # fasterpaxos
        fsp.ClientRequest(2, fscommand),
        fsp.Phase2a(slot=5, round=1, value=fscommand),
        fsp.Phase2b(server_index=0, slot=5, round=1,
                    command=fscommand),
        fsp.Phase3a(slot=5, value=fsp.NOOP),
        fsp.ClientReply(fsp.CommandId("c", 0, 1), b"r"),
        # vanillamencius
        vm.ClientRequest(vcommand),
        vm.Phase2a(sending_server=0, slot=5, round=1, value=vcommand),
        vm.Skip(server_index=1, start_slot_inclusive=3,
                stop_slot_exclusive=9),
        vm.Phase2b(server_index=1, slot=5, round=1),
        vm.Chosen(slot=5, value=vcommand, is_revocation=False),
        vm.ClientReply(vm.CommandId("c", 0, 1), b"r"),
        # craq
        cq.WriteBatch((cq.Write(ccid, "k", "v"),), seq=7),
        cq.ReadBatch((cq.Read(ccid, "k"),)),
        cq.TailRead(cq.ReadBatch((cq.Read(ccid, "k"),))),
        cq.Ack(cq.WriteBatch((cq.Write(ccid, "k", "v"),), seq=7)),
        cq.ClientReply(ccid),
        cq.ReadReply(ccid, "v"),
        # paxworld (tags 201-202): the bare client-edge shapes, so
        # the lane classifier sees CRAQ client traffic.
        cq.Write(ccid, "k", "v"),
        cq.Read(ccid, "k"),
        # paxchaos (tag 203): the chain re-link (control lane).
        cq.ChainReconfigure(version=2, chain=(("h", 1), ("h", 2))),
        # fastmultipaxos
        fmp.ProposeRequest(fcommand),
        fmp.ProposeReply(fmp.CommandId(("h", 5), 3), b"r", round=2),
        fmp.Phase2a(slot=5, round=1, value=fcommand),
        fmp.Phase2b(acceptor_id=0, slot=5, round=1, vote=fcommand),
        fmp.Phase2bBuffer((
            fmp.Phase2b(acceptor_id=0, slot=5, round=1,
                        vote=fmp.NOOP),)),
        fmp.ValueChosen(slot=5, value=fcommand),
        # baselines
        ec.EchoRequest("hello"),
        ec.EchoReply("hello back"),
        ur.ClientRequest(("10.0.0.1", 9000), 3, 1, b"cmd"),
        ur.ClientReply(3, 1, b"result"),
        bu.ClientRequest(bu.Command(bu.CommandId(("h", 1), 7), b"x")),
        bu.ClientRequestBatch((bu.Command(bu.CommandId("c1", 0),
                                          b"a"),)),
        bu.ClientReply(bu.CommandId("c1", 0), b"r"),
        bu.ClientReplyBatch((bu.ClientReply(bu.CommandId("c1", 0),
                                            b"r0"),)),
        px.ProposeRequest("v"), px.ProposeReply("chosen"),
        px.Phase1a(3), px.Phase1b(3, 1, 2, "earlier"),
        px.Phase2a(3, "v"), px.Phase2b(1, 3),
        fp.ProposeRequest("v"), fp.ProposeReply("chosen"),
        fp.Phase1a(4), fp.Phase1b(4, 0, 0, "fast"),
        fp.Phase2a(4, "v"), fp.Phase2b(2, 4),
        cp.ClientRequest(("h", 5), 9, frozenset({1, 5})),
        cp.ClientReply(9, frozenset({2})),
        cp.Phase1a(1), cp.Phase1b(1, 2, 0, frozenset({4})),
        cp.Phase2a(1, frozenset({1, 2})), cp.Phase2b(1, 0),
        cp.Nack(7),
        mkp.ClientRequest("v"), mkp.ClientReply("chosen"),
        mkp.MatchRequest(mkp.AcceptorGroup(
            2, {"kind": "simple_majority", "members": [0, 1, 2]})),
        mkp.MatchReply(2, 1, (mkp.AcceptorGroup(
            0, {"kind": "grid", "grid": [[1, 0], [2, 3]]}),)),
        mkp.Phase1a(2), mkp.Phase1b(2, 1, mkp.Phase1bVote(0, "old")),
        mkp.Phase2a(2, "v"), mkp.Phase2b(2, 1),
        mkp.MatchmakerNack(5), mkp.AcceptorNack(6),
    ]
    # reconfig (paxepoch): the extended tag page (0x00-escaped).
    from frankenpaxos_tpu import reconfig as rc

    samples += [
        rc.Reconfigure(members=(("10.0.0.1", 9000), "a1", "a2")),
        rc.EpochCommit(epoch=1, start_slot=64, f=1, round=2,
                       members=("a0", ("10.0.0.2", 9001), "a3")),
        rc.EpochAck(epoch=1, round=2),
        rc.EpochPhase2aRun(epoch=1, start_slot=64, round=2,
                           values=(batch, mp.NOOP)),
    ]
    # serve (paxload): the admission-control reject reply.
    from frankenpaxos_tpu import serve

    samples += [
        serve.Rejected(entries=((2, 7), (3, 9)), retry_after_ms=250,
                       reason=2),
    ]
    # paxwire (runtime/paxwire.py + protocols/multipaxos/wire.py): the
    # batch envelopes and the coalesced ack batch -- transport-layer
    # frames, but they share the wire tag space and the containment
    # contract, so they fuzz like every role-sent message.
    from frankenpaxos_tpu.protocols.multipaxos.wire import Phase2bAckBatch
    from frankenpaxos_tpu.runtime import paxwire

    seg1 = DEFAULT_SERIALIZER.to_bytes(HOT_MESSAGES[0])
    seg2 = DEFAULT_SERIALIZER.to_bytes(HOT_MESSAGES[6])
    samples += [
        paxwire.FrameBatch((seg1, seg1, seg2)),
        paxwire.ClientFrameBatch((seg2,)),
        Phase2bAckBatch(ranges=((5, 9, 1, 0, 2), (11, 12, 1, 0, 2))),
        # COD301 burn-down (tags 153-159): the failover cold path.
        mp.Phase1a(round=3, chosen_watermark=64),
        mp.Phase1b(group_index=0, acceptor_index=1, round=3,
                   info=(mp.Phase1bSlotInfo(slot=5, vote_round=1,
                                            vote_value=batch),
                         mp.Phase1bSlotInfo(slot=6, vote_round=2,
                                            vote_value=mp.NOOP)),
                   epochs=(rc.EpochCommit(epoch=1, start_slot=64, f=1,
                                          round=2,
                                          members=("a0", "a1")),)),
        mp.Nack(round=7),
        mp.Recover(slot=99),
        fmp.Phase1bNack(acceptor_id=1, round=5),
        vm.Phase1Nack(start_slot_inclusive=2, stop_slot_exclusive=9,
                      round=4),
        vm.Phase2Nack(slot=3, round=6),
    ]
    # paxgeo (protocols/wpaxos, tags 160-172): every message carries a
    # fixed layout from day one.
    import frankenpaxos_tpu.protocols.wpaxos  # noqa: F401
    from frankenpaxos_tpu.geo.epochs import GeoEpoch
    from frankenpaxos_tpu.protocols.wpaxos import messages as wp

    wentry = GeoEpoch(group=2, epoch=3, start_slot=17, home_zone=1,
                      ballot=7)
    samples += [
        wp.WRequest(group=2, command=command, steal=True),
        wp.WReply(command_id=cid, group=2, slot=9, result=b"r"),
        wp.WNotOwner(group=2, command_id=cid, home_zone=1, ballot=4),
        wp.Steal(group=2),
        wp.WPhase1a(group=2, ballot=7, epoch=3),
        wp.WPhase1b(group=2, ballot=7, epoch=3, acceptor=5,
                    votes=(wp.WVote(slot=4, ballot=1, value=batch),
                           wp.WVote(slot=5, ballot=2, value=mp.NOOP)),
                    epochs=(wentry,)),
        wp.WPhase2a(group=2, slot=9, ballot=7, value=batch),
        wp.WPhase2b(group=2, slot=9, ballot=7, acceptor=5),
        wp.WNack(group=2, ballot=8, home_zone=0),
        wp.WChosen(group=2, slot=9, value=batch),
        wp.WEpochCommit(entry=wentry),
        wp.WEpochAck(group=2, epoch=3),
        wp.WRecover(group=2, slot=4),
    ]
    # COD301 burn-down tranche 3 (tags 173-180): the epaxos/bpaxos
    # recovery cold paths + horizontal's reconfigure/chaos admin.
    samples += [
        em.Prepare(instance=Instance(0, 5), ballot=(2, 1)),
        em.Nack(instance=Instance(1, 3), largest_ballot=(4, 0)),
        em.PrepareOk(ballot=(2, 1), instance=Instance(0, 5),
                     replica_index=1, vote_ballot=(1, 0),
                     status=em.CommandStatus.ACCEPTED,
                     command_or_noop=ecommand, sequence_number=7,
                     dependencies=edeps),
        bp.Phase1a(vertex_id=bp.VertexId(0, 3), round=2),
        bp.Phase1b(vertex_id=bp.VertexId(0, 3), acceptor_id=1,
                   round=2, vote_round=1,
                   vote_value=bp.VoteValue(bcommand, bdeps)),
        bp.Nack(vertex_id=bp.VertexId(1, 9), higher_round=4),
        hz.Reconfigure({"kind": "grid", "grid": [[0, 1], [2, 3]]}),
        hz.Die(),
    ]
    # COD301 burn-down tranche 4 (tags 181-191): the matchmaker
    # epoch-change single-decree Paxos + GC pair, and scalog's
    # steady-state cut proposal loop.
    mmp_mc = mmp.MatchmakerConfiguration(
        epoch=2, reconfigurer_index=1, matchmaker_indices=(3, 4, 5))
    samples += [
        mmp.Stopped(epoch=2),
        mmp.GarbageCollect(matchmaker_configuration=mmp_mc,
                           gc_watermark=9),
        mmp.GarbageCollectAck(epoch=2, matchmaker_index=4,
                              gc_watermark=9),
        mmp.MatchPhase1a(matchmaker_configuration=mmp_mc, round=7),
        mmp.MatchPhase1b(epoch=2, round=7, matchmaker_index=3,
                         vote_round=5, vote_value=mmp_mc),
        mmp.MatchPhase2a(matchmaker_configuration=mmp_mc, round=7,
                         value=mmp_mc),
        mmp.MatchPhase2b(epoch=2, round=7, matchmaker_index=3),
        mmp.MatchChosen(value=mmp_mc),
        mmp.MatchNack(epoch=2, round=7),
        sc.ProposeCut(sc.GlobalCut((3, 5, 1 << 40))),
        sc.RawCutChosen(slot=6, raw_cut_or_noop=sc.GlobalCut((3, 5))),
        fsp.Phase2aAny(round=3, delegates=(0, 2), start_slot=64),
        fsp.Phase2aAnyAck(server_index=2, round=3),
        fsp.RoundInfo(round=3, delegates=(0, 2)),
    ]
    # COD301 burn-down tranche 5 (tags 195-200, paxsim): the
    # matchmaker whole-log transfers (round -> quorum-system dict
    # logs) and simplebpaxos hole recovery.
    mmp_configs = (
        (3, {"kind": "simple_majority", "members": [0, 1, 2]}),
        (5, {"kind": "grid", "grid": [[0, 1], [2, 3]]}),
    )
    samples += [
        mmp.Stop(matchmaker_configuration=mmp_mc),
        mmp.StopAck(matchmaker_index=4, epoch=2, gc_watermark=9,
                    configurations=mmp_configs),
        mmp.Bootstrap(epoch=3, reconfigurer_index=1, gc_watermark=9,
                      configurations=mmp_configs),
        mmp.BootstrapAck(matchmaker_index=4, epoch=3),
        mmp.ReconfigureMatchmakers(matchmaker_configuration=mmp_mc,
                                   new_matchmaker_indices=(6, 7, 8)),
        bp.Recover(vertex_id=bp.VertexId(1, 9)),
    ]
    # paxingest run descriptors (ingest/wire.py, tags 204-205 + 210):
    # the disseminator/sequencer hot path, including the lazy
    # value-array boundary and the paxfan pipelining seq/credit pair.
    from frankenpaxos_tpu.ingest.messages import (
        IngestCredit,
        IngestRun,
        NotLeaderIngest,
    )

    ingest_run = IngestRun(
        batcher_index=1,
        values=(mp.CommandBatch((command,)),
                mp.CommandBatch((mp.Command(
                    mp.CommandId(("10.0.0.2", 9001), 3, 8),
                    b"second"),))),
        seq=7)
    samples += [
        ingest_run,
        NotLeaderIngest(group_index=1, run=ingest_run),
        IngestCredit(group_index=1, watermark_seq=7),
    ]
    # COD301 burn-down, final tranche (tags 206-207, paxown): the
    # simplegcbpaxos snapshot cold path -- the baseline is now empty.
    from frankenpaxos_tpu.protocols import simplegcbpaxos as gcbp
    from frankenpaxos_tpu.protocols.simplebpaxos.messages import (
        VertexIdPrefixSet,
    )

    gc_watermark = VertexIdPrefixSet(2)
    gc_watermark.add(bp.VertexId(0, 0))
    gc_watermark.add(bp.VertexId(1, 0))
    gc_watermark.add(bp.VertexId(1, 3))
    samples += [
        gcbp.SnapshotRequest(),
        gcbp.CommitSnapshot(
            id=4,
            watermark=gc_watermark.to_dict(),
            state_machine=b"\x00register state",
            client_table={"kv": [{
                "client": (("10.0.0.1", 5000), 2),
                "largest_id": 7,
                "largest_output": b"ok",
                "executed_ids": {"watermark": 6, "values": [7]},
            }]}),
    ]
    # paxruns dep-reply runs (runs/wire.py, tags 208-209): the
    # drain-coalesced dependency columns for epaxos/simplebpaxos --
    # transport-layer frames like Phase2bAckBatch, fuzzed like every
    # role-sent message. Column layout: B=2 entries x L=2 leaders.
    from frankenpaxos_tpu.runs.wire import DepReplyRun, PreAcceptOkRun

    samples += [
        PreAcceptOkRun(
            num_leaders=2,
            headers=((0, 4, 1, 0, 2, 7), (1, 9, 1, 0, 2, 3)),
            watermarks=(1, 0, 2, 1), counts=(1, 0, 2, 0),
            values=(3, 5, 6)),
        DepReplyRun(
            num_leaders=2,
            headers=((0, 3, 1), (1, 5, 2)),
            watermarks=(2, 1, 0, 0), counts=(0, 1, 1, 0),
            values=(4, 2)),
    ]
    by_tag: dict = {}
    for message in samples:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] < 128, type(message).__name__
        tag = data[0] if data[0] else 128 + data[1]
        by_tag.setdefault(tag, message)
    return by_tag, serializer._CODECS_BY_TAG


def test_every_registered_codec_has_a_fuzz_sample():
    """Completeness gate: a new wire codec without a containment-fuzz
    sample fails HERE, so the registry-wide fuzz can never silently
    lose coverage."""
    by_tag, registry = all_codec_samples()
    missing = sorted(set(registry) - set(by_tag))
    assert not missing, (
        f"registered wire tags without a fuzz sample: "
        f"{[(t, type(registry[t]).__name__) for t in missing]}")


def test_registry_wide_corrupt_frame_containment():
    """Single-byte and truncation corruption over EVERY registered
    codec's frame: decode yields garbage or ValueError -- never an
    uncontrolled exception type escaping to the connection task (the
    transport guard catches broadly, but WAL replay and tools rely on
    the ValueError channel)."""
    import random

    by_tag, registry = all_codec_samples()
    rng = random.Random(13)
    for tag, message in sorted(by_tag.items()):
        data = DEFAULT_SERIALIZER.to_bytes(message)
        # decode must round-trip cleanly first (sanity).
        decoded = DEFAULT_SERIALIZER.from_bytes(data)
        assert type(decoded) is type(message), tag
        trials = 40 if len(data) > 2 else 10
        for _ in range(trials):
            corrupt = bytearray(data)
            mode = rng.random()
            if mode < 0.5 and len(corrupt) > 1:
                corrupt[rng.randrange(1, len(corrupt))] ^= \
                    1 << rng.randrange(8)
            elif mode < 0.8 and len(corrupt) > 1:
                corrupt[rng.randrange(1, len(corrupt))] = 0xFF
            else:
                corrupt = corrupt[:rng.randrange(1, len(corrupt) + 1)]
            try:
                got = DEFAULT_SERIALIZER.from_bytes(bytes(corrupt))
                values = getattr(got, "values", None)
                if values is not None:
                    list(values)  # force the lazy boundary
            except ValueError:
                pass  # the contract: ValueError or garbage
    # The WAL record codecs honor the same contract in their own tag
    # space (recovery treats any ValueError as a torn frame).
    from frankenpaxos_tpu.wal.records import WAL_SERIALIZER
    from frankenpaxos_tpu.wal import (
        WalChosenRun,
        WalEpoch,
        WalNoopRange,
        WalPromise,
        WalSnapshot,
        WalVote,
        WalVoteRun,
    )
    from frankenpaxos_tpu.reconfig import encode_epoch_config

    from frankenpaxos_tpu.geo.epochs import GeoEpoch as _GeoEpoch
    from frankenpaxos_tpu.protocols.wpaxos.wire import encode_geo_epoch
    from frankenpaxos_tpu.wal import WalGeoEpoch, WalGeoPromise, WalGeoVote

    for record in [WalPromise(round=3),
                   WalVote(slot=7, round=1, value=b"\x01ab"),
                   WalVoteRun(start_slot=1, stride=2, round=0,
                              values=b"\x00\x01"),
                   WalNoopRange(slot_start_inclusive=0,
                                slot_end_exclusive=9, round=1),
                   WalChosenRun(start_slot=3, stride=1, values=b"zz"),
                   WalEpoch(payload=encode_epoch_config(
                       1, 64, 1, 2, ("a0", ("10.0.0.2", 9001)))),
                   WalGeoPromise(group=2, ballot=7),
                   WalGeoVote(group=2, slot=9, ballot=7,
                              value=b"\x01ab"),
                   WalGeoEpoch(payload=encode_geo_epoch(_GeoEpoch(
                       group=2, epoch=3, start_slot=17, home_zone=1,
                       ballot=7))),
                   WalSnapshot(payload=b"snap")]:
        data = WAL_SERIALIZER.to_bytes(record)
        for _ in range(40):
            corrupt = bytearray(data)
            if rng.random() < 0.7 and len(corrupt) > 1:
                corrupt[rng.randrange(len(corrupt))] ^= \
                    1 << rng.randrange(8)
            else:
                corrupt = corrupt[:rng.randrange(1, len(corrupt) + 1)]
            try:
                WAL_SERIALIZER.from_bytes(bytes(corrupt))
            except ValueError:
                pass


def test_run_pipeline_codecs_fuzz():
    """Property fuzz for the run-pipeline codecs: random value arrays
    round-trip exactly, and random byte corruptions either decode to
    SOMETHING or raise ValueError -- never an uncontrolled exception
    type (struct.error/IndexError escaping the lazy boundary)."""
    import random

    from frankenpaxos_tpu.runtime.serializer import DEFAULT_SERIALIZER
    from frankenpaxos_tpu.protocols.multipaxos.messages import (
        ChosenRun,
        Command,
        CommandBatch,
        CommandId,
        NOOP,
        Phase2aRun,
    )

    rng = random.Random(7)

    def random_value():
        if rng.random() < 0.2:
            return NOOP
        return CommandBatch(tuple(
            Command(CommandId(
                ("10.0.0.%d" % rng.randrange(4), 9000 + rng.randrange(4)),
                rng.randrange(8), rng.randrange(1 << 40)),
                bytes(rng.randrange(256) for _ in range(rng.randrange(12))))
            for _ in range(rng.randrange(1, 4))))

    for trial in range(60):
        n = rng.randrange(1, 20)
        message = (Phase2aRun(start_slot=rng.randrange(1 << 40),
                              round=rng.randrange(1 << 20),
                              values=tuple(random_value()
                                           for _ in range(n)))
                   if trial % 2 else
                   ChosenRun(start_slot=rng.randrange(1 << 40),
                             values=tuple(random_value()
                                          for _ in range(n))))
        data = DEFAULT_SERIALIZER.to_bytes(message)
        decoded = DEFAULT_SERIALIZER.from_bytes(data)
        assert tuple(decoded.values) == tuple(message.values), trial
        # Re-encode of the lazy array is byte-identical.
        assert DEFAULT_SERIALIZER.to_bytes(decoded) == data, trial

        # Random single-byte corruption: containment, not correctness.
        corrupt = bytearray(data)
        corrupt[rng.randrange(1, len(corrupt))] ^= 0xFF
        try:
            d2 = DEFAULT_SERIALIZER.from_bytes(bytes(corrupt))
            if hasattr(d2, "values"):
                list(d2.values)  # force the lazy decode
        except ValueError:
            pass  # the contract: ValueError or garbage, nothing else


def test_wpaxos_codecs_round_trip():
    """paxgeo (protocols/wpaxos): every message rides a fixed layout
    from day one -- no pickle, extended tags 160-172."""
    import frankenpaxos_tpu.protocols.wpaxos  # noqa: F401
    from frankenpaxos_tpu.geo.epochs import GeoEpoch
    from frankenpaxos_tpu.protocols.wpaxos import messages as wp

    cid = wp.CommandId(("10.0.0.1", 9000), 2, 7)
    sim_cid = wp.CommandId("client-0", 0, 3)
    command = wp.Command(cid, b"geo-payload")
    batch = wp.CommandBatch((command,))
    entry = GeoEpoch(group=1, epoch=2, start_slot=64, home_zone=2,
                     ballot=5)
    for message in [
        wp.WRequest(group=1, command=command),
        wp.WRequest(group=1, command=wp.Command(sim_cid, b""),
                    steal=True),
        wp.WReply(command_id=cid, group=1, slot=64, result=b"ok"),
        wp.WNotOwner(group=1, command_id=sim_cid, home_zone=2,
                     ballot=5),
        wp.Steal(group=3),
        wp.WPhase1a(group=1, ballot=5, epoch=2),
        wp.WPhase1b(group=1, ballot=5, epoch=2, acceptor=7,
                    votes=(), epochs=(entry,)),
        wp.WPhase1b(group=1, ballot=5, epoch=2, acceptor=7,
                    votes=(wp.WVote(slot=3, ballot=2, value=batch),
                           wp.WVote(slot=4, ballot=2,
                                    value=wp.NOOP)),
                    epochs=()),
        wp.WPhase2a(group=1, slot=64, ballot=5, value=batch),
        wp.WPhase2a(group=1, slot=64, ballot=5, value=wp.NOOP),
        wp.WPhase2b(group=1, slot=64, ballot=5, acceptor=7),
        wp.WNack(group=1, ballot=8, home_zone=0),
        wp.WChosen(group=1, slot=64, value=batch),
        wp.WEpochCommit(entry=entry),
        wp.WEpochAck(group=1, epoch=2),
        wp.WRecover(group=1, slot=12),
    ]:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] == 0, type(message).__name__  # extended page
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_wpaxos_request_is_client_lane():
    """The frame classifier can shed WRequest under overload without
    decoding it (serve/lanes.py); everything else in the unit --
    votes, steals, epoch commits -- stays control."""
    import frankenpaxos_tpu.protocols.wpaxos  # noqa: F401
    from frankenpaxos_tpu.protocols.wpaxos import messages as wp
    from frankenpaxos_tpu.serve.lanes import (
        frame_lane,
        LANE_CLIENT,
        LANE_CONTROL,
    )

    command = wp.Command(wp.CommandId("c", 0, 1), b"x")
    request = DEFAULT_SERIALIZER.to_bytes(
        wp.WRequest(group=0, command=command))
    assert frame_lane(request) == LANE_CLIENT
    for message in [wp.WPhase1a(group=0, ballot=1, epoch=1),
                    wp.WPhase2b(group=0, slot=1, ballot=1,
                                acceptor=0),
                    wp.Steal(group=0),
                    wp.WEpochAck(group=0, epoch=1)]:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert frame_lane(data) == LANE_CONTROL, type(message).__name__


def test_cod301_burn_down_tranche3_round_trip():
    """epaxos Prepare/PrepareOk/Nack, simplebpaxos Phase1a/Phase1b/
    Nack, and horizontal Reconfigure/Die graduated from the pickle
    fallback (tags 173-180; .paxlint-baseline.json 30 -> 22)."""
    import frankenpaxos_tpu.protocols.epaxos  # noqa: F401
    import frankenpaxos_tpu.protocols.horizontal as hz
    import frankenpaxos_tpu.protocols.simplebpaxos  # noqa: F401
    from frankenpaxos_tpu.protocols.epaxos import messages as em
    from frankenpaxos_tpu.protocols.epaxos.instance_prefix_set import (
        Instance,
        InstancePrefixSet,
    )
    from frankenpaxos_tpu.protocols.simplebpaxos import messages as bp

    edeps = InstancePrefixSet(2)
    edeps.add(Instance(0, 1))
    bdeps = bp.VertexIdPrefixSet(2)
    bdeps.add(bp.VertexId(0, 1))
    for message in [
        em.Prepare(instance=Instance(0, 5), ballot=(2, 1)),
        em.Nack(instance=Instance(1, 3), largest_ballot=(4, 0)),
        em.PrepareOk(ballot=(2, 1), instance=Instance(0, 5),
                     replica_index=1, vote_ballot=(1, 0),
                     status=em.CommandStatus.PRE_ACCEPTED,
                     command_or_noop=em.Command("c", 0, 1, b"xyz"),
                     sequence_number=7, dependencies=edeps),
        em.PrepareOk(ballot=(2, 1), instance=Instance(0, 5),
                     replica_index=1, vote_ballot=(-1, -1),
                     status=em.CommandStatus.NOT_SEEN,
                     command_or_noop=None, sequence_number=None,
                     dependencies=None),
        bp.Phase1a(vertex_id=bp.VertexId(0, 3), round=2),
        bp.Phase1b(vertex_id=bp.VertexId(0, 3), acceptor_id=1,
                   round=2, vote_round=-1, vote_value=None),
        bp.Phase1b(vertex_id=bp.VertexId(0, 3), acceptor_id=1,
                   round=2, vote_round=1,
                   vote_value=bp.VoteValue(bp.NOOP, bdeps)),
        bp.Nack(vertex_id=bp.VertexId(1, 9), higher_round=4),
        hz.Reconfigure({"kind": "simple_majority",
                        "members": [0, 1, 2]}),
        hz.Reconfigure({"kind": "zone_grid",
                        "grid": [[0, 1, 2], [3, 4, 5]]}),
        hz.Die(),
    ]:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] == 0, type(message).__name__  # extended page
        back = DEFAULT_SERIALIZER.from_bytes(data)
        assert repr(back) == repr(message)


def test_cod301_burn_down_tranche4_round_trip():
    """Matchmaker epoch-change Paxos (Stopped/GC/GCAck/MatchPhase1a/
    1b/2a/2b/MatchChosen/MatchNack) and scalog's ProposeCut/
    RawCutChosen graduated from the pickle fallback (tags 181-191;
    .paxlint-baseline.json 22 -> 8)."""
    import frankenpaxos_tpu.protocols.matchmakermultipaxos as mmp
    import frankenpaxos_tpu.protocols.scalog as sc

    mc = mmp.MatchmakerConfiguration(
        epoch=3, reconfigurer_index=0, matchmaker_indices=(0, 1, 2))
    mc2 = mmp.MatchmakerConfiguration(
        epoch=4, reconfigurer_index=1, matchmaker_indices=(3, 4, 5))
    for message in [
        mmp.Stopped(epoch=0),
        mmp.GarbageCollect(matchmaker_configuration=mc,
                           gc_watermark=1 << 40),
        mmp.GarbageCollectAck(epoch=3, matchmaker_index=2,
                              gc_watermark=0),
        mmp.MatchPhase1a(matchmaker_configuration=mc, round=9),
        mmp.MatchPhase1b(epoch=3, round=9, matchmaker_index=1,
                         vote_round=-1, vote_value=None),
        mmp.MatchPhase1b(epoch=3, round=9, matchmaker_index=1,
                         vote_round=4, vote_value=mc2),
        mmp.MatchPhase2a(matchmaker_configuration=mc, round=9,
                         value=mc2),
        mmp.MatchPhase2b(epoch=3, round=9, matchmaker_index=0),
        mmp.MatchChosen(value=mc2),
        mmp.MatchNack(epoch=3, round=9),
        sc.ProposeCut(sc.GlobalCut(())),
        sc.ProposeCut(sc.GlobalCut((0, 7, 1 << 50))),
        sc.RawCutChosen(slot=0, raw_cut_or_noop=sc.Noop()),
        sc.RawCutChosen(slot=1 << 40,
                        raw_cut_or_noop=sc.GlobalCut((1, 2, 3))),
    ]:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] == 0, type(message).__name__  # extended page
        assert DEFAULT_SERIALIZER.from_bytes(data) == message
    # The fasterpaxos delegation-control trio (tags 192-194): the
    # protocol whose SAFE903 double-choose this PR fixed keeps its
    # failover traffic off the pickle fallback too.
    import frankenpaxos_tpu.protocols.fasterpaxos as fsp

    for message in [
        fsp.Phase2aAny(round=0, delegates=(), start_slot=0),
        fsp.Phase2aAny(round=9, delegates=(0, 1, 4), start_slot=1 << 40),
        fsp.Phase2aAnyAck(server_index=4, round=9),
        fsp.RoundInfo(round=9, delegates=(2,)),
    ]:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] == 0, type(message).__name__  # extended page
        assert DEFAULT_SERIALIZER.from_bytes(data) == message


def test_cod301_burn_down_tranche5_round_trip():
    """The matchmaker whole-log transfers (Stop/StopAck/Bootstrap/
    BootstrapAck/ReconfigureMatchmakers, tags 195-199) and
    simplebpaxos Recover (tag 200) graduated from the pickle fallback
    (.paxlint-baseline.json 8 -> 2, paxsim). The quorum-system dict
    payloads cover all four structured kinds plus the guarded-pickle
    escape hatch for exotic dicts."""
    import frankenpaxos_tpu.protocols.matchmakermultipaxos as mmp
    from frankenpaxos_tpu.protocols.simplebpaxos import messages as bp
    from frankenpaxos_tpu.runtime import serializer

    mc = mmp.MatchmakerConfiguration(
        epoch=3, reconfigurer_index=0, matchmaker_indices=(0, 1, 2))
    configs = (
        (0, {"kind": "simple_majority", "members": [0, 1, 2]}),
        (2, {"kind": "unanimous_writes", "members": [3, 4]}),
        (4, {"kind": "grid", "grid": [[0, 1, 2], [3, 4, 5]]}),
        (6, {"kind": "zone_grid", "grid": [[0, 1], [2, 3], [4, 5]]}),
        (8, {"kind": "grid", "grid": []}),
    )
    for message in [
        mmp.Stop(matchmaker_configuration=mc),
        mmp.StopAck(matchmaker_index=1, epoch=3, gc_watermark=1 << 40,
                    configurations=configs),
        mmp.StopAck(matchmaker_index=0, epoch=0, gc_watermark=-1,
                    configurations=()),
        mmp.Bootstrap(epoch=4, reconfigurer_index=1, gc_watermark=0,
                      configurations=configs),
        mmp.BootstrapAck(matchmaker_index=2, epoch=4),
        mmp.ReconfigureMatchmakers(matchmaker_configuration=mc,
                                   new_matchmaker_indices=()),
        mmp.ReconfigureMatchmakers(matchmaker_configuration=mc,
                                   new_matchmaker_indices=(5, 6, 7)),
        bp.Recover(vertex_id=bp.VertexId(0, 0)),
        bp.Recover(vertex_id=bp.VertexId(3, 1 << 40)),
    ]:
        data = DEFAULT_SERIALIZER.to_bytes(message)
        assert data[0] == 0, type(message).__name__  # extended page
        assert DEFAULT_SERIALIZER.from_bytes(data) == message
    # Exotic quorum-system dicts (unknown kind, non-int members) ride
    # the guarded-pickle hatch: round-trip with the fallback enabled,
    # refused at the SENDER with it disabled.
    exotic = mmp.StopAck(
        matchmaker_index=1, epoch=3, gc_watermark=2,
        configurations=((1, {"kind": "weighted",
                             "weights": {"a": 2}}),))
    data = DEFAULT_SERIALIZER.to_bytes(exotic)
    assert data[0] == 0
    assert DEFAULT_SERIALIZER.from_bytes(data) == exotic
    serializer.set_pickle_fallback(False)
    try:
        import pytest as _pytest

        with _pytest.raises(ValueError, match="pickle fallback"):
            DEFAULT_SERIALIZER.to_bytes(exotic)
        # The structured kinds stay fully binary under the same flag.
        plain = mmp.StopAck(matchmaker_index=1, epoch=3,
                            gc_watermark=2, configurations=configs)
        assert DEFAULT_SERIALIZER.from_bytes(
            DEFAULT_SERIALIZER.to_bytes(plain)) == plain
    finally:
        serializer.set_pickle_fallback(True)


def test_tranche4_rejects_hostile_index_values():
    """Index VALUES are validated at decode, not just counts: a
    negative delegate/matchmaker index would silently wrap a Python
    list lookup (misrouting), and a huge one would IndexError deep in
    the actor loop instead of dying here as a corrupt frame."""
    import pytest

    import frankenpaxos_tpu.protocols.fasterpaxos as fsp
    import frankenpaxos_tpu.protocols.matchmakermultipaxos as mmp

    good = DEFAULT_SERIALIZER.to_bytes(
        fsp.RoundInfo(round=1, delegates=(0,)))
    hostile = bytearray(good)
    # delegates live after [0x00][tag][i64 round][i32 count]: flip the
    # sole index to -1.
    hostile[-4:] = (-1).to_bytes(4, "little", signed=True)
    with pytest.raises(ValueError):
        DEFAULT_SERIALIZER.from_bytes(bytes(hostile))
    good = DEFAULT_SERIALIZER.to_bytes(mmp.MatchChosen(
        value=mmp.MatchmakerConfiguration(
            epoch=1, reconfigurer_index=0, matchmaker_indices=(2,))))
    hostile = bytearray(good)
    hostile[-4:] = (1 << 30).to_bytes(4, "little", signed=True)
    with pytest.raises(ValueError):
        DEFAULT_SERIALIZER.from_bytes(bytes(hostile))

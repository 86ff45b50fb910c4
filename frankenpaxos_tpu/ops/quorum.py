"""The TpuQuorumChecker: batched quorum-vote aggregation on device.

This is the keystone kernel of the framework (BASELINE.json north star).
It replaces the reference's per-message vote-collection loops --
multipaxos/ProxyLeader.scala:217-258 (Phase2b -> Chosen),
multipaxos/Leader.scala:504-576 (Phase1b quorums),
multipaxos/Client.scala:851-933 (MaxSlot read quorums) -- with a
persistent device **vote board** plus one jitted, state-donating step per
event-loop drain.

Layout (TPU-first): the board is ``votes[acceptors, window]`` --
**slot-major along the 128-wide lane dimension**. A ``[window, n_acc]``
layout with a tiny trailing dim wastes >95% of every (8, 128) TPU tile;
transposed, every op runs at full lane utilization (measured ~40x faster
on v5e).

Two update paths:

  * **dense blocks** (the hot path): slots are allocated contiguously, so
    a drain's votes for slot range ``[start, start+B)`` are a dense
    ``[n, B]`` bitmask applied with ``dynamic_update_slice`` -- no
    scatter at all. Measured ~1.5-4G slot-checks/s on one v5e core.
  * **sparse scatter** (stragglers, retries, out-of-order): classic
    ``.at[nodes, slots].max`` scatter; ~40x slower per element but only
    used for the thin out-of-order tail.

The quorum predicate itself is ``counts = masks @ votes_block`` (a
``[G, N] x [N, B]`` matmul) + compare + any/all over groups -- see
quorums/spec.py for how every quorum system factors into this form.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from frankenpaxos_tpu.quorums.spec import ANY, QuorumSpec

# Plain int (promoted inside jit): creating a device array at import
# time would initialize the backend in every process that merely imports
# a protocol module.
_NEG_INF32 = -(2**31) + 1


class VoteBoard(NamedTuple):
    """Per-slot vote-collection state for a window of slots.

    The window is a ring over slot space: column ``slot % window`` holds
    slot ``slot``. Each column carries its current OWNER slot number, so
    wrapping is self-reclaiming: a vote for a newer slot landing on a
    column still holding ``slot - window`` clears the stale state in the
    same kernel pass, and a straggler vote for a slot the ring has moved
    past is dropped. This replaces the host-driven watermark GC the
    reference needs (util/BufferMap.scala:8-66) -- no release() plumbing
    is required for correctness, only ``window`` > max slots in flight.
    """

    votes: jax.Array   # [n, window] uint8: acceptor voted in `rounds[slot]`
    rounds: jax.Array  # [window] int32: highest round seen per slot
    chosen: jax.Array  # [window] bool: quorum already reached
    owner: jax.Array   # [window] int32: slot currently occupying the column


def _named(name: str):
    """Give a function a fixed name before it is jitted: a device trace
    then shows ``jit_<name>`` for the tracker's entry points, whatever
    the Python functions are called after a refactor."""
    def rename(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return rename


@functools.partial(jax.jit, static_argnums=(0, 1))
@_named("fpx_quorum_make_board")
def make_vote_board(window: int, num_nodes: int) -> VoteBoard:
    """An empty board, made on the device by ONE program (eager
    ``jnp.zeros`` / ``jnp.full`` are a compiled program each, and a
    ``convert_element_type`` beside every fill value)."""
    return VoteBoard(
        votes=jnp.zeros((num_nodes, window), dtype=jnp.uint8),
        rounds=jnp.full((window,), -1, dtype=jnp.int32),
        chosen=jnp.zeros((window,), dtype=jnp.bool_),
        owner=jnp.full((window,), -1, dtype=jnp.int32),
    )


def _quorum_hit(votes_block: jax.Array, masks: jax.Array,
                thresholds: jax.Array, combine_any: bool) -> jax.Array:
    """``[B]`` bool from a ``[N, B]`` vote block: the predicate matmul."""
    counts = masks @ votes_block.astype(jnp.int32)        # [G, B]
    satisfied = counts >= thresholds[:, None]
    return satisfied.any(0) if combine_any else satisfied.all(0)


def grid_layout(masks, thresholds, combine_any: bool):
    """Detect a Grid quorum predicate in factored (masks, thresholds)
    form (quorums/Grid.scala:5-57 via quorums/spec.py).

    Returns ``(kind, rows, cols, perm)`` when the spec is a grid:
    ``kind`` is ``"write"`` ("one vote in every row": thresholds all 1,
    ALL-combine) or ``"read"`` ("some row fully present": thresholds ==
    row sizes, ANY-combine); ``perm`` is a column permutation into
    row-major ``[rows, cols]`` order, or None when the universe is
    already row-major. Returns None for anything else.

    Grids deserve a first-class fast path (Flexible Paxos,
    arXiv:1608.06696): the generic ``[G, N] x [N, B]`` int32 mask
    matmul degenerates, for a grid, to a pure boolean
    reshape-to-``[rows, cols, B]`` col-OR/row-AND (write) or
    col-AND/row-OR (read) reduction -- no dtype widening, no MXU pass,
    and bit-identical booleans (votes are 0/1, so ``count >= 1`` IS
    ``any`` and ``count >= cols`` IS ``all``).
    """
    masks = np.asarray(masks, dtype=np.uint8)
    thresholds = np.asarray(thresholds, dtype=np.int64)
    if masks.ndim != 2:
        return None
    g, n = masks.shape
    if g < 1 or n < 1 or n % g != 0:
        return None
    cols = n // g
    # Rows must partition the universe into equal-size groups.
    if not (masks.sum(axis=0) == 1).all():
        return None
    if not (masks.sum(axis=1) == cols).all():
        return None
    if combine_any:
        if not (thresholds == cols).all():
            return None
        kind = "read"
    else:
        if not (thresholds == 1).all():
            return None
        kind = "write"
    perm = np.concatenate([np.flatnonzero(masks[r]) for r in range(g)])
    if (perm == np.arange(n)).all():
        return kind, g, cols, None
    return kind, g, cols, tuple(int(x) for x in perm)


def _fused_grid_hit(votes_block: jax.Array, grid: tuple) -> jax.Array:
    """``[B]`` bool from a ``[N, B]`` vote block via the fused grid
    reduction (see :func:`grid_layout`).

    The rows/cols reductions are UNROLLED at trace time into a chain of
    elementwise uint8 ``|``/``&`` ops over the block's row vectors (a
    grid has a handful of rows): XLA fuses the whole chain into the
    block's producer pass, where `jnp.any`/`jnp.all` reduce ops over a
    tiny leading axis break fusion and cost ~3x on host XLA. Votes are
    0/1, so ``|`` IS any and ``&`` IS all -- bit-identity preserved.
    """
    kind, rows, cols, perm = grid
    row_of = (lambda i: votes_block[i]) if perm is None \
        else (lambda i: votes_block[perm[i]])
    acc = None
    for r in range(rows):
        row = row_of(r * cols)
        for c in range(1, cols):
            cell = row_of(r * cols + c)
            row = (row | cell) if kind == "write" else (row & cell)
        acc = row if acc is None \
            else ((acc & row) if kind == "write" else (acc | row))
    return acc.astype(jnp.bool_)


def _predicate_hit(votes_block: jax.Array, masks_t: tuple,
                   meta: tuple) -> jax.Array:
    """Trace-time kernel selection: the fused grid reduction when
    ``_spec_statics`` tagged the spec as a grid, else the generic
    factored matmul."""
    thresholds_t, combine_any = meta[0], meta[1]
    grid = meta[2] if len(meta) > 2 else None
    if grid is not None:
        return _fused_grid_hit(votes_block, grid)
    masks = jnp.asarray(np.asarray(masks_t, dtype=np.int32))
    thresholds = jnp.asarray(np.asarray(thresholds_t, dtype=np.int32))
    return _quorum_hit(votes_block, masks, thresholds, combine_any)


def _apply_sparse_votes(board: VoteBoard, slots, true_slots, nodes,
                        vote_rounds, valid):
    """Shared traced body of the sparse scatter kernels: ring
    self-reclaim + round preemption + vote recording, WITHOUT the
    quorum predicate (the single-spec and epoch-segmented kernels each
    attach their own). Returns ``(votes, new_rounds, chosen0, owner,
    mine)``."""
    # Ring self-reclaim: a newer slot claims its column (clearing stale
    # state from `slot - k*window`); votes for slots the column has moved
    # past are dropped. All per-column derived values are identical for
    # duplicate batch entries, so duplicate scatters are deterministic.
    old_owner = board.owner[slots]                              # [B]
    owner = board.owner.at[slots].max(
        jnp.where(valid, true_slots, _NEG_INF32))
    cur_owner = owner[slots]                                    # [B]
    reclaimed = cur_owner > old_owner                           # [B]
    mine = valid & (true_slots == cur_owner)
    cols0 = board.votes[:, slots]                               # [N, B]
    cols0 = jnp.where(reclaimed[None, :], jnp.uint8(0), cols0)
    votes0 = board.votes.at[:, slots].set(cols0)
    rounds0 = board.rounds.at[slots].set(
        jnp.where(reclaimed, jnp.int32(-1), board.rounds[slots]))
    chosen0 = board.chosen.at[slots].set(
        jnp.where(reclaimed, False, board.chosen[slots]))

    old_rounds = rounds0[slots]                                 # [B]
    new_rounds = rounds0.at[slots].max(
        jnp.where(mine, vote_rounds, _NEG_INF32))
    cur = new_rounds[slots]                                     # [B]
    # A newer round preempts: clear the slot's votes (ProxyLeader state is
    # per (slot, round); an old column must not count toward the new
    # round). `preempted` depends only on slot-level values, so duplicate
    # batch entries for one slot all scatter identical columns.
    preempted = cur > old_rounds                                # [B]
    cols = votes0[:, slots]                                     # [N, B]
    cols = jnp.where(preempted[None, :], jnp.uint8(0), cols)
    votes = votes0.at[:, slots].set(cols)
    # Record votes that are for the slot's (possibly new) current round.
    live = mine & (vote_rounds == cur)
    votes = votes.at[nodes, slots].max(live.astype(jnp.uint8))
    return votes, new_rounds, chosen0, owner, mine


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(6, 7))
@_named("fpx_quorum_record_votes")
def _record_and_check(
    board: VoteBoard,
    slots: jax.Array,      # [B] int32, already reduced mod window
    true_slots: jax.Array,  # [B] int32 un-modded slot numbers (owner ids)
    nodes: jax.Array,      # [B] int32 acceptor rows
    vote_rounds: jax.Array,  # [B] int32
    valid: jax.Array,      # [B] bool (padding mask for partial batches)
    masks_t: tuple,        # static: ((row, ...), ...) -> rebuilt as [G, N]
    meta: tuple,           # static: (thresholds, combine_any, grid|None)
) -> tuple[VoteBoard, jax.Array]:
    """Sparse path: out-of-order / straggler votes. O(batch) work."""
    votes, new_rounds, chosen0, owner, mine = _apply_sparse_votes(
        board, slots, true_slots, nodes, vote_rounds, valid)
    # Quorum predicate for exactly the touched columns (duplicates are
    # fine: they see identical post-scatter state).
    hit = _predicate_hit(votes[:, slots], masks_t, meta)
    hit = hit & mine
    newly = hit & ~chosen0[slots]
    chosen = chosen0.at[slots].max(hit)
    return VoteBoard(votes, new_rounds, chosen, owner), newly


@functools.partial(jax.jit, donate_argnums=(0,))
@_named("fpx_quorum_record_votes_epochs")
def _record_and_check_epochs(
    board: VoteBoard,
    slots: jax.Array,        # [B] int32, reduced mod window
    true_slots: jax.Array,   # [B] int32 un-modded slot numbers
    nodes: jax.Array,        # [B] int32 acceptor rows (union universe)
    vote_rounds: jax.Array,  # [B] int32
    valid: jax.Array,        # [B] bool
    boundaries: jax.Array,   # [K-1] int64: start slots of epochs 1..K-1
    masks: jax.Array,        # [K, G, N] padded per-epoch masks
    thresholds: jax.Array,   # [K, G]
    combine_any: jax.Array,  # [K] bool
) -> tuple[VoteBoard, jax.Array]:
    """The epoch-segmented sparse kernel: identical board update to
    :func:`_record_and_check`, but each vote's quorum predicate is
    selected by its SLOT's epoch (``searchsorted`` over the epoch
    activation boundaries), so one fused drain can span a handover
    boundary -- old-epoch columns keep counting under the old spec
    while new-epoch columns count under the new one."""
    votes, new_rounds, chosen0, owner, mine = _apply_sparse_votes(
        board, slots, true_slots, nodes, vote_rounds, valid)
    config_idx = jnp.searchsorted(boundaries, true_slots, side="right")
    hit = _check_batch_multi(votes[:, slots].T, config_idx, masks,
                             thresholds, combine_any)
    hit = hit & mine
    newly = hit & ~chosen0[slots]
    chosen = chosen0.at[slots].max(hit)
    return VoteBoard(votes, new_rounds, chosen, owner), newly


#: Bytes of the three int32 scalars that ride below a dense block's votes.
_WHERE_BYTES = 12


def _stage_block(block: np.ndarray, width: int, start: int,
                 true_start: int, vote_round: int) -> np.ndarray:
    """The ONE host buffer of a ``_record_block`` call: ``block``'s votes
    in the first N rows, zero-padded to ``width`` columns, and below them
    the call's three scalars as little-endian int32 bytes (one more row
    at any width from 12 columns up). Every argument the host hands a
    jitted call is a transfer of its own on the calling thread; on the
    chip one buffer read a seventh less host time a drain than the
    scalars in an ``int32[3]`` of their own, and little more than half
    of three scalar arguments (``PERF.md``, PR 30)."""
    n, b = block.shape
    staged = np.zeros((n + -(-_WHERE_BYTES // width), width),
                      dtype=np.uint8)
    staged[:n, :b] = block
    staged[n:].reshape(-1)[:_WHERE_BYTES] = np.array(
        (start, true_start, vote_round), dtype="<i4").view(np.uint8)
    return staged


def _apply_block_votes(board: VoteBoard, staged: jax.Array, predicate):
    """Shared traced body of the dense kernels: ring self-reclaim + round
    preemption + vote recording by slices, then ``predicate(cols,
    owner) -> [B] bool`` over the block's columns and the slot each now
    holds (the single-spec and the epoch-segmented kernel each hand in
    their own) and the chosen plane. ``staged`` is
    :func:`_stage_block`'s buffer. Returns the new board and the ``[B]``
    newly-chosen mask."""
    n, block_size = board.votes.shape[0], staged.shape[1]
    block = staged[:n]
    start, true_start, vote_round = jax.lax.bitcast_convert_type(
        staged[n:].reshape(-1)[:_WHERE_BYTES].reshape(3, 4), jnp.int32)

    # named_scope: a trace read in Perfetto maps each device operation
    # (the copies and dynamic_update_slices) to one of these three.
    with jax.named_scope("owner-update"):
        touched = block.any(axis=0)                            # [B]
        # Ring self-reclaim (see VoteBoard): claim columns still owned
        # by an older slot; drop votes for slots the column has moved
        # past.
        slot_ids = true_start + jnp.arange(block_size, dtype=jnp.int32)
        old_owner = jax.lax.dynamic_slice(board.owner, (start,),
                                          (block_size,))
        claim = touched & (slot_ids > old_owner)
        stale = touched & (slot_ids < old_owner)
        touched = touched & ~stale
        new_owner = jnp.where(claim, slot_ids, old_owner)
        block = block & touched[None, :].astype(jnp.uint8)
        owner = jax.lax.dynamic_update_slice(board.owner, new_owner,
                                             (start,))

    with jax.named_scope("record"):
        old_rounds = jax.lax.dynamic_slice(board.rounds, (start,),
                                           (block_size,))
        old_rounds = jnp.where(claim, jnp.int32(-1), old_rounds)
        new_rounds = jnp.where(touched,
                               jnp.maximum(old_rounds, vote_round),
                               old_rounds)
        preempted = new_rounds > old_rounds
        cols = jax.lax.dynamic_slice(board.votes, (0, start),
                                     (n, block_size))
        cols = jnp.where((claim | preempted)[None, :], jnp.uint8(0), cols)
        live = touched & (vote_round == new_rounds)            # [B]
        cols = cols | (block & live[None, :].astype(jnp.uint8))
        votes = jax.lax.dynamic_update_slice(board.votes, cols,
                                             (0, start))
        rounds = jax.lax.dynamic_update_slice(board.rounds, new_rounds,
                                              (start,))

    with jax.named_scope("check"):
        # The slot a column HOLDS, not the one the block would put
        # there: a column this block does not touch (or whose vote was
        # stale) is judged as its owner's, so it reads as it did when
        # its last vote was recorded.
        hit = predicate(cols, new_owner)
        old_chosen = jax.lax.dynamic_slice(board.chosen, (start,),
                                           (block_size,))
        old_chosen = jnp.where(claim, False, old_chosen)
        newly = hit & ~old_chosen & touched
        chosen = jax.lax.dynamic_update_slice(board.chosen,
                                              hit | old_chosen, (start,))
    return VoteBoard(votes=votes, rounds=rounds, chosen=chosen,
                     owner=owner), newly


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(2, 3))
@_named("fpx_quorum_record_block")
def _record_block(
    board: VoteBoard,
    staged: jax.Array,       # uint8, from _stage_block: [N, B] vote
                             # arrivals for these slots, then the ring
                             # offset of the block, the slot number of
                             # that column and the votes' round
    masks_t: tuple,
    meta: tuple,
) -> tuple[VoteBoard, jax.Array]:
    """Dense path: votes for a contiguous slot block, one round.

    The steady-state Phase2b stream (Leader.scala:331-408 allocates slots
    contiguously; ProxyLeader collects in slot order) maps here: no
    scatter, only slicing. Returns the ``[B]`` newly-chosen mask.

    Columns with no vote in ``block`` (gap slots inside the run, or
    bucket padding) are left untouched -- in particular their rounds are
    NOT bumped, so an older-round slot mid-run keeps collecting its own
    round's votes (matching the per-(slot, round) dict semantics).
    """
    return _apply_block_votes(
        board, staged,
        lambda cols, owner: _predicate_hit(cols, masks_t, meta))


def _epoch_block_hit(cols: jax.Array, slot_ids: jax.Array,
                     boundaries: jax.Array, masks: jax.Array,
                     thresholds: jax.Array,
                     combine_any: jax.Array) -> jax.Array:
    """``[B]`` bool from a ``[N, B]`` vote block whose column ``b``
    holds slot ``slot_ids[b]``, each column judged under its slot's
    epoch.

    No per-column indexing (``masks[config_idx]`` is a B-row gather, and
    indexed access costs this chip about a microsecond an index): EVERY
    plane's predicate is evaluated for every column, one ``[K*G, N] x
    [N, B]`` product with the columns along the lanes, and the plane that
    governs a column is picked by comparing its slot number with the
    boundaries: plane ``k`` governs where ``boundaries[k-1] <= slot <
    boundaries[k]``, which is ``searchsorted(..., side="right")`` as a
    one-hot (of equal boundaries the last wins; a padding plane starts
    at the largest int32 and governs nothing)."""
    k, g, n = masks.shape
    counts = (masks.reshape(k * g, n).astype(jnp.int32)
              @ cols.astype(jnp.int32))                        # [K*G, B]
    satisfied = (counts >= thresholds.reshape(k * g, 1)).reshape(k, g, -1)
    plane_hit = jnp.where(combine_any[:, None], satisfied.any(1),
                          satisfied.all(1))                    # [K, B]
    begun = slot_ids[None, :] >= boundaries[:, None]           # [K-1, B]
    edge = jnp.ones((1, slot_ids.shape[0]), dtype=jnp.bool_)
    governs = (jnp.concatenate([edge, begun])
               & ~jnp.concatenate([begun, ~edge]))             # [K, B]
    return (plane_hit & governs).any(0)


@functools.partial(jax.jit, donate_argnums=(0,))
@_named("fpx_quorum_record_block_epochs")
def _record_block_epochs(
    board: VoteBoard,
    staged: jax.Array,       # uint8, from _stage_block
    boundaries: jax.Array,   # [K-1] int32: start slots of epochs 1..K-1
    masks: jax.Array,        # [K, G, N] padded per-epoch masks
    thresholds: jax.Array,   # [K, G]
    combine_any: jax.Array,  # [K] bool
) -> tuple[VoteBoard, jax.Array]:
    """The epoch-segmented dense kernel: :func:`_record_block`'s board
    update, with each column's quorum predicate that of its SLOT's
    epoch, so a block spans any number of hand-over boundaries. The
    planes live on the device (:func:`_place_planes`): a launch
    transfers the one staged buffer."""
    return _apply_block_votes(
        board, staged,
        lambda cols, owner: _epoch_block_hit(
            cols, owner, boundaries, masks, thresholds, combine_any))


@functools.partial(jax.jit, donate_argnums=(0,))
@_named("fpx_quorum_release")
def _release(board: VoteBoard, slots: jax.Array, valid: jax.Array) -> VoteBoard:
    """Reset columns for GC'd slots so the ring can wrap
    (BufferMap.scala:55-62)."""
    votes = board.votes.at[:, slots].set(
        jnp.where(valid[None, :], jnp.uint8(0), board.votes[:, slots]))
    rounds = board.rounds.at[slots].set(
        jnp.where(valid, jnp.int32(-1), board.rounds[slots]))
    chosen = board.chosen.at[slots].set(
        jnp.where(valid, False, board.chosen[slots]))
    owner = board.owner.at[slots].set(
        jnp.where(valid, jnp.int32(-1), board.owner[slots]))
    return VoteBoard(votes, rounds, chosen, owner)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _check_batch(present: jax.Array, masks_t: tuple, meta: tuple) -> jax.Array:
    """``[B, N]`` responder rows -> ``[B]`` bool (stateless)."""
    return _predicate_hit(present.T, masks_t, meta)


@jax.jit
def _check_batch_multi(
    present: jax.Array,       # [B, N]
    config_idx: jax.Array,    # [B] int32
    masks: jax.Array,         # [K, G, N]
    thresholds: jax.Array,    # [K, G]
    combine_any: jax.Array,   # [K] bool
) -> jax.Array:
    """Per-row quorum check under per-row configurations.

    This is the Matchmaker reconfiguration shape (SURVEY.md section 2.3):
    quorum systems change per round, so each checked row selects its own
    padded (masks, thresholds) plane.
    """
    sel_masks = masks[config_idx].astype(jnp.int32)        # [B, G, N]
    counts = jnp.einsum("bn,bgn->bg", present.astype(jnp.int32), sel_masks)
    satisfied = counts >= thresholds[config_idx]
    return jnp.where(combine_any[config_idx],
                     satisfied.any(-1), satisfied.all(-1))


def _shard_board(board: VoteBoard, mesh, window: int) -> VoteBoard:
    """Lay a :class:`VoteBoard` out over ``mesh``: the SLOT axis shards
    over every mesh axis (the slot-partitioning scaling axis, SURVEY.md
    section 2.3 / multipaxos/DistributionScheme) while the acceptor
    axis stays whole per device. Each device holds
    ``window / mesh.size`` columns; XLA's partitioner inserts the
    collectives for cross-shard scatters and block updates, and results
    stay bit-identical to the unsharded board
    (tests/test_multichip_checker.py)."""
    from jax.sharding import NamedSharding, PartitionSpec

    if window % mesh.size != 0:
        raise ValueError(f"window {window} must be a multiple of "
                         f"the mesh size {mesh.size}")
    axes = tuple(mesh.axis_names)
    slot_sharded = NamedSharding(mesh, PartitionSpec(axes))
    return VoteBoard(
        votes=jax.device_put(
            board.votes, NamedSharding(mesh, PartitionSpec(None, axes))),
        rounds=jax.device_put(board.rounds, slot_sharded),
        chosen=jax.device_put(board.chosen, slot_sharded),
        owner=jax.device_put(board.owner, slot_sharded),
    )


def _place_planes(planes: tuple, mesh) -> tuple:
    """Put host predicate planes on the device once, so that no drain
    transfers them again; with a ``mesh`` fully REPLICATED over it
    (the epoch-plane rule: predicate planes are tiny and every shard
    checks its own slots against all of them, so replication beats any
    split; explicit placement, so the drain kernels never re-lay them
    out and DEV1203 stays clean)."""
    if mesh is None:
        placement = jax.devices()[0]
    else:
        from jax.sharding import NamedSharding, PartitionSpec

        placement = NamedSharding(mesh, PartitionSpec())
    return tuple(jax.device_put(plane, placement) for plane in planes)


def _pin_board(board: VoteBoard, mesh) -> VoteBoard:
    """A board that rides beside placed planes, placed like them: with
    no mesh COMMITTED to the planes' device (a sharded board already
    is). A jitted call's outputs are committed as soon as one input is,
    so a board that starts out uncommitted (fresh from
    :func:`make_vote_board`, or adopted from a single-spec checker)
    would meet each program twice: as it is, and as the first call
    returns it -- and the second meeting compiles on the event loop."""
    if mesh is not None:
        return board
    return jax.device_put(board, jax.devices()[0])


def _spec_statics(spec: QuorumSpec) -> tuple[tuple, tuple]:
    """Hashable statics for the jitted kernels: ``(masks_t, meta)``
    where ``meta = (thresholds_t, combine_any, grid_or_None)``. Grid
    specs are detected HERE, once per checker, so every kernel built
    from these statics selects the fused grid reduction at trace time
    (see :func:`grid_layout`)."""
    masks_t = tuple(tuple(int(x) for x in row) for row in spec.masks)
    combine_any = spec.combine == ANY
    thresholds_t = tuple(int(t) for t in spec.thresholds)
    meta = (thresholds_t, combine_any,
            grid_layout(spec.masks, spec.thresholds, combine_any))
    return masks_t, meta


def epoch_column_map(old_universe, new_universe) -> np.ndarray:
    """``[N_new]`` int32 gather map for an epoch reshape: new column
    ``i`` draws its votes from old column ``map[i]``, or ``-1`` when
    universe node ``new_universe[i]`` is new to the board (its column
    starts empty). Node ids removed by the new universe simply have no
    image -- their columns are dropped (the shrink half of
    pad/shrink)."""
    old_col = {node: i for i, node in enumerate(old_universe)}
    return np.asarray([old_col.get(node, -1) for node in new_universe],
                      dtype=np.int32)


@jax.jit
def _reshape_columns(block: jax.Array, cmap: jax.Array) -> jax.Array:
    """``[N_old, B] x [N_new] -> [N_new, B]``: the epoch reshape gather
    (column permutation + pad with zero columns + shrink). One fused
    gather+select -- no host round trip for the board's vote matrix."""
    src = jnp.clip(cmap, 0, block.shape[0] - 1)
    return jnp.where((cmap >= 0)[:, None], block[src],
                     jnp.zeros((), dtype=block.dtype))


def reshape_block(block: np.ndarray, old_universe,
                  new_universe) -> np.ndarray:
    """Host wrapper over :func:`_reshape_columns` for a standalone
    ``[N_old, B]`` vote block (drain blocks crossing an epoch
    boundary)."""
    cmap = epoch_column_map(old_universe, new_universe)
    return np.asarray(_reshape_columns(np.asarray(block), cmap))


class _BoardChecker:
    """The host side of a stateful vote board, shared by the single-spec
    and the epoch-segmented checker: bucket padding, the one staged
    buffer of a dense call, ring surveillance, release. A subclass sets
    ``num_nodes`` (the rows a dense block must have), calls
    :meth:`_init_board`, and supplies the two jitted kernels with its
    predicate's arguments (:meth:`_block_kernel`,
    :meth:`_votes_kernel`)."""

    def _init_board(self, window: int, rows: int, mesh) -> None:
        """``mesh``: an optional ``jax.sharding.Mesh``. When given, the
        vote board's SLOT axis shards over every mesh axis (the
        slot-partitioning scaling axis, SURVEY.md section 2.3 /
        multipaxos/DistributionScheme): each device holds
        ``window / mesh.size`` columns and XLA's partitioner inserts the
        collectives for cross-shard scatters and block updates. Results
        are bit-identical to the unsharded board (asserted by
        tests/test_multichip_checker.py)."""
        if window <= 0:
            raise ValueError("window must be positive")
        self.window = window
        # Ring-invariant surveillance (the "window > max slots in
        # flight" contract, see VoteBoard): a vote whose slot trails the
        # newest recorded slot by >= window may land on a reclaimed
        # column and be silently dropped on device -- which manifests as
        # a permanently-unchosen slot. Detect it host-side from the slot
        # numbers we already have (no kernel change, no sync): count
        # violations and log the first occurrence loudly.
        self._max_slot_seen = -1
        self.window_violations = 0
        self.board = make_vote_board(window, rows)
        if mesh is not None:
            self.board = _shard_board(self.board, mesh, window)

    def _block_kernel(self, staged: np.ndarray) -> jax.Array:
        """Run the dense kernel on ``self.board``; the device mask."""
        raise NotImplementedError

    def _votes_kernel(self, slots, true_slots, nodes, rounds,
                      valid) -> jax.Array:
        """Run the scatter kernel on ``self.board``; the device mask."""
        raise NotImplementedError

    def record_block_async(self, start_slot: int, block: np.ndarray,
                           vote_round: int = 0) -> jax.Array:
        """Like :meth:`record_block` but returns the DEVICE newly-chosen
        mask without waiting -- callers overlap the device round-trip
        with host work and fetch later (np.asarray).

        The returned array keeps the PADDED bucket length (entries past
        the input width are padding) -- slicing it on device would
        dispatch a fresh variable-shape executable per width; slice on
        the host after fetching instead.

        ONE runtime call with ONE host buffer (:func:`_stage_block`),
        which the jitted program places itself: no eager ``jnp``
        operation, each of which is a compiled program or a transfer
        of its own."""
        n, b = block.shape
        if n != self.num_nodes:
            raise ValueError(f"block has {n} acceptor rows, the board "
                             f"has {self.num_nodes}")
        start = start_slot % self.window
        if start + b > self.window:
            raise ValueError(
                f"block [{start}, {start + b}) straddles the ring end "
                f"(window {self.window}); split it")
        self._note_slot_span(start_slot, start_slot + b - 1)
        padded = 64
        while padded < b:
            padded *= 2
        if start + padded > self.window:
            padded = b
        return self._block_kernel(
            _stage_block(block, padded, start, start_slot, vote_round))

    def record_block(self, start_slot: int, block: np.ndarray,
                     vote_round: int = 0) -> np.ndarray:
        """Dense path: record ``block[n, B]`` arrivals for slots
        ``[start_slot, start_slot + B)`` (must not straddle the ring end);
        return the ``[B]`` newly-chosen mask.

        Widths are bucketed to powers of two so variable drain sizes
        compile O(log max_width) kernels, not one per width. Padding
        columns are all-zero, which the kernel leaves untouched.
        """
        b = block.shape[1]
        # paxlint: disable=TPU203 -- explicit sync wrapper; hot paths
        # use record_block_async and fetch off the drain.
        return np.asarray(self.record_block_async(start_slot, block,
                                                  vote_round))[:b]

    def record_and_check_async(
        self,
        slots: Sequence[int] | np.ndarray,
        node_cols: Sequence[int] | np.ndarray,
        rounds: Sequence[int] | np.ndarray | None = None,
        pad_to: int | None = None,
    ) -> jax.Array:
        """Like :meth:`record_and_check` but returns the DEVICE per-vote
        mask without waiting. The returned array keeps the PADDED batch
        length (see :meth:`record_block_async`); slice on the host."""
        slots = np.asarray(slots, dtype=np.int32)
        b = slots.shape[0]
        if b:
            self._note_slot_span(int(slots.min()), int(slots.max()))
        if rounds is None:
            rounds = np.zeros(b, dtype=np.int32)
        if pad_to is None:
            # Bucket to powers of two so variable drain sizes compile
            # O(log max_batch) kernels, not one per size.
            pad_to = 64
            while pad_to < b:
                pad_to *= 2
        size = max(pad_to, b)
        slots_p = np.zeros(size, dtype=np.int32)
        true_p = np.zeros(size, dtype=np.int32)
        nodes_p = np.zeros(size, dtype=np.int32)
        rounds_p = np.zeros(size, dtype=np.int32)
        valid = np.zeros(size, dtype=bool)
        slots_p[:b] = slots % self.window
        true_p[:b] = slots
        nodes_p[:b] = np.asarray(node_cols, dtype=np.int32)
        rounds_p[:b] = np.asarray(rounds, dtype=np.int32)
        valid[:b] = True
        return self._votes_kernel(slots_p, true_p, nodes_p, rounds_p,
                                  valid)

    def record_and_check(
        self,
        slots: Sequence[int] | np.ndarray,
        node_cols: Sequence[int] | np.ndarray,
        rounds: Sequence[int] | np.ndarray | None = None,
        pad_to: int | None = None,
    ) -> np.ndarray:
        """Sparse path: record out-of-order votes; return per-vote "slot
        newly has quorum".

        Duplicate slots in one batch each report quorum; callers dedup
        (the host side keeps the small pending-slot dict, as ProxyLeader
        keeps `states`, ProxyLeader.scala:135).
        """
        b = np.asarray(slots).shape[0]
        # paxlint: disable=TPU203 -- explicit sync wrapper; hot paths
        # use record_and_check_async and fetch off the drain.
        return np.asarray(self.record_and_check_async(
            slots, node_cols, rounds, pad_to))[:b]

    def _note_slot_span(self, lowest: int, highest: int) -> None:
        """Flag votes that trail the frontier by >= window (they may hit
        a self-reclaimed column and be dropped on device). The batch's
        own span counts too: two same-batch slots >= window apart alias
        one column regardless of the prior frontier."""
        if max(self._max_slot_seen, highest) - lowest >= self.window:
            self.window_violations += 1
            if self.window_violations == 1:
                import warnings

                warnings.warn(
                    f"{type(self).__name__}: vote for slot {lowest} trails "
                    f"the frontier ({self._max_slot_seen}) by >= window "
                    f"({self.window}); straggler votes may be silently "
                    f"dropped -- raise `window` above the max slots in "
                    f"flight (further violations counted in "
                    f"`window_violations` without warning)",
                    RuntimeWarning, stacklevel=3)
        if highest > self._max_slot_seen:
            self._max_slot_seen = highest

    def release(self, slots: Sequence[int] | np.ndarray) -> None:
        """GC slot columns below the chosen watermark so the ring can wrap."""
        slots = np.asarray(slots, dtype=np.int32) % self.window
        valid = np.ones(slots.shape[0], dtype=bool)
        self.board = _release(self.board, slots, valid)


class TpuQuorumChecker(_BoardChecker):
    """Stateful batched quorum checking for one quorum predicate.

    Typical use (ProxyLeader Phase2b path)::

        checker = TpuQuorumChecker(qs.write_spec(), window=1 << 20)
        # hot path: contiguous slot block, dense [n, B] arrival mask
        newly = checker.record_block(start_slot, arrivals, round=3)
        # thin tail: out-of-order votes
        newly = checker.record_and_check(slots, acceptor_cols, rounds)

    One call per event-loop drain, thousands of votes per call.
    """

    def __init__(self, spec: QuorumSpec, window: int, mesh=None):
        """``mesh``: see :meth:`_BoardChecker._init_board`."""
        self.spec = spec
        self.num_nodes = spec.num_nodes
        self._masks_t, self._meta = _spec_statics(spec)
        self._init_board(window, spec.num_nodes, mesh)

    def _block_kernel(self, staged):
        self.board, newly = _record_block(self.board, staged,
                                          self._masks_t, self._meta)
        return newly

    def _votes_kernel(self, slots, true_slots, nodes, rounds, valid):
        self.board, newly = _record_and_check(
            self.board, slots, true_slots, nodes, rounds, valid,
            self._masks_t, self._meta)
        return newly

    def reshape(self, new_spec: QuorumSpec) -> None:
        """Epoch reshape: remap the live board's ACCEPTOR axis onto
        ``new_spec``'s universe and swap the predicate, in place.

        The ``[acceptors, window]`` vote matrix is re-laid-out by ONE
        on-device gather (:func:`_reshape_columns`): columns permute to
        the new universe order, members new to the universe get empty
        columns (pad), members the new universe drops lose theirs
        (shrink). Slot-axis state (rounds/chosen/owner) is untouched --
        an epoch changes who votes, not which slots exist -- so a board
        mid-collection survives the handover: votes already recorded
        for surviving acceptors keep counting, bit-identical to
        replaying them onto a fresh new-universe board (asserted
        against the two-config ``quorums/systems.py`` oracle in
        tests/test_reconfig.py)."""
        cmap = epoch_column_map(self.spec.universe, new_spec.universe)
        self.board = VoteBoard(
            votes=_reshape_columns(self.board.votes, cmap),
            rounds=self.board.rounds,
            chosen=self.board.chosen,
            owner=self.board.owner,
        )
        self.spec = new_spec
        self.num_nodes = new_spec.num_nodes
        self._masks_t, self._meta = _spec_statics(new_spec)

    def check_batch(self, present: np.ndarray) -> np.ndarray:
        """Stateless: evaluate the predicate for ``[B, N]`` responder rows."""
        return np.asarray(_check_batch(np.asarray(present), self._masks_t,
                                       self._meta))


#: Planes an epoch stack holds at least, and the rows its board's
#: acceptor axis is allocated by (see ``_rebuild_universe``).
_MIN_PLANES = 128
_ROW_TILE = 8


class EpochSegmentedChecker(_BoardChecker):
    """Quorum checking where each SLOT selects its epoch's predicate.

    The reconfiguration (paxepoch) shape: epochs partition slot space
    at activation watermarks (epoch ``k`` governs ``[start_k,
    start_{k+1})``), each with its own acceptor set and QuorumSpec.
    Specs are padded into one ``[K, G, N]`` plane stack over the UNION
    universe (``quorums.spec.pad_specs``), and every kernel selects a
    slot's plane by its slot number against the activation boundaries
    -- so ONE fused call (stateless ``check_batch``, the stateful
    scatter ``record_and_check`` or the stateful dense
    ``record_block``, which take and pad what
    :class:`TpuQuorumChecker`'s do) spans the handover boundary instead
    of splitting the drain at it.

    ``add_epoch`` grows the stack in place: specs reindex onto the
    widened union universe and the live vote board reshapes by the
    same on-device gather as :meth:`TpuQuorumChecker.reshape` --
    mid-flight votes for surviving acceptors keep counting across the
    handover.

    ``mesh``: an optional ``jax.sharding.Mesh``. The board's SLOT axis
    shards over every mesh axis (:func:`_shard_board`, the same layout
    as the sharded TpuQuorumChecker) while the epoch planes
    (``masks``/``thresholds``/``combine_any``/``boundaries``) are
    REPLICATED: every shard's slots select their own plane by
    searchsorted, so the plane stack must be whole on every device.
    Results stay bit-identical to the unsharded checker
    (tests/test_multichip_epoch.py, vs the two-config systems oracle).
    """

    def __init__(self, specs: Sequence[QuorumSpec],
                 boundaries: Sequence[int], window: int = 4096,
                 mesh=None):
        if len(specs) != len(boundaries):
            raise ValueError(
                f"{len(specs)} specs vs {len(boundaries)} boundaries")
        if list(boundaries) != sorted(boundaries):
            raise ValueError(
                f"epoch boundaries must be nondecreasing: {boundaries}")
        self.mesh = mesh
        # Per-epoch specs in their OWN universes; the union universe is
        # first-seen order so adding an epoch only APPENDS columns
        # (existing columns keep their indices -- the board gather for
        # a pure-growth reshape is the identity prefix).
        self._own_specs = list(specs)
        self._starts = [int(b) for b in boundaries]
        self.universe: tuple = ()
        self._rebuild_universe()
        self._init_board(window, self._rows, mesh)
        self.board = _pin_board(self.board, mesh)

    @property
    def num_nodes(self) -> int:
        """The board's rows, which a dense block must have: the union
        universe padded to whole tiles."""
        return self._rows

    def _block_kernel(self, staged):
        self.board, newly = _record_block_epochs(
            self.board, staged, self._boundaries, self._masks,
            self._thresholds, self._combine_any)
        return newly

    def _votes_kernel(self, slots, true_slots, nodes, rounds, valid):
        self.board, newly = _record_and_check_epochs(
            self.board, slots, true_slots, nodes, rounds, valid,
            self._boundaries, self._masks, self._thresholds,
            self._combine_any)
        return newly

    def _rebuild_universe(self) -> None:
        seen: dict = {}
        for spec in self._own_specs:
            for node in spec.universe:
                seen.setdefault(node, len(seen))
        self.universe = tuple(seen)
        specs = [s.reindexed(self.universe) for s in self._own_specs]
        from frankenpaxos_tpu.quorums.spec import pad_specs

        # boundaries[k-1] = first slot of epoch k (epoch 0 governs
        # everything below boundaries[0]). int32 like the board's slot
        # state: x64 is off in jitted kernels, and no ring outlives
        # 2^31 slots between GCs.
        # K and N are shapes of every jitted call, so both are padded:
        # the stack to a capacity that doubles, the acceptor axis (the
        # board's rows and the masks' columns) to whole tiles of
        # ``_ROW_TILE``. A cluster reconfigured once a second would
        # otherwise compile the scatter again, for every batch width,
        # at every reconfiguration and for every acceptor new to the
        # universe, on its event loop (seconds each on a TPU). A
        # padding plane starts at the largest int32, which no slot
        # reaches, so searchsorted never selects it; a padding row is
        # in no mask and no vote names it.
        capacity = _MIN_PLANES
        while capacity < len(specs):
            capacity *= 2
        spare = capacity - len(specs)
        self._rows = -(-len(self.universe) // _ROW_TILE) * _ROW_TILE
        masks, thresholds, combine_any = pad_specs(specs)
        (self._masks, self._thresholds, self._combine_any,
         self._boundaries) = _place_planes(
            (np.pad(masks, ((0, spare), (0, 0),
                            (0, self._rows - len(self.universe)))),
             np.pad(thresholds, ((0, spare), (0, 0))),
             np.pad(combine_any, (0, spare)),
             np.pad(np.asarray(self._starts[1:], dtype=np.int32),
                    (0, spare), constant_values=np.iinfo(np.int32).max)),
            self.mesh)
        self._boundaries_np = np.asarray(self._starts[1:],
                                         dtype=np.int64)

    def column_of(self, node_id: int) -> int:
        return self.universe.index(node_id)

    def add_epoch(self, spec: QuorumSpec, start_slot: int) -> None:
        """Append an epoch: slots >= ``start_slot`` check under
        ``spec``. Reshapes the live board onto the widened union
        universe (the epoch reshape gather)."""
        if start_slot < self._starts[-1]:
            raise ValueError(
                f"epoch start {start_slot} below the newest epoch's "
                f"{self._starts[-1]}")
        self._own_specs.append(spec)
        self._starts.append(int(start_slot))
        old_universe, old_rows = self.universe, self._rows
        self._rebuild_universe()
        if (self.universe[:len(old_universe)] != old_universe
                or self._rows != old_rows):
            self._take_votes(self.board, old_universe)

    def _take_votes(self, board: VoteBoard, universe) -> None:
        """This checker's board from ``board``, whose rows are
        ``universe``'s: the epoch reshape gather onto this universe
        and its padding rows; the slot-axis state as it stands."""
        cmap = np.full(self._rows, -1, dtype=np.int32)
        cmap[:len(self.universe)] = epoch_column_map(universe,
                                                    self.universe)
        self.board = _pin_board(VoteBoard(
            votes=_reshape_columns(board.votes, cmap),
            rounds=board.rounds, chosen=board.chosen, owner=board.owner),
            self.mesh)

    def adopt(self, checker: "TpuQuorumChecker") -> None:
        """Continue on ``checker``'s live board: its acceptor axis is
        gathered onto this union universe (the epoch reshape gather of
        :meth:`TpuQuorumChecker.reshape`), its slot-axis state is taken
        as it stands, so a slot mid-collection there completes here.
        ``checker`` must not be used afterwards."""
        if checker.window != self.window:
            raise ValueError(f"cannot adopt a {checker.window}-slot board "
                             f"into a {self.window}-slot one")
        self._take_votes(checker.board, checker.spec.universe)

    def config_indices(self, slots: np.ndarray) -> np.ndarray:
        """Which epoch plane governs each slot."""
        return np.searchsorted(self._boundaries_np,
                               np.asarray(slots, dtype=np.int64),
                               side="right")

    def check_batch(self, present: np.ndarray,
                    slots: np.ndarray) -> np.ndarray:
        """Stateless: ``[B, N]`` union-universe responder rows checked
        under each row's slot's epoch -- one fused kernel across the
        handover boundary."""
        config_idx = self.config_indices(slots)
        present = np.asarray(present, dtype=np.uint8)
        present = np.pad(present, ((0, 0),
                                   (0, self._rows - present.shape[1])))
        return np.asarray(_check_batch_multi(
            present,
            np.asarray(config_idx, dtype=np.int32),
            self._masks, self._thresholds, self._combine_any))

    def check_block(self, start_slot: int,
                    block: np.ndarray) -> np.ndarray:
        """Stateless dense form: ``block[N, B]`` covers contiguous
        slots ``[start_slot, start_slot + B)`` (which may straddle any
        number of epoch boundaries)."""
        b = block.shape[1]
        slots = start_slot + np.arange(b, dtype=np.int64)
        return self.check_batch(np.asarray(block, dtype=np.uint8).T,
                                slots)


class MultiConfigQuorumChecker:
    """Stateless batched checks where each row picks its own quorum system.

    Built from :func:`frankenpaxos_tpu.quorums.spec.pad_specs`; serves
    Matchmaker per-round configurations and mixed acceptor-group grids.
    """

    def __init__(self, specs: Sequence[QuorumSpec]):
        from frankenpaxos_tpu.quorums.spec import pad_specs

        self.universe = specs[0].universe
        self._masks, self._thresholds, self._combine_any = _place_planes(
            pad_specs(specs), None)

    def check_batch(self, present: np.ndarray,
                    config_idx: np.ndarray) -> np.ndarray:
        return np.asarray(_check_batch_multi(
            np.asarray(present), np.asarray(config_idx, dtype=np.int32),
            self._masks, self._thresholds, self._combine_any))

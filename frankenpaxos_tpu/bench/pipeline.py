"""The fully device-resident MultiPaxos steady-state pipeline.

This is the north-star benchmark configuration (BASELINE.json): the
steady-state Phase2 write path of compartmentalized MultiPaxos --
propose -> acceptor votes -> quorum check -> chosen -> replica execute ->
GC -- expressed as one jitted step over a ``[acceptors, window]`` vote
board with a 1M-slot in-flight window, iterated under ``lax.fori_loop``
with donated state. No host round-trips on the hot path.

The SAME ``steady_state_step`` function serves both single-chip execution
(axes ``None``) and multi-chip ``shard_map`` execution over a
``(group, slot)`` mesh: acceptor rows shard over ``group`` (quorum counts
ride a psum over ICI), the slot window shards over ``slot`` (committed /
sm-state counters psum over it). Global semantics are identical across
mesh shapes because vote arrivals and proposed commands are functions of
the *logical* (block-lane, acceptor) coordinates, which partition the
same way under every sharding.

Mapping to the reference's roles (SURVEY.md section 3.1):

  * Leader.processClientRequestBatch (Leader.scala:331-408): slot
    assignment is the contiguous block frontier; proposed command ids are
    written into the window.
  * Acceptor.handlePhase2a (Acceptor.scala:184-220): vote arrivals land
    as a dense ``[n, B]`` bitmask OR'd into the board. Arrival patterns
    are hash-derived per (iteration, acceptor, block-lane): ~87% of votes
    arrive in the drain after proposal, the rest one drain later --
    modeling cross-drain vote straggling.
  * ProxyLeader.handlePhase2b (ProxyLeader.scala:217-258): the quorum
    predicate matmul over the touched blocks; newly-chosen = hit & ~chosen.
  * Replica.executeLog (Replica.scala:394-453): chosen commands apply to
    a device state register; the executed watermark trails the fully
    chosen block; replies are counted.
  * BufferMap GC (BufferMap.scala:55-62): executed blocks are zeroed so
    the ring can wrap.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from frankenpaxos_tpu.ops.telemetry import (
    drain_update,
    make_telemetry,
    quorum_pass_update,
    TELEMETRY_PARTITION,
    TelemetryState,
)


class PipelineState(NamedTuple):
    votes: jax.Array      # [n, window] uint8
    chosen: jax.Array     # [window] bool
    commands: jax.Array   # [window] int32 proposed command ids
    results: jax.Array    # [window] int32 state-machine outputs
    sm_state: jax.Array   # [] int32: the replica's running register
    committed: jax.Array  # [] int32 committed commands
    exec_wm: jax.Array    # [] int32 executed watermark (global slots)
    # paxpulse device counters (ops/telemetry.py) -- None means the
    # telemetry plane is OFF and every accumulation site compiles out
    # (the pytree simply has no leaves there), keeping the traced ops
    # byte-identical to the pre-paxpulse pipeline.
    telemetry: Optional[TelemetryState] = None


def make_state(window: int, num_acceptors: int, *,
               telemetry: bool = False,
               slot_shards: int = 1) -> PipelineState:
    return PipelineState(
        votes=jnp.zeros((num_acceptors, window), jnp.uint8),
        chosen=jnp.zeros((window,), jnp.bool_),
        commands=jnp.zeros((window,), jnp.int32),
        results=jnp.zeros((window,), jnp.int32),
        sm_state=jnp.int32(0),
        committed=jnp.int32(0),
        exec_wm=jnp.int32(0),
        telemetry=(make_telemetry(num_acceptors, slot_shards)
                   if telemetry else None),
    )


def _arrivals(i: jax.Array, lanes: jax.Array, accs: jax.Array,
              salt: int) -> jax.Array:
    """Deterministic pseudo-random [len(accs), len(lanes)] uint8 arrival
    mask, keyed by logical (block-lane, global-acceptor) coordinates so
    every mesh sharding generates the same votes for the same slot."""
    h = (lanes[None, :] * 1103515245 + accs[:, None] * 12820163
         + (i + salt) * 22695477) >> 7
    return ((h & 7) < 7).astype(jnp.uint8)  # ~87.5% arrive this drain


def _psum(x, axis: Optional[str]):
    return x if axis is None else jax.lax.psum(x, axis)


def _axis_index(axis: Optional[str]) -> jax.Array:
    return jnp.int32(0) if axis is None else jax.lax.axis_index(axis)


def local_block(block_size: int, slot_shards: int) -> tuple:
    """``(b_local, pad)``: the per-shard lane count (the global block
    rounded UP over the slot shards) and the number of pad lanes the
    rounding adds to the padded global block. A non-divisible split
    (e.g. a 1M-slot block over 3 slot shards) pads the last lanes of
    every block; the padded lanes are masked out of proposals, votes,
    commits, and execution inside :func:`steady_state_step`, so the
    committed results stay bit-identical to the unpadded host oracle."""
    b_local = -(-block_size // slot_shards)
    return b_local, b_local * slot_shards - block_size


def padded_window(window: int, block_size: int, slot_shards: int) -> int:
    """The padded GLOBAL window for a sharded run: every shard holds
    whole rounded-up ``b_local`` blocks, so the global window grows by
    ``pad`` lanes per block when the block does not divide over the
    slot shards (and is unchanged when it does)."""
    if window % block_size:
        raise ValueError(
            f"window {window} must hold whole {block_size}-slot blocks")
    b_local, _ = local_block(block_size, slot_shards)
    return (window // block_size) * b_local * slot_shards


def gathered_layout(slot_shards: int, w_local: int, b_local: int,
                    block_size: int) -> tuple:
    """``(logical, valid)`` for each physical column of the gathered
    sharded window (shard windows concatenated): ``logical[c]`` is the
    unsharded slot id the column holds and ``valid[c]`` is False for
    pad columns (lane >= block_size under a rounded-up split), whose
    logical id is meaningless. Within shard ``s``, local column ``j``
    holds block ``j // b_local`` at block-lane
    ``s * b_local + (j % b_local)``; the unsharded layout is
    block-major."""
    cols = np.arange(slot_shards * w_local)
    s, j = cols // w_local, cols % w_local
    bi, lane = j // b_local, s * b_local + (j % b_local)
    return bi * block_size + lane, lane < block_size


def steady_state_step(state: PipelineState, i: jax.Array, *,
                      block_size: int, masks: np.ndarray,
                      thresholds, combine_any: bool,
                      group_axis: Optional[str] = None,
                      slot_axis: Optional[str] = None,
                      group_shards: int = 1,
                      slot_shards: int = 1) -> PipelineState:
    """One event-loop drain: new proposals + straggler completion.

    Each block gets exactly two passes (drain t: most votes; drain t+1:
    the stragglers), so the window holds ~2 blocks of in-flight
    vote-collection at the frontier plus the chosen/executing tail
    behind it.

    The quorum predicate is the general factored form
    (quorums/spec.py): ``masks`` is ``[G, N]`` over the global
    acceptors, ``thresholds`` is ``[G]``, and per-slot satisfaction
    combines over the G mask groups with any (``combine_any=True``) or
    all. SimpleMajority is G=1; a Grid write spec is one mask per row
    with threshold 1 combined with ALL ("one vote in every row",
    quorums/Grid.scala:5-57).

    ``block_size`` and ``masks`` are GLOBAL (whole-mesh) quantities; when
    called inside ``shard_map``, ``state`` holds this shard's local view
    and ``group_axis``/``slot_axis`` name the mesh axes (with their
    static sizes in ``group_shards``/``slot_shards``).
    """
    n_local, w_local = state.votes.shape
    # A block that does not divide over the slot shards rounds the
    # local block UP; the pad lanes (global lane >= block_size) are
    # masked out of every effect below, so the committed semantics are
    # those of the unpadded global block (make_sharded_state sizes the
    # padded window to match).
    b_local, block_pad = local_block(block_size, slot_shards)
    assert w_local % b_local == 0, (
        f"local window {w_local} must hold whole {b_local}-slot blocks")
    masks_d = jnp.asarray(masks, dtype=jnp.int32)          # [G, n_global]
    thresholds_d = jnp.asarray(np.asarray(thresholds, dtype=np.int32))
    assert thresholds_d.shape == (masks_d.shape[0],), (
        f"{thresholds_d.shape} thresholds for {masks_d.shape[0]} mask "
        f"groups")
    assert masks_d.shape[1] == group_shards * n_local, (
        f"masks cover {masks_d.shape[1]} acceptors but the mesh holds "
        f"{group_shards} x {n_local}")
    num_blocks = w_local // b_local
    start_new = (i % num_blocks) * b_local
    start_old = ((i - 1) % num_blocks) * b_local

    # Grid specs take the fused col-OR/row-AND reduction instead of the
    # mask matmul (ops/quorum.grid_layout): pure boolean ops, no int32
    # widening, bit-identical hits. Under group sharding the fused path
    # engages only when every shard holds WHOLE rows (row-major
    # universe, local columns a multiple of the row length); rows that
    # straddle shards fall back to the psum'd matmul.
    from frankenpaxos_tpu.ops.quorum import _fused_grid_hit, grid_layout

    grid = grid_layout(masks, thresholds, combine_any)
    if grid is not None and group_axis is not None \
            and (grid[3] is not None or n_local % grid[2] != 0):
        grid = None

    # Logical coordinates: lane within the global block, global acceptor.
    # The unsharded case avoids the (traced-index) slice/offset ops so
    # XLA sees pure iota inputs and fuses everything into the matmul.
    if slot_axis is None:
        lanes_new = jnp.arange(b_local, dtype=jnp.int32)
    else:
        lanes_new = (_axis_index(slot_axis) * b_local
                     + jnp.arange(b_local, dtype=jnp.int32))
    if group_axis is None:
        accs = jnp.arange(n_local, dtype=jnp.int32)
        masks_local = masks_d
    else:
        group_idx = _axis_index(group_axis)
        accs = group_idx * n_local + jnp.arange(n_local, dtype=jnp.int32)
        masks_local = jax.lax.dynamic_slice(
            masks_d, (0, group_idx * n_local),
            (masks_d.shape[0], n_local))

    # Pad-lane mask for non-divisible splits; the divisible (and the
    # unsharded) case stays mask-free so the hot path traces the exact
    # same ops as before. Lane coordinates are block-relative, so ONE
    # mask covers the new block, the straggler block, and execution.
    lane_valid = lanes_new < block_size if block_pad else None

    def _mask_arrivals(arr):
        if lane_valid is None:
            return arr
        return arr & lane_valid[None, :].astype(jnp.uint8)

    # --- Leader: assign slots, propose command ids --------------------------
    proposed = lanes_new * 7 + i * 13 + 1
    if lane_valid is not None:
        proposed = jnp.where(lane_valid, proposed, 0)
    commands = jax.lax.dynamic_update_slice(state.commands, proposed,
                                            (start_new,))

    def quorum_pass(votes, chosen, committed, tel, start, arrivals):
        block = jax.lax.dynamic_slice(votes, (0, start),
                                      (n_local, b_local)) | arrivals
        votes = jax.lax.dynamic_update_slice(votes, block, (0, start))
        if grid is not None and group_axis is None:
            hit = _fused_grid_hit(block, grid)
        elif grid is not None:
            # Sharded: this shard holds whole rows (see the gate
            # above; perm is None there). Per-row unrolled elementwise
            # chains like _fused_grid_hit's, combined ACROSS shards by
            # psum-ing missing/full row counts.
            kind, _, g_cols, _ = grid
            local_rows = []
            for r in range(block.shape[0] // g_cols):
                row = block[r * g_cols]
                for c in range(1, g_cols):
                    cell = block[r * g_cols + c]
                    row = (row | cell) if kind == "write" else (row & cell)
                local_rows.append(row)
            if kind == "write":
                # ALL rows present <=> zero missing rows mesh-wide.
                missing = sum((jnp.uint8(1) - row for row in local_rows),
                              jnp.zeros((b_local,), jnp.uint8))
                hit = _psum(missing.astype(jnp.int32), group_axis) == 0
            else:
                full = sum(local_rows,
                           jnp.zeros((b_local,), jnp.uint8))
                hit = _psum(full.astype(jnp.int32), group_axis) > 0
        else:
            counts = _psum(masks_local @ block.astype(jnp.int32),
                           group_axis)                   # [G, b_local]
            satisfied = counts >= thresholds_d[:, None]
            hit = satisfied.any(0) if combine_any else satisfied.all(0)
        if lane_valid is not None:
            # Pad lanes never accrue votes, but a degenerate spec could
            # still "hit" them; keep them permanently unchosen.
            hit = hit & lane_valid
        old = jax.lax.dynamic_slice(chosen, (start,), (b_local,))
        newly = hit & ~old
        chosen = jax.lax.dynamic_update_slice(chosen, hit | old, (start,))
        # Post-group-psum ``newly`` is replicated over group; summing the
        # slot shards yields the global count, replicated everywhere.
        committed = committed + _psum(newly.sum(dtype=jnp.int32), slot_axis)
        if tel is not None:
            # paxpulse: at choose time, how many GLOBAL votes had landed
            # on each lane? (Only traced on the telemetry-on arm.)
            votes_count = _psum(block.astype(jnp.int32).sum(0),
                                group_axis)
            tel = quorum_pass_update(tel, votes_count=votes_count,
                                     newly=newly, slot_axis=slot_axis)
        return votes, chosen, committed, tel

    # --- Acceptors + ProxyLeader: pass 1 on the new block -------------------
    arr1 = _mask_arrivals(_arrivals(i, lanes_new, accs, salt=0))
    votes, chosen, committed, tel = quorum_pass(
        state.votes, state.chosen, state.committed, state.telemetry,
        start_new, arr1)
    # --- pass 2: stragglers complete the previous block ---------------------
    arr2 = _mask_arrivals(1 - _arrivals(i - 1, lanes_new, accs, salt=0))
    votes, chosen, committed, tel = quorum_pass(
        votes, chosen, committed, tel, start_old, arr2)

    # --- Replica: execute the now fully-chosen previous block ---------------
    cmds_old = jax.lax.dynamic_slice(commands, (start_old,), (b_local,))
    block_results = cmds_old * 3 + 7
    if lane_valid is not None:
        block_results = jnp.where(lane_valid, block_results, 0)
    results = jax.lax.dynamic_update_slice(state.results, block_results,
                                           (start_old,))
    sm_state = state.sm_state + _psum(cmds_old.sum(dtype=jnp.int32),
                                      slot_axis)
    exec_wm = jnp.where(i >= 1, i.astype(jnp.int32) * block_size, 0)

    # --- GC: release the block executed long ago so the ring can wrap -------
    # (Early iterations "GC" still-zero wrap-around blocks: harmless.)
    start_gc = ((i - 2) % num_blocks) * b_local
    votes = jax.lax.dynamic_update_slice(
        votes, jnp.zeros((n_local, b_local), jnp.uint8), (0, start_gc))
    chosen = jax.lax.dynamic_update_slice(
        chosen, jnp.zeros((b_local,), jnp.bool_), (start_gc,))

    # paxpulse once-per-drain counters: proposal fill, pad-lane waste,
    # and the end-of-drain watermark lag (slots proposed but unchosen --
    # with ring reuse, cumulative proposals are (i+1) * block_size).
    # The lag expression stays under the guard so the telemetry-off
    # trace is the pre-paxpulse program to the op.
    if tel is not None:
        tel = drain_update(tel, proposed_block=proposed,
                           lane_valid=lane_valid,
                           lag=(i.astype(jnp.int32) + 1) * block_size
                           - committed,
                           slot_axis=slot_axis)

    return PipelineState(votes, chosen, commands, results, sm_state,
                         committed, exec_wm, tel)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5),
                   donate_argnums=(0,))
def run_steps(state: PipelineState, iters: int, block_size: int,
              masks_t: tuple, thresholds_t: tuple,
              combine_any: bool) -> PipelineState:
    """``iters`` drains in one dispatch (the bench hot loop)."""
    masks = np.asarray(masks_t, dtype=np.int32)
    thresholds = np.asarray(thresholds_t, dtype=np.int32)

    def body(i, s):
        return steady_state_step(s, i, block_size=block_size, masks=masks,
                                 thresholds=thresholds,
                                 combine_any=combine_any)

    return jax.lax.fori_loop(0, iters, body, state)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6),
                   donate_argnums=(0,))
def run_steps_from(state: PipelineState, start: jax.Array, iters: int,
                   block_size: int, masks_t: tuple, thresholds_t: tuple,
                   combine_any: bool) -> PipelineState:
    """:func:`run_steps` with a TRACED start iteration: chunked A/B
    arms resume the drain counter where the previous chunk left off
    (ring positions and arrival hashes continue instead of replaying
    drain 0), and every chunk reuses one compiled executable."""
    masks = np.asarray(masks_t, dtype=np.int32)
    thresholds = np.asarray(thresholds_t, dtype=np.int32)

    def body(i, s):
        return steady_state_step(s, i, block_size=block_size, masks=masks,
                                 thresholds=thresholds,
                                 combine_any=combine_any)

    return jax.lax.fori_loop(start, start + iters, body, state)


def drain_latency_distribution(spec_arrays, num_acceptors: int,
                               window: int, block_size: int,
                               mean_drain_us: float,
                               time_budget_s: float = 20.0,
                               target_samples: int = 1024) -> dict:
    """A TRUE per-drain latency distribution: host-timed dispatches of
    ``chunk`` drains each, p50/p99 over >= dozens-to-1k samples.

    The fused ``fori_loop`` throughput run can only report a mean (no
    per-drain observation exists inside the loop); this replaces that
    proxy for the latency figure. The chunk size ADAPTS to the cost of
    one dispatch+fetch: every host-timed sample pays one, so the chunk
    must be wide enough that compute dominates its jitter (floor 128
    drains). The measured null dispatch+fetch p50 is subtracted from
    each sample; jitter beyond that is attributed to the drain, making
    the reported p99 an honest UPPER bound. All methodology inputs are
    returned alongside the percentiles."""
    import time

    masks_t, thresholds_t, combine_any = spec_arrays

    # Null dispatch+fetch RTT: same sync pattern as a timed sample.
    noop = jax.jit(lambda x: x + 1)
    x = jnp.int32(0)
    for _ in range(3):
        x = noop(x)
        _ = int(x)
    null = []
    for _ in range(30):
        t0 = time.perf_counter()
        x = noop(x)
        _ = int(x)
        null.append(time.perf_counter() - t0)
    null_p50_us = float(np.percentile(null, 50) * 1e6)
    null_p90_us = float(np.percentile(null, 90) * 1e6)

    # Chunk so compute >= 8x the null p90 (its jitter), floor 128.
    chunk = 128
    while chunk * mean_drain_us < 8 * null_p90_us and chunk < (1 << 16):
        chunk *= 2
    est_sample_s = (chunk * mean_drain_us + null_p50_us) / 1e6
    samples = max(24, min(target_samples,
                          int(time_budget_s / max(est_sample_s, 1e-9))))

    state = make_state(window, num_acceptors)
    state = run_steps(state, chunk, block_size, masks_t, thresholds_t,
                      combine_any)
    _ = int(state.committed)  # warm the exact chunked shape
    times = []
    for _ in range(samples):
        t0 = time.perf_counter()
        state = run_steps(state, chunk, block_size, masks_t,
                          thresholds_t, combine_any)
        _ = int(state.committed)  # value fetch: cannot complete early
        times.append(time.perf_counter() - t0)
    per_drain_us = (np.asarray(times) * 1e6 - null_p50_us) / chunk
    per_drain_us = np.maximum(per_drain_us, 0.0)
    return {
        "p50_drain_latency_us": round(float(
            np.percentile(per_drain_us, 50)), 2),
        "p99_drain_latency_us": round(float(
            np.percentile(per_drain_us, 99)), 2),
        "latency_samples": samples,
        "drains_per_sample": chunk,
        "null_rtt_p50_us": round(null_p50_us, 1),
        "null_rtt_p90_us": round(null_p90_us, 1),
        "latency_method": (
            "host-timed dispatches of drains_per_sample fused drains "
            "each; per-drain = (sample - null_rtt_p50) / "
            "drains_per_sample; chunk auto-scaled so compute >= 8x "
            "null-RTT p90, so dispatch+fetch jitter beyond the median "
            "is attributed to the drain (p99 is an upper bound)"),
    }


# --------------------------------------------------------------------------
# Multi-chip: the same step under shard_map over a (group, slot) mesh.
# --------------------------------------------------------------------------

PIPELINE_PARTITION = PipelineState(
    votes=("group", "slot"),
    chosen=("slot",),
    commands=("slot",),
    results=("slot",),
    sm_state=(),
    committed=(),
    exec_wm=(),
    # The telemetry leaf defaults to None (plane off). When the plane is
    # on, its per-leaf axes come from ops/telemetry.TELEMETRY_PARTITION
    # via :func:`partition_specs`.
)


def partition_specs(telemetry: bool = False):
    """The ``PartitionSpec`` tree for a ``PipelineState`` over the
    ``(group, slot)`` mesh: ``PIPELINE_PARTITION`` leaf-for-leaf, with
    the paxpulse subtree (per ``TELEMETRY_PARTITION``) attached when the
    telemetry plane is on and an empty (``None``) node when off."""
    from jax.sharding import PartitionSpec as P

    tel = (TelemetryState(*(P(*axes) for axes in TELEMETRY_PARTITION))
           if telemetry else None)
    base = {field: P(*axes)
            for field, axes in zip(PipelineState._fields,
                                   PIPELINE_PARTITION)
            if isinstance(axes, tuple)}
    return PipelineState(telemetry=tel, **base)


def make_sharded_step(mesh, *, block_size: int, masks: np.ndarray,
                      thresholds, combine_any: bool,
                      telemetry: bool = False):
    """Jit ``steady_state_step`` under shard_map over ``mesh``.

    ``mesh`` must have axes ``("group", "slot")``. Returns
    ``(step, state_sharding)``: ``step(state, i)`` runs one drain with
    quorum counts psum'd over the group axis and counters psum'd over the
    slot axis; ``state_sharding`` is the matching ``NamedSharding`` tree
    for ``jax.device_put``.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    group_shards = mesh.shape["group"]
    slot_shards = mesh.shape["slot"]
    step = functools.partial(
        steady_state_step, block_size=block_size, masks=masks,
        thresholds=thresholds, combine_any=combine_any,
        group_axis="group", slot_axis="slot",
        group_shards=group_shards, slot_shards=slot_shards)

    spec_tree = partition_specs(telemetry)
    sharded = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(spec_tree, P()), out_specs=spec_tree,
        check_vma=False), donate_argnums=(0,))
    sharding = jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)
    return sharded, sharding


def state_sharding(mesh, telemetry: bool = False):
    """The ``NamedSharding`` tree matching ``PIPELINE_PARTITION`` over
    ``mesh`` (what :func:`make_sharded_step` returns as its second
    element), for callers that place state without building a step."""
    from jax.sharding import NamedSharding

    spec_tree = partition_specs(telemetry)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


def make_sharded_state(mesh, window: int, block_size: int,
                       num_acceptors: int, *,
                       telemetry: bool = False) -> tuple:
    """``(state, sharding, w_padded)``: a fresh ``PipelineState`` laid
    out over ``mesh`` for a GLOBAL ``window`` of whole ``block_size``
    blocks. When the block does not divide over the slot shards the
    window is PADDED (see :func:`padded_window`); the pad lanes are
    masked inside :func:`steady_state_step`, so committed counts and
    per-slot results match the unpadded host oracle bit-for-bit
    (compare through :func:`gathered_layout`)."""
    slot_shards = mesh.shape["slot"]
    w_padded = padded_window(window, block_size, slot_shards)
    sharding = state_sharding(mesh, telemetry)
    state = jax.device_put(
        make_state(w_padded, num_acceptors, telemetry=telemetry,
                   slot_shards=slot_shards), sharding)
    return state, sharding, w_padded


def make_sharded_runner(mesh, *, block_size: int, masks: np.ndarray,
                        thresholds, combine_any: bool, iters: int,
                        telemetry: bool = False):
    """The mesh twin of :func:`run_steps_from`: jit one shard_map'd
    ``fori_loop`` of ``iters`` drains (ONE dispatch per call, the bench
    hot loop -- per-drain dispatch through :func:`make_sharded_step`
    costs a host round-trip per drain and measures the dispatch, not
    the mesh). Returns ``(runner, sharding)`` with
    ``runner(state, start) -> state``."""
    from jax.sharding import PartitionSpec as P

    group_shards = mesh.shape["group"]
    slot_shards = mesh.shape["slot"]

    def run(state, start):
        def body(i, s):
            return steady_state_step(
                s, i, block_size=block_size, masks=masks,
                thresholds=thresholds, combine_any=combine_any,
                group_axis="group", slot_axis="slot",
                group_shards=group_shards, slot_shards=slot_shards)

        return jax.lax.fori_loop(start, start + iters, body, state)

    spec_tree = partition_specs(telemetry)
    runner = jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(spec_tree, P()), out_specs=spec_tree,
        check_vma=False), donate_argnums=(0,))
    return runner, state_sharding(mesh, telemetry)

"""Vectorized delivery-wave masks for the simulator core (paxsim).

A delivery WAVE is the batch of buffered frames the simulator consumes
in one step: everything currently buffered (``SimTransport`` FIFO
waves) or everything sharing the next virtual arrival time
(``GeoSimTransport``). The per-message drop decisions -- is either
endpoint partitioned? is the zone link up? -- become one mask
evaluation over the wave's SoA columns (src/dst address ids, src/dst
zone ids) instead of per-message set/dict probes.

Kernels are numpy: waves are host-side, sized tens to tens of
thousands, and feed straight into Python handler dispatch. A jit-able
variant of the combined mask is provided for schedule-scale waves
(``link_keep_mask_jit``); it pads the wave to the next power of two so
XLA compiles one program per size BUCKET, not per wave length (the
TPU2xx retrace hazard). Parity with the numpy kernels is asserted in
tests/test_sim_core.py.

The transports only call these above ``WAVE_VECTOR_MIN`` messages;
below it, per-message Python checks beat the fixed cost of array
staging (measured in bench/sim_core_ab.py).
"""

from __future__ import annotations

import os

import numpy as np

#: Wave size below which the transports keep per-message Python checks
#: (array staging costs ~5us per wave; a 4-message wave of dict probes
#: costs ~1us).
WAVE_VECTOR_MIN = 32

#: Zone id for unplaced addresses (admin/chaos senders): their links
#: are free and always up, modeled as a sentinel row/column of True in
#: the up-matrix.
UNPLACED_ZONE = -1


def keep_mask(src_ids: np.ndarray, dst_ids: np.ndarray,
              blocked_ids: np.ndarray) -> np.ndarray:
    """Partition mask: keep[i] is False when either endpoint of frame
    ``i`` is in ``blocked_ids`` (the transport's ``partitioned`` set,
    interned to address ids)."""
    if blocked_ids.size == 0:
        return np.ones(src_ids.shape, dtype=bool)
    dropped = np.isin(src_ids, blocked_ids) \
        | np.isin(dst_ids, blocked_ids)
    return ~dropped


def link_keep_mask(src_zones: np.ndarray, dst_zones: np.ndarray,
                   up: np.ndarray) -> np.ndarray:
    """Geo link mask: keep[i] = up[src_zone, dst_zone], with
    ``UNPLACED_ZONE`` (-1) endpoints always up. ``up`` is the
    topology's ``[Z+1, Z+1]`` bool matrix whose LAST row/column (the
    -1 index, by numpy wraparound) is the all-True sentinel for
    unplaced addresses -- see ``GeoTopology.up_matrix``."""
    return up[src_zones, dst_zones]


#: The link-mask kernel the geo transport dispatches through:
#: ``FPX_SIMWAVE_JIT=1`` swaps in the jit-able twin below (parity-
#: tested in tests/test_sim_core.py); the numpy kernel is the default
#: -- host-side waves are small enough that XLA dispatch overhead
#: loses to numpy except on schedule-scale runs.
LINK_KEEP_MASK = link_keep_mask


def _pad_pow2(a: np.ndarray, fill) -> np.ndarray:
    n = a.shape[0]
    cap = 1 if n == 0 else 1 << (n - 1).bit_length()
    if cap == n:
        return a
    return np.concatenate([a, np.full(cap - n, fill, dtype=a.dtype)])


def link_keep_mask_jit(src_zones: np.ndarray, dst_zones: np.ndarray,
                       up: np.ndarray) -> np.ndarray:
    """jit-able twin of :func:`link_keep_mask` for schedule-scale
    waves: pads the wave to the next power of two (one XLA program per
    size bucket) and gathers through the same sentinel-row up-matrix."""
    import jax

    n = src_zones.shape[0]
    src_p = _pad_pow2(src_zones.astype(np.int32), UNPLACED_ZONE)
    dst_p = _pad_pow2(dst_zones.astype(np.int32), UNPLACED_ZONE)
    mask = _link_keep_jax(jax.numpy.asarray(src_p),
                          jax.numpy.asarray(dst_p),
                          jax.numpy.asarray(up))
    # np.array (not asarray): device output buffers are read-only and
    # callers AND the partition mask in place.
    return np.array(mask[:n])


if os.environ.get("FPX_SIMWAVE_JIT") == "1":
    LINK_KEEP_MASK = link_keep_mask_jit


_LINK_KEEP_JAX_CACHE = {}


def _link_keep_jax(src_zones, dst_zones, up):
    import jax

    fn = _LINK_KEEP_JAX_CACHE.get("fn")
    if fn is None:
        def gather(src_z, dst_z, up_m):
            return up_m[src_z, dst_z]

        # paxlint: disable=TPU206 -- built ONCE and memoized in
        # _LINK_KEEP_JAX_CACHE (no per-call retrace); a module-scope
        # jit would force the jax import onto every simulator run,
        # jitted or not.
        fn = jax.jit(gather)
        _LINK_KEEP_JAX_CACHE["fn"] = fn
    return fn(src_zones, dst_zones, up)

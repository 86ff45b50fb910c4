"""One process per chip: platform selection and the compile cache.

libtpu gives a chip to ONE process at a time; a second process that
initialises the TPU backend fails or hangs. So every process decides,
before its JAX backend initialises, which side it is on:

  * :func:`claim_tpu` -- the process that runs device kernels (the role
    process hosting a device tracker, ``bench.py``, the ``chip_smoke.py``
    stages). It points JAX at the persistent compile cache, requires the
    TPU platform, and returns what JAX found. There is no CPU fallback:
    without a TPU it raises.
  * :func:`pin_cpu` -- everything else (host-backend roles, launchers,
    load generators, probe clients).

The one exception to "no fallback" is an explicit ``JAX_PLATFORMS=cpu``
in the environment, which the tests and CI set: ``claim_tpu`` then runs
the same kernels on CPU XLA and says so in what it returns.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Where compiled executables persist when ``JAX_COMPILATION_CACHE_DIR``
#: does not say otherwise. The directory is part of the cache key, so it
#: is a fixed path inside the checkout, never a temporary one.
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def explicit_cpu() -> bool:
    """Did the environment explicitly pin JAX to the CPU?"""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def pin_cpu() -> None:
    """Keep this process off the chip. Only this process's JAX config is
    touched, not ``os.environ``: a launcher pins itself and still hands
    its chip-owning child the environment it was started with."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def configure_compile_cache() -> None:
    """Persist every compiled executable, the small ones included: a
    device tracker prewarms ~10 sub-second kernels at each role start.
    Must run before the process compiles anything. When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    directory is set here."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def describe_devices() -> dict:
    """What JAX runs on, as every result must name it. Initialises the
    backend."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def claim_tpu() -> dict:
    """Make this process the chip's owner; returns
    :func:`describe_devices`. Raises ``RuntimeError`` when JAX cannot
    give it a TPU, unless the environment explicitly pins the CPU."""
    import jax

    if explicit_cpu():
        return describe_devices()
    configure_compile_cache()
    # Without this JAX falls back to the CPU with a warning when the
    # TPU fails to initialise (another process holds it, say).
    jax.config.update("jax_platforms", "tpu")
    device = describe_devices()
    if device["platform"] != "tpu":
        raise RuntimeError(f"asked for a TPU, JAX found {device}")
    return device

"""What every process that computes on the device does once, at start:
place the persistent compilation cache and say which device it got; a
service also keeps the host memory it frees (``keep_freed_host_memory``).

None runs by import side effect — the service runners
(services/service_factory.py) and ``bench.py`` call the first two before
anything compiles, the service runners the third.
"""

from __future__ import annotations

import ctypes
import logging
import os
import sys
from pathlib import Path

__all__ = [
    "device_identity",
    "enable_persistent_compilation_cache",
    "keep_freed_host_memory",
    "log_device_identity",
]

logger = logging.getLogger(__name__)

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def _default_cache_dir() -> Path:
    """One fixed path: the directory is part of the cache's key, so a
    temp name, a pid or a per-run directory would never hit. Inside the
    checkout when there is one, else the per-user cache (an installed
    wheel's package directory is not writable by the service user)."""
    root = Path(__file__).resolve().parents[3]
    if (root / "pyproject.toml").is_file():
        return root / ".jax_cache"
    return Path.home() / ".cache" / "esslivedata-tpu" / "jax_cache"


def enable_persistent_compilation_cache() -> str:
    """Turn on JAX's on-disk compilation cache so a restarted process
    skips XLA for every program it compiled before (the AOT warm-up's
    ``Lowered.compile`` writes the same cache). Returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it
    is set, jax already reads it and no directory is set in code. Every
    entry is cached regardless of size or compile time: the tick
    programs are small and fast on CPU but seconds-scale on the chip.
    """
    import jax

    directory = os.environ.get(_CACHE_ENV)
    if not directory:
        directory = str(_default_cache_dir())
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    logger.info("persistent compilation cache at %s", directory)
    return directory


#: glibc's ``mallopt`` parameters (malloc.h) and the values the service
#: sets (ADR 0133).
_M_TRIM_THRESHOLD, _M_TOP_PAD, _M_MMAP_THRESHOLD = -1, -2, -3
_MALLOPT = (
    # Below 32 MiB, the most glibc takes on a 64-bit host, an array
    # comes from a heap and not from a mapping of its own.
    (_M_MMAP_THRESHOLD, 32 << 20),
    # A heap's free top is not given back below 1 GiB ...
    (_M_TRIM_THRESHOLD, 1 << 30),
    # ... and a thread's extra heap of 64 MiB is not unmapped when it
    # empties: a pad of its whole size keeps it.
    (_M_TOP_PAD, 64 << 20),
)
#: What an operator sets to tune glibc's malloc from outside.
_MALLOC_ENV = (
    "MALLOC_MMAP_THRESHOLD_",
    "MALLOC_TRIM_THRESHOLD_",
    "MALLOC_TOP_PAD_",
)


def _glibc():
    """The process's C library where it is glibc, else None."""
    if not sys.platform.startswith("linux"):
        return None
    try:
        libc = ctypes.CDLL(None)
    except OSError:
        return None
    return libc if hasattr(libc, "gnu_get_libc_version") else None


def keep_freed_host_memory() -> bool:
    """Keep the host memory the process frees for its next allocation,
    so a large array made every window (a fetched image, an encoded
    message, a flat wire) lands in pages that are already faulted in
    (ADR 0133). glibc by default maps an array of a few MiB on its own,
    or gives a heap's free top back once 32 MiB are free there, so the
    same array is faulted in again page by page whenever the heap's
    layout lets that happen: a service's windows then cost tens of ms
    more from one run to the next. The price is that the process's
    resident memory stays at its high-water mark.

    Returns whether the settings were made: not on a C library other
    than glibc, and not where the environment tunes glibc's malloc
    (``MALLOC_*_`` or ``glibc.malloc.*`` in ``GLIBC_TUNABLES``), whose
    choice stands."""
    tunables = os.environ.get("GLIBC_TUNABLES", "")
    if "glibc.malloc." in tunables or any(
        os.environ.get(name) for name in _MALLOC_ENV
    ):
        logger.info("malloc tuned from the environment; left as set")
        return False
    libc = _glibc()
    if libc is None:
        return False
    for param, value in _MALLOPT:
        if libc.mallopt(param, value) != 1:
            logger.warning("mallopt(%d, %d) refused", param, value)
            return False
    return True


def device_identity() -> dict[str, str | int]:
    """The device as jax reports it: ``platform``, ``device_kind`` and
    ``count``. Initializes the backend (and so claims the chip)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


def log_device_identity() -> dict[str, str | int]:
    """Log the one start-up line naming the device. CPU is a choice
    (``--cpu`` / ``LIVEDATA_FORCE_CPU`` / ``JAX_PLATFORMS=cpu``), never
    a silent landing: when jax fell back to it unasked the line is a
    WARNING."""
    import jax

    identity = device_identity()
    asked = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS") or ""
    unasked_cpu = identity["platform"] == "cpu" and "cpu" not in asked.split(",")
    logger.log(
        logging.WARNING if unasked_cpu else logging.INFO,
        "device: platform=%s device_kind=%s count=%d default_backend=%s%s",
        identity["platform"],
        identity["device_kind"],
        identity["count"],
        # What the kernels' ``== "tpu"`` gates and interpret-mode
        # defaults (ops/, parallel/) actually test.
        jax.default_backend(),
        " — jax found no accelerator and fell back to the CPU without "
        "being asked (pass --cpu or JAX_PLATFORMS=cpu to choose it)"
        if unasked_cpu
        else "",
    )
    return identity

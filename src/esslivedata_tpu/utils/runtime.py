"""What every process that computes on the device does once, at start:
place the persistent compilation cache and say which device it got.

Neither runs by import side effect — the service runners
(services/service_factory.py) and ``bench.py`` call both before anything
compiles.
"""

from __future__ import annotations

import logging
import os
from pathlib import Path

__all__ = [
    "device_identity",
    "enable_persistent_compilation_cache",
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

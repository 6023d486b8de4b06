"""Pin the CPU and N virtual devices for tests.

Used by tests/conftest.py, __graft_entry__.py, the services' ``--cpu``
flag and bench.py's ``--cpu``/``--smoke``: sets ``JAX_PLATFORMS=cpu``
(and the virtual-device count in ``XLA_FLAGS``) and updates jax.config,
before anything touches a backend.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"


def pin_cpu(n_devices: int | None = None) -> None:
    """Force the CPU backend, with at least ``n_devices`` virtual devices.

    Safe to call repeatedly; must first be called before JAX initializes a
    backend (later calls are no-ops in effect). An existing device-count
    flag in XLA_FLAGS is raised to ``n_devices`` if it is lower — never
    lowered.
    """
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(rf"{_COUNT_FLAG}=(\d+)", flags)
        if m is None:
            flags = f"{flags} {_COUNT_FLAG}={n_devices}".strip()
        elif int(m.group(1)) < n_devices:
            flags = flags.replace(m.group(0), f"{_COUNT_FLAG}={n_devices}")
        os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")

"""Mesh construction helpers for the mesh serving tier (ADR 0115)."""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

__all__ = ["make_mesh", "mesh_from_spec"]


def mesh_from_spec(spec: str, *, devices=None) -> Mesh:
    """Parse the service surface's ``--mesh data,bank`` form (also the
    ``LIVEDATA_MESH`` env value) into a 2-D ('data', 'bank') mesh.

    ``"2,4"`` = data=2 x bank=4; a single integer (``"8"``) puts every
    device on the bank axis (the memory-relieving default, matching
    ``make_mesh``); ``"auto"`` uses all visible devices the same way.
    """
    spec = spec.strip().lower()
    if devices is None:
        devices = jax.devices()
    if spec in ("auto", ""):
        return make_mesh(len(devices), devices=devices)
    parts = [p.strip() for p in spec.split(",")]
    try:
        dims = [int(p) for p in parts]
    except ValueError as err:
        raise ValueError(
            f"--mesh expects 'data,bank' integers or 'auto'; got {spec!r}"
        ) from err
    if any(d < 1 for d in dims):
        # A zero axis would build an EMPTY mesh: make_mesh's
        # data*bank == n_devices check passes at 0 == 0, and the
        # placement then degrades to unplaced serving one contained
        # ZeroDivisionError at a time — an operator typo must fail the
        # build instead.
        raise ValueError(
            f"--mesh axes must be >= 1; got {spec!r}"
        )
    if len(dims) == 1:
        return make_mesh(dims[0], devices=devices)
    if len(dims) != 2:
        raise ValueError(
            f"--mesh expects at most two axes (data,bank); got {spec!r}"
        )
    data, bank = dims
    return make_mesh(data * bank, data=data, bank=bank, devices=devices)


def make_mesh(
    n_devices: int | None = None,
    *,
    data: int | None = None,
    bank: int | None = None,
    devices=None,
) -> Mesh:
    """Build a 2-D ('data', 'bank') mesh over the first ``n_devices`` devices.

    ``data`` shards the event stream (DP analog); ``bank`` shards bin space
    (TP/SP analog). If only one of data/bank is given the other is inferred;
    if neither, devices all go to ``bank`` (bin-space sharding is the
    memory-relieving axis, which is the usual reason to shard).
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is None:
        n_devices = len(devices)
    devices = devices[:n_devices]
    if len(devices) < n_devices:
        raise ValueError(
            f"Requested {n_devices} devices, only {len(devices)} available"
        )
    if data is None and bank is None:
        data, bank = 1, n_devices
    elif data is None:
        if n_devices % bank:
            raise ValueError(f"{n_devices} devices not divisible by bank={bank}")
        data = n_devices // bank
    elif bank is None:
        if n_devices % data:
            raise ValueError(f"{n_devices} devices not divisible by data={data}")
        bank = n_devices // data
    if data * bank != n_devices:
        raise ValueError(f"data*bank = {data * bank} != n_devices = {n_devices}")
    arr = np.asarray(devices).reshape(data, bank)
    return Mesh(arr, ("data", "bank"))

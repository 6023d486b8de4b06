"""On-hardware pallas kernel check: lowering + parity + device-resident A/B.

Run on the chip (`python scripts/tpu_kernel_check.py`). Everything
heavier than a scalar stays on device — parity is checked against an
on-device XLA scatter, so the 600 MB headline window is never fetched.

Sections:
  1-D: bincount_pallas vs XLA scatter at monitor scale (1000 bins).
  2-D: scatter_add_pallas2d (bf16 + int8) vs XLA scatter at LOKI
       headline scale (1.5M px x 100 toa), incl. host partition rate.
"""

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402


def main() -> None:
    import jax
    import jax.numpy as jnp

    from esslivedata_tpu.ops.pallas_hist import bincount_pallas
    from esslivedata_tpu.ops.pallas_hist2d import (
        padded_bins,
        partition_events_host,
        scatter_add_pallas2d,
    )

    print("device:", jax.devices()[0], flush=True)
    rng = np.random.default_rng(0)
    n = 1 << 22

    # ---- 1-D ------------------------------------------------------------
    nbins = 1000
    flat = rng.integers(-5, nbins + 5, n).astype(np.int32)
    dev = jax.device_put(flat)
    out = bincount_pallas(dev, nbins, interpret=False)
    out.block_until_ready()
    ref = np.bincount(flat[(flat >= 0) & (flat < nbins)], minlength=nbins)
    np.testing.assert_array_equal(np.asarray(out), ref.astype(np.float32))
    print("1-D parity OK", flush=True)

    t0 = time.perf_counter()
    for _ in range(20):
        out = bincount_pallas(dev, nbins, interpret=False)
    out.block_until_ready()
    print(
        f"1-D pallas: {20 * n / (time.perf_counter() - t0):.3e} ev/s "
        "device-resident",
        flush=True,
    )

    @jax.jit
    def scat1(s, f):
        return s.at[jnp.clip(f, 0, nbins - 1)].add(1.0, mode="drop")

    s = scat1(jnp.zeros(nbins, jnp.float32), dev)
    s.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        s = scat1(s, dev)
    s.block_until_ready()
    print(
        f"1-D scatter: {20 * n / (time.perf_counter() - t0):.3e} ev/s "
        "device-resident",
        flush=True,
    )

    # ---- 2-D (headline scale) -------------------------------------------
    nbins2 = 1_500_000 * 100 + 1  # incl. dump
    flat2 = rng.integers(0, nbins2, n).astype(np.int32)
    pb = padded_bins(nbins2)
    t0 = time.perf_counter()
    events, cmap = partition_events_host(flat2, nbins2)
    print(
        f"2-D partition: {n / (time.perf_counter() - t0):.3e} ev/s host "
        f"({cmap.shape[0]} chunks)",
        flush=True,
    )

    out2 = scatter_add_pallas2d(
        jnp.zeros(pb, jnp.float32), events, cmap, interpret=False
    )
    devF = jax.device_put(flat2)

    @jax.jit
    def scat2(s, f):
        return s.at[f].add(1.0, mode="drop")

    ref2 = scat2(jnp.zeros(pb, jnp.float32), devF)
    diff = float(jnp.abs(out2 - ref2).max())
    assert diff == 0.0, f"2-D parity broke: max diff {diff}"
    print("2-D parity OK (device-side compare)", flush=True)

    devE, devM = jax.device_put(events), jax.device_put(cmap)
    for prec in ("bf16", "int8"):
        w = scatter_add_pallas2d(
            jnp.zeros(pb, jnp.float32), devE, devM,
            interpret=False, precision=prec,
        )
        w.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(20):
            w = scatter_add_pallas2d(
                w, devE, devM, interpret=False, precision=prec
            )
        w.block_until_ready()
        print(
            f"2-D pallas2d ({prec}): "
            f"{20 * n / (time.perf_counter() - t0):.3e} ev/s "
            "device-resident",
            flush=True,
        )

    s2 = scat2(jnp.zeros(pb, jnp.float32), devF)
    s2.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        s2 = scat2(s2, devF)
    s2.block_until_ready()
    print(
        f"2-D scatter: {20 * n / (time.perf_counter() - t0):.3e} ev/s "
        "device-resident",
        flush=True,
    )


if __name__ == "__main__":
    main()

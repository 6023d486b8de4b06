"""On-hardware pallas kernel check: lowering + parity + device-resident A/B.

Run on the chip (`python scripts/tpu_kernel_check.py`). Everything
heavier than a scalar stays on device — parity is checked against an
on-device XLA scatter, so the 600 MB headline window is never fetched.

Sections:
  1-D: bincount_pallas vs XLA scatter at monitor scale (1000 bins).
  2-D: scatter_add_pallas2d (bf16 + int8) vs XLA scatter at LOKI
       headline scale (1.5M px x 100 toa), incl. host partition rate.
  lookup (``--lookup`` runs this section alone): the Q step's
       ``table[pixel, TOA bin]`` at LOKI's shapes (int16 over 100 bins,
       one byte plane: 802 816 x 200 and 172 032 x 200) and at DREAM's
       powder shapes (int32 over 34 000 bins, two planes: the mantle's
       491 520 x 500 and the SANS bank's 30 720 x 500), a 4 Mi bucket
       holding 14 pulses of 229 376, the cell's id distribution and
       every event in one pixel: the windowed lookup of
       ops/pallas_lookup.py exact against the gather, ms a step for
       every rung of the ladder and the crossover sweep (PERF.md
       section 6).
  bincount (``--bincount`` runs this section alone): the Q step's
       count of looked-up bins, the three ways ``QHistogrammer`` has
       (XLA's scatter-add, the flat one-hot ``bincount_pallas``, the
       factorised one-hot on the MXU ``bincount_mxu``) at 1, 2, 4, 8,
       38, 79 and 266 lane groups of 128 bins (LOKI's 100 bins,
       BIFROST's 4 800 and 10 000, DREAM powder's 34 000) for a 4 Mi
       and a 16 Mi batch with the cells' 23 % of padding routed to the
       drop slot: each exact against ``np.bincount``, ns an event, and
       what ``method="auto"`` takes (``MXU_LANE_GROUPS``).
  view (``--view`` runs this section alone; about 320 s through the
       builder's tool): the detector view's count of 4 Mi slots, 23.4 %
       of them dropped, into 6 553 601, 163 840 001, 15 769 601 and
       25 601 bins (LOKI's bank view, NMX's panel, DREAM's largest and
       smallest): XLA's scatter weighted and unit against the
       device-partitioned count of ops/pallas_hist2d.py (key sort on the
       chip, (chunk, block) work items, the factorised one-hot on the
       MXU in place) at blocks of 16 Ki and 64 Ki bins and chunks of
       512, 2 048 and 8 192 keys, updates 1 and 1/4, each exact against
       ``np.bincount``, ms and ns a slot; the table is in that module's
       docstring. ``view_section(n=16384, bin_spaces=(100_001, 3_001,
       25_601), bpbs=(2048, 16384), chunks=(512, 2048))`` is the
       rehearsal on the CPU (interpret mode: no timing).
"""

import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402


def _ms(fn, *args, repeats: int = 10) -> float:
    """Milliseconds a call of the jitted ``fn``, device-resident."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


#: ``lookup_section``'s shapes for DREAM's powder reduction: the
#: largest and the smallest bank, behind XLA's scatter as in the cell.
DREAM_POWDER = {
    "banks": (491_520, 30_720),
    "n_toa": 500,
    "n_q": 2000 * 17,
    "dtype": np.int32,
    "method": "scatter",
}


def lookup_section(
    n: int = 1 << 22,
    n_valid: int = 14 * 229_376,
    banks: tuple[int, ...] = (802_816, 172_032),
    n_toa: int = 200,
    n_q: int = 100,
    dtype=np.int16,
    method: str = "pallas",
) -> None:
    """Parity and the ladder at LOKI's shapes (the defaults;
    ``DREAM_POWDER`` holds DREAM's; a rehearsal on the CPU passes small
    ones); raises on a mismatch."""
    import jax
    import jax.numpy as jnp

    from esslivedata_tpu.ops import pallas_lookup
    from esslivedata_tpu.ops.qhistogram import table_scatter_delta

    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(28)
    lo, hi = 0.0, 1e9 / 14

    def staged(ids):
        pid = np.full(n, -1, np.int32)
        pid[:n_valid] = ids
        toa = np.zeros(n, np.float32)
        toa[:n_valid] = (rng.integers(0, n_toa, n_valid) + 0.5) * (
            (hi - lo) / n_toa
        )
        return jax.device_put(pid), jax.device_put(toa)

    for n_pix in banks:
        table = rng.integers(-1, n_q, (n_pix, n_toa), dtype=dtype)
        planes = pallas_lookup.packable(table, n_q)
        assert planes
        dev = jax.device_put(table)
        del table
        packed = pallas_lookup.pack_table(dev, planes=planes)
        tag = f"{n_pix}x{n_toa} {planes}-plane"
        centre = 0.7 * n_pix
        blob = np.rint(rng.normal(centre, n_pix / 8, n_valid)).astype(
            np.int64
        ) % n_pix
        cases = {
            "blob": staged(blob.astype(np.int32) + 1),
            "one_pixel": staged(np.full(n_valid, n_pix // 3, np.int32)),
        }

        def delta(tbl, pid, toa, *, packed_shape=None):
            return table_scatter_delta(
                tbl, pid, toa, id_base=1, lo=lo, hi=hi,
                inv_width=n_toa / (hi - lo), n_bins=n_q,
                dtype=jnp.float32, method=method,
                packed_shape=packed_shape,
            )

        step_gather = jax.jit(delta)
        step_windowed = jax.jit(
            functools.partial(delta, packed_shape=(n_pix, n_toa))
        )
        for name, (pid, toa) in cases.items():
            want = np.asarray(step_gather(dev, pid, toa))
            got = np.asarray(step_windowed(packed, pid, toa))
            np.testing.assert_array_equal(got, want)
            assert want.sum() > 0
            print(
                f"lookup {tag} {name}: step parity OK; "
                f"gather {_ms(step_gather, dev, pid, toa):.2f} ms a step, "
                f"windowed {_ms(step_windowed, packed, pid, toa):.2f}",
                flush=True,
            )

        # the ladder: the lookup alone, clipped indices as the step's
        pid, toa = cases["blob"]
        local = jnp.clip(pid - 1, 0, n_pix - 1)
        tb = jnp.clip(
            jnp.floor(toa * (n_toa / (hi - lo))).astype(jnp.int32),
            0, n_toa - 1,
        )
        ok = pid >= 1
        flat = local * n_toa + tb
        flat_sorted = jnp.sort(flat)
        dev1 = dev.reshape(-1)
        shift = pallas_lookup._toa_bits(packed.shape[1])
        n_windows = packed.shape[2] // pallas_lookup.WINDOW
        keys = jnp.where(ok, (local << shift) | tb, np.iinfo(np.int32).max)
        keys_sorted = jnp.sort(keys)
        rungs = {
            f"gather [pid, tb] {dev.dtype}": (
                lambda t, p, b: t[p, b], dev, local, tb
            ),
            f"gather flat 1-D {dev.dtype}": (lambda t, f: t[f], dev1, flat),
            "gather packed, an element a plane, bf16": (
                lambda t, p, b: [t[k, b, p] for k in range(planes)],
                packed, local, tb,
            ),
            "gather flat sorted, indices_are_sorted": (
                lambda t, f: t.at[f].get(
                    indices_are_sorted=True, mode="promise_in_bounds"
                ),
                dev1, flat_sorted,
            ),
            "sort s32 keys (stable)": (jnp.sort, keys),
            "sort s32 keys (unstable)": (
                lambda k: jax.lax.sort(k, is_stable=False), keys
            ),
            "windowed lookup (sort + items + kernel)": (
                pallas_lookup.lookup, packed, local, tb, ok,
            ),
            "windowed kernel + items, keys sorted": (
                lambda t, k: pallas_lookup._lookup_sorted(
                    t, k, shift, interpret
                ),
                packed, keys_sorted,
            ),
            "work items alone": (
                lambda k: pallas_lookup.work_items(
                    k,
                    group=pallas_lookup.BLOCK,
                    span=pallas_lookup.WINDOW << shift,
                    n_targets=n_windows,
                ),
                keys_sorted,
            ),
        }
        if dev.dtype != jnp.int32:
            rungs["gather [pid, tb] int32"] = (
                lambda t, p, b: t[p, b], dev.astype(jnp.int32), local, tb
            )
        for name, (fn, *args) in rungs.items():
            print(
                f"lookup {tag} rung {name}: "
                f"{_ms(jax.jit(fn), *args):.2f} ms",
                flush=True,
            )
        items = pallas_lookup.work_items(
            keys_sorted,
            group=pallas_lookup.BLOCK,
            span=pallas_lookup.WINDOW << shift,
            n_targets=n_windows,
        )
        print(
            f"lookup {tag}: {int(items[2][0])} work items of "
            f"{items[0].shape[0]} grid steps",
            flush=True,
        )

        # the crossover: both paths of ``lookup`` on the packed table,
        # by batch size (its two constants are read at trace time)
        rule = pallas_lookup.MIN_EVENTS, pallas_lookup.EVENTS_PER_WINDOW
        for log2 in range(14, 23):
            m = 1 << log2
            if m > n:
                break
            args = (packed, local[:m], tb[:m], ok[:m])
            ms = {}
            for kind, forced in (("gather", (n + 1, 0)), ("windowed", (0, 0))):
                pallas_lookup.MIN_EVENTS, pallas_lookup.EVENTS_PER_WINDOW = forced
                # a function of its own: jit's cache goes by function
                ms[kind] = _ms(
                    jax.jit(lambda *a: pallas_lookup.lookup(*a)), *args
                )
            pallas_lookup.MIN_EVENTS, pallas_lookup.EVENTS_PER_WINDOW = rule
            print(
                f"lookup {tag} crossover n={m}: gather "
                f"{ms['gather']:.3f} ms, windowed {ms['windowed']:.3f}, "
                f"the rule takes {pallas_lookup.lookup_kind(m, packed.shape)}",
                flush=True,
            )


#: ``bincount_section``'s bin spaces: 1, 2, 4, 8, 38, 79, 266 lane groups.
BINCOUNT_BINS = (100, 256, 512, 1024, 4_800, 10_000, 34_000)


def bincount_section(
    sizes: tuple[int, ...] = (1 << 22, 1 << 24),
    bin_spaces: tuple[int, ...] = BINCOUNT_BINS,
    pad_share: float = 0.23,
) -> None:
    """Parity with numpy and ns an event of the three bincounts (a
    rehearsal on the CPU passes small sizes); raises on a mismatch."""
    import jax
    import jax.numpy as jnp

    from esslivedata_tpu.ops import pallas_hist

    rng = np.random.default_rng(34)
    for n in sizes:
        for n_bins in bin_spaces:
            flat = rng.integers(0, n_bins, n).astype(np.int32)
            # dropped events and the bucket's padding, as the step
            # routes them: to the slot one past the last bin
            flat[rng.random(n) < pad_share] = n_bins
            want = np.bincount(flat[flat < n_bins], minlength=n_bins)
            dev = jax.device_put(flat)
            ways = {
                "scatter": lambda f, k=n_bins: jnp.zeros((k,), jnp.float32)
                .at[f]
                .add(1.0, mode="drop"),
                "mxu": lambda f, k=n_bins: pallas_hist.bincount_mxu(f, k),
            }
            if n_bins + 1 <= pallas_hist.MAX_PALLAS_BINS:
                ways["pallas"] = lambda f, k=n_bins: pallas_hist.bincount_pallas(f, k)
            line = []
            for name, fn in sorted(ways.items()):
                fn = jax.jit(fn)
                np.testing.assert_array_equal(np.asarray(fn(dev)), want)
                took = _ms(fn, dev)
                line.append(f"{name} {took:.3f} ms = {took * 1e6 / n:.3f} ns an event")
            print(
                f"bincount n={n} bins={n_bins} ({-(-n_bins // 128)} lane "
                "groups), exact: " + "; ".join(line)
                + f"; auto takes {pallas_hist.tpu_bincount(n_bins)}",
                flush=True,
            )


#: ``view_section``'s bin spaces (dump slot included): LOKI's bank view
#: (256 x 256 x 100), NMX's panel (1280 x 1280 x 100), DREAM's largest
#: and smallest views.
VIEW_BINS = (6_553_601, 163_840_001, 15_769_601, 25_601)


def _ms_donated(fn, state, *args, repeats: int = 10) -> tuple[float, object]:
    """Milliseconds a call of the jitted ``fn(state, *args) -> state``
    that donates its state, and the state after ``repeats + 1`` calls."""
    import jax

    state = jax.block_until_ready(fn(state, *args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        state = fn(state, *args)
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / repeats * 1e3, state


def view_section(
    n: int = 1 << 22,
    bin_spaces: tuple[int, ...] = VIEW_BINS,
    pad_share: float = 0.234,
    bpbs: tuple[int, ...] = (16_384, 65_536),
    chunks: tuple[int, ...] = (512, 2_048, 8_192),
) -> None:
    """The detector view's count of ``n`` slots (``pad_share`` of them
    routed to the dump slot, as a bucket's padding is): XLA's scatter,
    weighted (a float array of 1/4 a slot, LOKI's replicas before PR
    38) and unit, against the device-partitioned count of
    ops/pallas_hist2d.py at every (block, chunk) of the sweep, with the
    update 1 and 1/4; each exact against ``np.bincount`` (a touched bin
    reads its count, and the total leaves none for any other), ns a
    slot. A rehearsal on the CPU passes small sizes; raises on a
    mismatch."""
    import jax
    import jax.numpy as jnp

    from esslivedata_tpu.ops import pallas_hist2d, pallas_lookup

    rng = np.random.default_rng(38)
    kept = pallas_hist2d.COUNT_BPB, pallas_hist2d.COUNT_CHUNK
    for n_incl_dump in bin_spaces:
        n_bins = n_incl_dump - 1
        flat = rng.integers(0, n_bins, n).astype(np.int32)
        flat[rng.random(n) < pad_share] = n_bins
        bins, counts = np.unique(flat[flat < n_bins], return_counts=True)
        dev = jax.device_put(flat)
        dev_bins = jax.device_put(bins.astype(np.int32))

        def exact(window, upd, rounds, dev_bins=dev_bins, counts=counts):
            got = np.asarray(window[dev_bins])
            np.testing.assert_array_equal(got, counts * upd * rounds)
            # in quarters, as integers: a float32 sum rounds past 2**24
            total = int(jnp.sum((window * 4).astype(jnp.int32)))
            assert total == int(counts.sum() * 4 * upd * rounds), total

        tag = f"view bins={n_incl_dump} n={n}"
        scatters = {
            "scatter weighted": (
                lambda w, f: w.at[f].add(
                    jnp.full(f.shape, 0.25, jnp.float32), mode="drop"
                ),
                0.25,
            ),
            "scatter unit": (lambda w, f: w.at[f].add(1.0, mode="drop"), 1.0),
        }
        for name, (fn, upd) in scatters.items():
            window = jnp.zeros((n_incl_dump,), jnp.float32)
            took, window = _ms_donated(
                jax.jit(fn, donate_argnums=(0,)), window, dev
            )
            window = window.at[n_bins].set(0.0)  # the scatter counts the dump
            exact(window, upd, 11)
            print(
                f"{tag}: {name} {took:.3f} ms = {took * 1e6 / n:.3f} ns a slot, "
                "exact",
                flush=True,
            )
            del window
        for bpb in bpbs:
            for chunk in chunks:
                pallas_hist2d.COUNT_BPB = bpb
                pallas_hist2d.COUNT_CHUNK = chunk
                blk, n_state = pallas_hist2d.count_layout(n_bins)
                line = []
                for upd in (1.0, 0.25):

                    def count(w, f, upd=upd, blk=blk, n_bins=n_bins):
                        part = pallas_hist2d.partition_on_device(
                            f, n_bins, bpb=blk
                        )
                        return pallas_hist2d.count_partitioned(
                            w, *part, bpb=blk, upd=upd
                        )

                    window = jnp.zeros((n_state,), jnp.float32)
                    took, window = _ms_donated(
                        jax.jit(count, donate_argnums=(0,)), window, dev
                    )
                    exact(window, upd, 11)
                    line.append(
                        f"upd {upd}: {took:.3f} ms = {took * 1e6 / n:.3f} ns"
                    )
                    del window
                part = jax.jit(
                    lambda f, blk=blk, n_bins=n_bins: (
                        pallas_hist2d.partition_on_device(f, n_bins, bpb=blk)
                    )
                )(dev)
                items = int(part[3][0])
                print(
                    f"{tag}: mxu bpb={bpb} chunk={chunk} ({n_state // blk} "
                    f"blocks of {blk}; {items} items of "
                    f"{part[2].shape[0]} steps), exact: " + "; ".join(line),
                    flush=True,
                )
        # the count's parts at the constants kept: key sort, work items
        pallas_hist2d.COUNT_BPB, pallas_hist2d.COUNT_CHUNK = kept
        blk, n_state = pallas_hist2d.count_layout(n_bins)
        keys = jnp.where(dev < n_bins, dev, np.iinfo(np.int32).max)
        keys_sorted = jax.lax.sort(keys, is_stable=False)
        parts = {
            "key sort": (lambda k: jax.lax.sort(k, is_stable=False), keys),
            "work items": (
                lambda k, blk=blk, n_state=n_state: pallas_lookup.work_items(
                    k, group=kept[1], span=blk, n_targets=n_state // blk
                ),
                keys_sorted,
            ),
        }
        for name, (fn, *args) in parts.items():
            print(
                f"{tag}: {name} at bpb={kept[0]} chunk={kept[1]}: "
                f"{_ms(jax.jit(fn), *args):.3f} ms",
                flush=True,
            )
    pallas_hist2d.COUNT_BPB, pallas_hist2d.COUNT_CHUNK = kept


def main() -> None:
    import jax
    import jax.numpy as jnp

    print("device:", jax.devices()[0], flush=True)
    if "--view" in sys.argv[1:]:
        view_section()
        return
    if "--bincount" in sys.argv[1:]:
        bincount_section()
        return
    if "--lookup" in sys.argv[1:]:
        lookup_section()
        lookup_section(**DREAM_POWDER)
        return

    from esslivedata_tpu.ops.pallas_hist import bincount_pallas
    from esslivedata_tpu.ops.pallas_hist2d import (
        padded_bins,
        partition_events_host,
        scatter_add_pallas2d,
    )

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

    lookup_section()
    lookup_section(**DREAM_POWDER)
    bincount_section()
    view_section()


if __name__ == "__main__":
    main()

"""Windowed table lookup (ops/pallas_lookup.py): parity with ``table[pid, tb]``.

Runs the Pallas kernel in interpret mode on the CPU; the compiled path
is what ``scripts/tpu_kernel_check.py --lookup`` checks on the chip. The
crossover is lowered for the kernel's cases, so that interpret mode
stays quick; the last cases run under the real one. Tables of up to 255
bins pack in one byte plane (LOKI's), wider ones of up to 65 535 in two
(DREAM's powder: int32 over 34 000 bins, 500 TOA columns).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from esslivedata_tpu.ops import EventBatch, pallas_lookup
from esslivedata_tpu.ops.qhistogram import PixelBinMap, QHistogrammer
from esslivedata_tpu.telemetry.instruments import Q_LOOKUP_STEPS

N_Q = 100
SENTINEL = np.iinfo(np.int32).max


@pytest.fixture
def low_crossover(monkeypatch):
    monkeypatch.setattr(pallas_lookup, "MIN_EVENTS", 1024)


def random_table(n_pix, n_toa, seed=0, n_bins=N_Q, dtype=np.int16):
    rng = np.random.default_rng(seed)
    return rng.integers(-1, n_bins, (n_pix, n_toa)).astype(dtype)


def sorted_reference(table, pid, tb, ok):
    """``table[pid, tb]`` (-1 where dropped) in the order the windowed
    lookup returns it: by packed key, dropped events last."""
    shift = pallas_lookup._toa_bits(-(-table.shape[1] // 16) * 16)
    keys = np.where(ok, (pid.astype(np.int64) << shift) | tb, SENTINEL)
    order = np.argsort(keys, kind="stable")
    return np.where(ok, table[pid, tb], -1)[order]


def pack(table, n_bins=N_Q):
    """The table as ``QHistogrammer`` installs it on a TPU."""
    planes = pallas_lookup.packable(table, n_bins)
    assert planes
    return pallas_lookup.pack_table(jnp.asarray(table), planes=planes)


def windowed(table, pid, tb, ok, n_bins=N_Q):
    packed = pack(table, n_bins)
    assert pallas_lookup.lookup_kind(len(pid), packed.shape) == "windowed"
    got = jax.jit(pallas_lookup.lookup)(
        packed,
        jnp.asarray(pid, jnp.int32),
        jnp.asarray(tb, jnp.int32),
        jnp.asarray(ok),
    )
    return np.asarray(got)


def _random(rng, n_pix, n_toa, n):
    return (
        rng.integers(0, n_pix, n),
        rng.integers(0, n_toa, n),
        rng.random(n) < 0.8,
    )


def _one_pixel(rng, n_pix, n_toa, n):
    return np.full(n, n_pix // 3), rng.integers(0, n_toa, n), np.ones(n, bool)


def _all_padding(rng, n_pix, n_toa, n):
    # what a clipped padding slot looks like: entry (0, 0), dropped
    return np.zeros(n, int), np.zeros(n, int), np.zeros(n, bool)


def _none_valid(rng, n_pix, n_toa, n):
    pid, tb, _ = _random(rng, n_pix, n_toa, n)
    return pid, tb, np.zeros(n, bool)


def _window_edges(rng, n_pix, n_toa, n):
    w = pallas_lookup.WINDOW
    edges = np.array([0, w - 1, w, 2 * w - 1, 2 * w, n_pix - 1])
    return rng.choice(edges, n), rng.integers(0, n_toa, n), np.ones(n, bool)


def _tail_block_spans_windows(rng, n_pix, n_toa, n):
    # one pixel for all but the last block's worth of events, which
    # step through every window above it
    pid = np.full(n, 5)
    tail = pallas_lookup.BLOCK // 2
    pid[-tail:] = np.linspace(pallas_lookup.WINDOW, n_pix - 1, tail).astype(int)
    return pid, rng.integers(0, n_toa, n), np.ones(n, bool)


def _toa_extremes(rng, n_pix, n_toa, n):
    pid, _, ok = _random(rng, n_pix, n_toa, n)
    return pid, rng.choice([0, n_toa - 1], n), ok


WIDE_BINS = 2000 * 17  # DREAM's I(d, 2-theta)
#: the values on either side of a byte boundary, and the ends of what
#: two planes hold
EDGE_VALUES = (-1, 0, 255, 256, 33_999, 65_534)


def wide_table(n_pix, n_toa, n_bins, dtype, seed=0):
    """A random table over ``n_bins`` with every edge value that fits
    under ``n_bins`` (and the dtype) planted in its first and last row."""
    table = random_table(n_pix, n_toa, seed, n_bins, dtype)
    fits = [v for v in EDGE_VALUES if v < n_bins]
    for row in (0, n_pix - 1):
        table[row, : len(EDGE_VALUES)] = 0
        table[row, : len(fits)] = fits
    return table


CASES = {
    "random": _random,
    "one_pixel": _one_pixel,
    "all_padding": _all_padding,
    "none_valid": _none_valid,
    "window_first_and_last_rows": _window_edges,
    "tail_block_spans_many_windows": _tail_block_spans_windows,
    "toa_first_and_last_bin": _toa_extremes,
}


@pytest.mark.usefixtures("low_crossover")
class TestWindowedLookup:
    @pytest.mark.parametrize("case", CASES)
    def test_entry_for_entry(self, case):
        # n_pix no multiple of WINDOW, n_toa = 200, n no multiple of BLOCK
        n_pix, n_toa, n = 1000, 200, 5000
        table = random_table(n_pix, n_toa)
        pid, tb, ok = CASES[case](np.random.default_rng(7), n_pix, n_toa, n)
        np.testing.assert_array_equal(
            windowed(table, pid, tb, ok), sorted_reference(table, pid, tb, ok)
        )

    @pytest.mark.parametrize(
        ("n_pix", "n_toa", "n"),
        [(128, 16, 1024), (300, 20, 2048), (129, 3, 4096), (2048, 256, 3000)],
    )
    def test_shapes(self, n_pix, n_toa, n):
        table = random_table(n_pix, n_toa, seed=n_pix)
        pid, tb, ok = _random(np.random.default_rng(n), n_pix, n_toa, n)
        np.testing.assert_array_equal(
            windowed(table, pid, tb, ok), sorted_reference(table, pid, tb, ok)
        )

    @pytest.mark.parametrize("value", [-1, 0, N_Q - 1, 254])
    def test_constant_table_value(self, value):
        # -1 (the builders' drop) and the largest values come back exact
        table = np.full((300, 200), value, np.int16)
        pid, tb, ok = _random(np.random.default_rng(3), 300, 200, 2048)
        got = windowed(table, pid, tb, ok)
        assert set(got[: ok.sum()]) == {value}
        assert set(got[ok.sum() :]) <= {-1}

    @pytest.mark.parametrize(
        ("dtype", "n_bins"),
        [(np.int32, WIDE_BINS), (np.int16, 1000), (np.int32, 65_535)],
    )
    @pytest.mark.parametrize("case", ["random", "window_first_and_last_rows"])
    def test_entry_for_entry_in_two_planes(self, dtype, n_bins, case):
        # DREAM's powder table: int32 over 2000 x 17 bins, a 500-column
        # TOA axis (padded to 512 in the packed layout alone)
        n_pix, n_toa, n = 1000, 500, 5000
        table = wide_table(n_pix, n_toa, n_bins, dtype)
        assert pallas_lookup.packable(table, n_bins) == 2
        rng = np.random.default_rng(11)
        pid, tb, ok = CASES[case](rng, n_pix, n_toa, n)
        # a third of the events on the planted values (row 0 and the last)
        planted = rng.random(n) < 0.3
        pid[planted] = rng.choice([0, n_pix - 1], planted.sum())
        tb[planted] = rng.integers(0, len(EDGE_VALUES), planted.sum())
        got = windowed(table, pid, tb, ok, n_bins)
        np.testing.assert_array_equal(got, sorted_reference(table, pid, tb, ok))
        assert {v for v in EDGE_VALUES if v < n_bins} <= set(got)

    @pytest.mark.parametrize("value", [-1, 0, 255, 256, 33_999, 65_534])
    def test_constant_table_value_in_two_planes(self, value):
        table = np.full((300, 500), value, np.int32)
        pid, tb, ok = _random(np.random.default_rng(3), 300, 500, 2048)
        got = windowed(table, pid, tb, ok, n_bins=65_535)
        assert set(got[: ok.sum()]) == {value}
        assert set(got[ok.sum() :]) <= {-1}

    def test_pack_table_layout(self):
        table = random_table(300, 200)
        packed = np.asarray(pack(table))
        assert packed.dtype == jnp.bfloat16
        assert packed.shape == (1, 208, 384)
        np.testing.assert_array_equal(packed[0, :200, :300], table.T)
        assert not packed[:, 200:].any() and not packed[:, :, 300:].any()

    @pytest.mark.parametrize("dtype", [np.int16, np.int32])
    def test_pack_table_layout_in_two_planes(self, dtype):
        n_bins = 1000 if dtype == np.int16 else WIDE_BINS
        table = wide_table(300, 500, n_bins, dtype)
        packed = np.asarray(pack(table, n_bins))
        assert packed.dtype == jnp.bfloat16
        assert packed.shape == (2, 512, 384)
        high, low = packed[:, :500, :300].astype(np.int32)
        # the high plane keeps the sign (-1 = -1 * 256 + 255), the low
        # one is a byte: each exact in bfloat16
        np.testing.assert_array_equal(high, table.T >> 8)
        np.testing.assert_array_equal(low, table.T & 255)
        assert high.min() == -1 and low.max() == 255
        np.testing.assert_array_equal(high * 256 + low, table.T)
        assert not packed[:, 500:].any() and not packed[:, :, 300:].any()

    def test_work_items_cover_each_block_once_per_window(self):
        shift = 8
        pid = np.sort(np.random.default_rng(1).integers(0, 1000, 3000))
        keys = np.full(4096, SENTINEL, np.int32)
        keys[:3000] = pid << shift
        block, window, n_items = map(
            np.asarray,
            pallas_lookup.work_items(
                jnp.asarray(keys),
                group=pallas_lookup.BLOCK,
                span=pallas_lookup.WINDOW << shift,
                n_targets=8,
            ),
        )
        n_items = int(n_items[0])
        assert block.shape == window.shape == (4 + 8,)
        items = set(zip(block[:n_items], window[:n_items]))
        assert len(items) == n_items  # no item twice
        # every event's (block, window) is an item, and blocks are
        # visited in order (the output's revisiting rule)
        events = set(
            zip(np.arange(3000) // pallas_lookup.BLOCK, pid // pallas_lookup.WINDOW)
        )
        assert events <= items
        assert (np.diff(block) >= 0).all()
        # skipped grid steps repeat the last item: no block moves
        assert (block[n_items:] == block[n_items - 1]).all()
        assert (window[n_items:] == window[n_items - 1]).all()


def packed_shape(n_pix, n_toa, planes):
    """The shape ``pack_table`` gives a table of ``n_pix`` x ``n_toa``."""
    return (planes, -(-n_toa // 16) * 16, -(-n_pix // 128) * 128)


def unmaterialised(dtype, n_pix, n_toa):
    """A table of zeros of any size that takes a few bytes."""
    return np.lib.stride_tricks.as_strided(
        np.zeros(1, dtype), (n_pix, n_toa), (0, 0)
    )


class TestCrossover:
    @pytest.mark.parametrize(
        ("n_events", "n_pix", "kind"),
        [
            (1 << 22, 802_816, "windowed"),  # the cell's step
            (1 << 19, 802_816, "windowed"),
            (1 << 18, 802_816, "gather"),  # a tie on the chip
            (1 << 16, 172_032, "windowed"),
            (1 << 15, 172_032, "gather"),
            (1 << 15, 1000, "gather"),  # under the floor, however small the table
            (1 << 16, 1000, "windowed"),
        ],
    )
    def test_follows_batch_and_table_size(self, n_events, n_pix, kind):
        # LOKI's tables: one plane, 200 TOA bins
        shape = packed_shape(n_pix, 200, 1)
        assert pallas_lookup.lookup_kind(n_events, shape) == kind

    @pytest.mark.parametrize(
        "n_pix", [491_520, 157_696, 71_680, 61_440, 30_720]
    )
    def test_dreams_powder_steps_are_windowed(self, n_pix):
        # two planes of 512 TOA rows: the cell's 4 Mi step lies far
        # above the crossover of every bank, 2**15 events under the floor
        shape = packed_shape(n_pix, 500, 2)
        assert pallas_lookup.lookup_kind(1 << 22, shape) == "windowed"
        assert pallas_lookup.lookup_kind(1 << 15, shape) == "gather"

    @pytest.mark.parametrize(
        ("n_events", "kind"),
        [
            (1 << 18, "windowed"),  # 7.47 ms gathered, 6.47 windowed on the chip
            (1 << 17, "gather"),  # 3.78 against 6.24
        ],
    )
    def test_the_mantle_crosses_where_the_sweep_did(self, n_events, kind):
        shape = packed_shape(491_520, 500, 2)
        assert pallas_lookup.lookup_kind(n_events, shape) == kind

    def test_a_window_of_more_rows_crosses_higher(self):
        # 3 840 windows either way: 48 events a window of 208 rows (one
        # plane, LOKI's TOA axis), 64 a window of 1 024 (two planes of
        # DREAM's)
        narrow = packed_shape(491_520, 200, 1)
        wide = packed_shape(491_520, 500, 2)
        assert pallas_lookup.lookup_kind(3840 * 48, narrow) == "windowed"
        assert pallas_lookup.lookup_kind(3840 * 48, wide) == "gather"
        assert pallas_lookup.lookup_kind(3840 * 64 - 1, wide) == "gather"
        assert pallas_lookup.lookup_kind(3840 * 64, wide) == "windowed"


class TestPackable:
    @pytest.mark.parametrize("n_pix", [802_816, 172_032])
    def test_loki_tables_take_one_plane(self, n_pix):
        table = unmaterialised(np.int16, n_pix, 200)
        assert pallas_lookup.packable(table, N_Q) == 1

    @pytest.mark.parametrize(
        "n_pix", [491_520, 157_696, 71_680, 61_440, 30_720]
    )
    def test_dreams_powder_tables_take_two(self, n_pix):
        # int32 over 34 000 bins; the mantle's key is 28 bits
        table = unmaterialised(np.int32, n_pix, 500)
        assert pallas_lookup.packable(table, WIDE_BINS) == 2

    @pytest.mark.parametrize(
        ("dtype", "n_bins", "n_pix", "planes"),
        [
            (np.int32, N_Q, 1000, 1),  # a wide dtype, a narrow bin space
            (np.int16, 255, 1000, 1),  # the most one plane holds
            (np.int16, 256, 1000, 2),  # n_bins + 1 past bfloat16's integers
            (np.int16, 32_767, 1000, 2),  # all an int16 table can address
            (np.int32, 65_535, 1000, 2),  # the most two planes hold
            (np.int32, 65_536, 1000, 0),  # n_bins + 1 past two bytes
            (np.int32, 1 << 20, 1000, 0),
            (np.int16, N_Q, 1 << 23, 0),  # a packed key would pass int32
            (np.int32, WIDE_BINS, 1 << 22, 0),  # 22 + 9 bits: the same
            (np.int64, N_Q, 1000, 0),  # no builder's dtype
        ],
    )
    def test_planes_follow_dtype_range_and_key(self, dtype, n_bins, n_pix, planes):
        n_toa = 500 if n_bins == WIDE_BINS else 200
        table = unmaterialised(dtype, n_pix, n_toa)
        assert pallas_lookup.packable(table, n_bins) == planes


def _events(seed, n, n_pix, id_base):
    rng = np.random.default_rng(seed)
    return EventBatch.from_arrays(
        # ids below the bank, inside it and above it
        rng.integers(id_base - 3, id_base + n_pix + 3, n).astype(np.int64),
        rng.uniform(-1e6, 7.3e7, n).astype(np.float32),
    )


def _packed_histogrammer(monkeypatch, qmap, edges, method, n_q=N_Q):
    """A QHistogrammer that took the packed layout, as on a TPU: the
    backend is asked at construction alone, so the step itself traces
    for the CPU (interpret mode)."""
    with monkeypatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        hist = QHistogrammer(qmap=qmap, toa_edges=edges, n_q=n_q, method=method)
    assert hist._planes
    return hist


class TestQHistogrammerUnderTheDenseLookup:
    n_pix, n_toa, id_base = 700, 200, 40

    def _qmap(self, seed=0):
        return PixelBinMap(
            table=random_table(self.n_pix, self.n_toa, seed), id_base=self.id_base
        )

    @pytest.mark.usefixtures("low_crossover")
    @pytest.mark.parametrize("method", ["scatter", "pallas"])
    def test_state_bit_identical_to_the_gather_path(self, monkeypatch, method):
        edges = np.linspace(0.0, 7.1e7, self.n_toa + 1)
        plain = QHistogrammer(
            qmap=self._qmap(), toa_edges=edges, n_q=N_Q, method=method
        )
        dense = _packed_histogrammer(monkeypatch, self._qmap(), edges, method)
        assert not plain._planes and dense._planes == 1
        before = Q_LOOKUP_STEPS.value(lookup="windowed")
        s_plain, s_dense = plain.init_state(), dense.init_state()
        for seed in range(3):
            batch = _events(seed, 5000, self.n_pix, self.id_base)
            s_plain = plain.step(s_plain, batch, monitor_count=2.0)
            s_dense = dense.step(s_dense, batch, monitor_count=2.0)
        assert float(s_plain.cumulative.sum()) > 0
        for got, want in zip(s_dense, s_plain, strict=True):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert Q_LOOKUP_STEPS.value(lookup="windowed") == before + 3

    @pytest.mark.usefixtures("low_crossover")
    def test_swap_table_compiles_nothing(self, monkeypatch):
        edges = np.linspace(0.0, 7.1e7, self.n_toa + 1)
        dense = _packed_histogrammer(monkeypatch, self._qmap(), edges, "scatter")
        batch = _events(9, 5000, self.n_pix, self.id_base)
        first = dense.step(dense.init_state(), batch)
        compiled = dense._step._cache_size(), pallas_lookup.pack_table._cache_size()
        swapped = self._qmap(seed=1)
        dense.swap_table(swapped)
        second = dense.step(dense.init_state(), batch)
        assert (
            dense._step._cache_size(),
            pallas_lookup.pack_table._cache_size(),
        ) == compiled
        plain = QHistogrammer(qmap=swapped, toa_edges=edges, n_q=N_Q)
        want = plain.step(plain.init_state(), batch)
        np.testing.assert_array_equal(
            np.asarray(second.cumulative), np.asarray(want.cumulative)
        )
        assert not np.array_equal(
            np.asarray(first.cumulative), np.asarray(second.cumulative)
        )

    def test_batch_under_the_crossover_falls_to_the_gather(self, monkeypatch):
        # the real crossover: the packed table is read by XLA's gather
        edges = np.linspace(0.0, 7.1e7, self.n_toa + 1)
        n = 4096
        plain = QHistogrammer(qmap=self._qmap(), toa_edges=edges, n_q=N_Q)
        dense = _packed_histogrammer(monkeypatch, self._qmap(), edges, "scatter")
        assert pallas_lookup.lookup_kind(n, dense._qmap.shape) == "gather"
        before = Q_LOOKUP_STEPS.value(lookup="gather")
        batch = _events(4, n, self.n_pix, self.id_base)
        got = dense.step(dense.init_state(), batch)
        want = plain.step(plain.init_state(), batch)
        np.testing.assert_array_equal(
            np.asarray(got.cumulative), np.asarray(want.cumulative)
        )
        assert Q_LOOKUP_STEPS.value(lookup="gather") == before + 2
        text = str(
            jax.make_jaxpr(dense._step_impl)(
                dense.init_state(), dense._qmap, *map(jnp.asarray, (batch.pixel_id, batch.toa)), 0.0
            )
        )
        assert "pallas_call" not in text

    def test_gather_from_the_packed_table_in_event_order(self):
        table = random_table(300, 200)
        pid, tb, ok = _random(np.random.default_rng(2), 300, 200, 512)
        got = pallas_lookup.lookup(
            pack(table), jnp.asarray(pid), jnp.asarray(tb), jnp.asarray(ok)
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.where(ok, table[pid, tb], -1)
        )

    def test_gather_from_two_planes_in_event_order(self):
        table = wide_table(300, 500, WIDE_BINS, np.int32)
        pid, tb, ok = _random(np.random.default_rng(2), 300, 500, 512)
        pid[:64], tb[:64] = 0, np.arange(64) % len(EDGE_VALUES)
        got = pallas_lookup.lookup(
            pack(table, WIDE_BINS), jnp.asarray(pid), jnp.asarray(tb), jnp.asarray(ok)
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.where(ok, table[pid, tb], -1)
        )

    def test_the_cpu_keeps_the_int_table(self):
        edges = np.linspace(0.0, 7.1e7, self.n_toa + 1)
        hist = QHistogrammer(qmap=self._qmap(), toa_edges=edges, n_q=N_Q, method="auto")
        assert not hist._planes
        assert hist._qmap.dtype == jnp.int16
        assert hist._qmap.shape == (self.n_pix, self.n_toa)


class TestQHistogrammerInTwoPlanes:
    """DREAM's powder side: an int32 table over 34 000 bins, 500 TOA
    bins, XLA's scatter behind the lookup."""

    n_pix, n_toa, id_base = 700, 500, 40
    edges = np.linspace(0.0, 7.1e7, n_toa + 1)

    def _qmap(self, seed=0):
        table = wide_table(self.n_pix, self.n_toa, WIDE_BINS, np.int32, seed)
        return PixelBinMap(table=table, id_base=self.id_base)

    @pytest.mark.usefixtures("low_crossover")
    def test_state_bit_identical_to_the_gather_path(self, monkeypatch):
        plain = QHistogrammer(
            qmap=self._qmap(), toa_edges=self.edges, n_q=WIDE_BINS, method="scatter"
        )
        dense = _packed_histogrammer(
            monkeypatch, self._qmap(), self.edges, "scatter", WIDE_BINS
        )
        assert not plain._planes and dense._planes == 2
        assert dense._qmap.shape == (2, 512, 768)
        before = Q_LOOKUP_STEPS.value(lookup="windowed")
        s_plain, s_dense = plain.init_state(), dense.init_state()
        for seed in range(3):
            batch = _events(seed, 5000, self.n_pix, self.id_base)
            s_plain = plain.step(s_plain, batch, monitor_count=2.0)
            s_dense = dense.step(s_dense, batch, monitor_count=2.0)
        assert float(s_plain.cumulative.sum()) > 0
        assert np.asarray(s_plain.cumulative)[256:].sum() > 0  # past one byte
        for got, want in zip(s_dense, s_plain, strict=True):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert Q_LOOKUP_STEPS.value(lookup="windowed") == before + 3

    @pytest.mark.usefixtures("low_crossover")
    def test_swap_table_compiles_nothing(self, monkeypatch):
        dense = _packed_histogrammer(
            monkeypatch, self._qmap(), self.edges, "scatter", WIDE_BINS
        )
        batch = _events(9, 5000, self.n_pix, self.id_base)
        first = dense.step(dense.init_state(), batch)
        compiled = dense._step._cache_size(), pallas_lookup.pack_table._cache_size()
        swapped = self._qmap(seed=1)
        dense.swap_table(swapped)
        assert dense._qmap.shape == (2, 512, 768)
        second = dense.step(dense.init_state(), batch)
        assert (
            dense._step._cache_size(),
            pallas_lookup.pack_table._cache_size(),
        ) == compiled
        plain = QHistogrammer(qmap=swapped, toa_edges=self.edges, n_q=WIDE_BINS)
        want = plain.step(plain.init_state(), batch)
        np.testing.assert_array_equal(
            np.asarray(second.cumulative), np.asarray(want.cumulative)
        )
        assert not np.array_equal(
            np.asarray(first.cumulative), np.asarray(second.cumulative)
        )

    def test_batch_under_the_crossover_counts_as_gather(self, monkeypatch):
        dense = _packed_histogrammer(
            monkeypatch, self._qmap(), self.edges, "scatter", WIDE_BINS
        )
        plain = QHistogrammer(qmap=self._qmap(), toa_edges=self.edges, n_q=WIDE_BINS)
        before = Q_LOOKUP_STEPS.value(lookup="gather")
        batch = _events(4, 4096, self.n_pix, self.id_base)
        got = dense.step(dense.init_state(), batch)
        want = plain.step(plain.init_state(), batch)
        np.testing.assert_array_equal(
            np.asarray(got.cumulative), np.asarray(want.cumulative)
        )
        assert Q_LOOKUP_STEPS.value(lookup="gather") == before + 2

    @pytest.mark.usefixtures("low_crossover")
    def test_both_lookup_series_exist_once_a_kernel_does(self, monkeypatch):
        # every step windowed: the gather's series reads 0, not "no
        # sample", so the benchmark's q_lookup_gather_share is 0.0
        dense = _packed_histogrammer(
            monkeypatch, self._qmap(), self.edges, "scatter", WIDE_BINS
        )
        series = {labels["lookup"] for labels, _ in Q_LOOKUP_STEPS.items()}
        assert series == {"windowed", "gather"}
        before = {k: Q_LOOKUP_STEPS.value(lookup=k) for k in series}
        dense.step(dense.init_state(), _events(1, 5000, self.n_pix, self.id_base))
        assert Q_LOOKUP_STEPS.value(lookup="gather") == before["gather"]
        assert Q_LOOKUP_STEPS.value(lookup="windowed") == before["windowed"] + 1

    def test_table_bytes_gauge_counts_both_planes(self, monkeypatch):
        from esslivedata_tpu.telemetry.instruments import TABLE_BYTES

        before = TABLE_BYTES.value(family="dspacing")
        qmap = self._qmap()._replace(family="dspacing")
        dense = _packed_histogrammer(
            monkeypatch, qmap, self.edges, "scatter", WIDE_BINS
        )
        # 4 B an entry, what the int32 table was, plus the padding
        assert TABLE_BYTES.value(family="dspacing") - before == 2 * 512 * 768 * 2
        del dense


def test_no_service_imports_the_kernel_at_start():
    # the import rule: the module is loaded inside the Q path, so a
    # detector service's import graph and set-up are what they were
    code = (
        "import sys\n"
        "import esslivedata_tpu.services.detector_data\n"
        "import esslivedata_tpu.services.data_reduction\n"
        "import esslivedata_tpu.ops.qhistogram\n"
        "assert not [m for m in sys.modules if m.endswith('pallas_lookup')]\n"
    )
    src = os.path.join(os.path.dirname(__file__), "..", "..", "src")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": src}
    subprocess.run([sys.executable, "-c", code], check=True, env=env)

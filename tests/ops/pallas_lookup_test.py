"""Windowed table lookup (ops/pallas_lookup.py): parity with ``table[pid, tb]``.

Runs the Pallas kernel in interpret mode on the CPU; the compiled path
is what ``scripts/tpu_kernel_check.py --lookup`` checks on the chip. The
crossover is lowered for the kernel's cases, so that interpret mode
stays quick; the last cases run under the real one.
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


def random_table(n_pix, n_toa, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(-1, N_Q, (n_pix, n_toa)).astype(np.int16)


def sorted_reference(table, pid, tb, ok):
    """``table[pid, tb]`` (-1 where dropped) in the order the windowed
    lookup returns it: by packed key, dropped events last."""
    shift = pallas_lookup._toa_bits(-(-table.shape[1] // 16) * 16)
    keys = np.where(ok, (pid.astype(np.int64) << shift) | tb, SENTINEL)
    order = np.argsort(keys, kind="stable")
    return np.where(ok, table[pid, tb], -1)[order]


def windowed(table, pid, tb, ok):
    packed = pallas_lookup.pack_table(jnp.asarray(table))
    assert pallas_lookup.lookup_kind(len(pid), table.shape[0]) == "windowed"
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

    def test_pack_table_layout(self):
        table = random_table(300, 200)
        packed = np.asarray(pallas_lookup.pack_table(jnp.asarray(table)))
        assert packed.dtype == jnp.bfloat16
        assert packed.shape == (208, 384)
        np.testing.assert_array_equal(packed[:200, :300], table.T)
        assert not packed[200:].any() and not packed[:, 300:].any()

    def test_work_items_cover_each_block_once_per_window(self):
        shift = 8
        pid = np.sort(np.random.default_rng(1).integers(0, 1000, 3000))
        keys = np.full(4096, SENTINEL, np.int32)
        keys[:3000] = pid << shift
        block, window, n_items = map(
            np.asarray, pallas_lookup._work_items(jnp.asarray(keys), 8, shift)
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
        assert pallas_lookup.lookup_kind(n_events, n_pix) == kind


class TestPackable:
    def test_loki_tables_are(self):
        for n_pix in (802_816, 172_032):
            table = np.lib.stride_tricks.as_strided(
                np.zeros(1, np.int16), (n_pix, 200), (0, 0)
            )
            assert pallas_lookup.packable(table, N_Q)

    @pytest.mark.parametrize(
        ("dtype", "n_bins", "n_pix"),
        [
            (np.int32, N_Q, 1000),  # the builders' wide tables
            (np.int16, 256, 1000),  # n_bins + 1 past bfloat16's integers
            (np.int16, N_Q, 1 << 23),  # a packed key would pass int32
        ],
    )
    def test_others_are_not(self, dtype, n_bins, n_pix):
        table = np.lib.stride_tricks.as_strided(
            np.zeros(1, dtype), (n_pix, 200), (0, 0)
        )
        assert not pallas_lookup.packable(table, n_bins)


def _events(seed, n, n_pix, id_base):
    rng = np.random.default_rng(seed)
    return EventBatch.from_arrays(
        # ids below the bank, inside it and above it
        rng.integers(id_base - 3, id_base + n_pix + 3, n).astype(np.int64),
        rng.uniform(-1e6, 7.3e7, n).astype(np.float32),
    )


def _packed_histogrammer(monkeypatch, qmap, edges, method):
    """A QHistogrammer that took the packed layout, as on a TPU: the
    backend is asked at construction alone, so the step itself traces
    for the CPU (interpret mode)."""
    with monkeypatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        hist = QHistogrammer(qmap=qmap, toa_edges=edges, n_q=N_Q, method=method)
    assert hist._packed
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
        assert not plain._packed
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
        assert pallas_lookup.lookup_kind(n, self.n_pix) == "gather"
        plain = QHistogrammer(qmap=self._qmap(), toa_edges=edges, n_q=N_Q)
        dense = _packed_histogrammer(monkeypatch, self._qmap(), edges, "scatter")
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
        packed = pallas_lookup.pack_table(jnp.asarray(table))
        got = pallas_lookup.lookup(
            packed, jnp.asarray(pid), jnp.asarray(tb), jnp.asarray(ok)
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.where(ok, table[pid, tb], -1)
        )

    def test_the_cpu_keeps_the_int_table(self):
        edges = np.linspace(0.0, 7.1e7, self.n_toa + 1)
        hist = QHistogrammer(qmap=self._qmap(), toa_edges=edges, n_q=N_Q, method="auto")
        assert not hist._packed
        assert hist._qmap.dtype == jnp.int16
        assert hist._qmap.shape == (self.n_pix, self.n_toa)


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

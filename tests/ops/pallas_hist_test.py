"""Pallas bincount kernels (the flat one-hot and the factorised one on
the MXU): parity with numpy and with the XLA scatter path, and which of
the three ``QHistogrammer(method="auto")`` takes.

Runs in interpret mode on the CPU test mesh; the compiled path is what
``scripts/tpu_kernel_check.py --bincount`` measures on the chip."""

import jax
import numpy as np
import pytest

from esslivedata_tpu.ops import EventBatch, EventHistogrammer
from esslivedata_tpu.ops.pallas_hist import (
    MAX_MXU_BINS,
    MAX_PALLAS_BINS,
    MXU_CHUNK,
    MXU_LANE_GROUPS,
    bincount_mxu,
    bincount_pallas,
)
from esslivedata_tpu.telemetry.instruments import Q_BINCOUNT_STEPS


class TestBincountKernel:
    @pytest.mark.parametrize("n_bins", [1, 100, 129, 1001, 3200])
    def test_parity_with_numpy(self, n_bins):
        rng = np.random.default_rng(n_bins)
        flat = rng.integers(-3, n_bins + 5, 4096).astype(np.int32)
        counts = np.asarray(bincount_pallas(flat, n_bins))
        valid = flat[(flat >= 0) & (flat < n_bins)]
        np.testing.assert_array_equal(
            counts, np.bincount(valid, minlength=n_bins)
        )

    def test_unaligned_event_count_pads_safely(self):
        flat = np.array([0, 1, 1, 2], np.int32)  # far below one block
        counts = np.asarray(bincount_pallas(flat, 4))
        np.testing.assert_array_equal(counts, [1, 2, 1, 0])

    def test_empty(self):
        counts = np.asarray(bincount_pallas(np.empty(0, np.int32), 8))
        np.testing.assert_array_equal(counts, np.zeros(8))

    def test_bin_bound_enforced(self):
        with pytest.raises(ValueError, match="VMEM"):
            bincount_pallas(np.zeros(4, np.int32), MAX_PALLAS_BINS + 1)


def _mxu_case(case: str, n_bins: int) -> np.ndarray:
    rng = np.random.default_rng(n_bins)
    if case == "unaligned":  # no multiple of the chunk, every bin's edge
        flat = rng.integers(0, n_bins, 3 * MXU_CHUNK + 77)
        flat[:4] = (0, 127, 128, n_bins - 1)
    elif case == "empty":
        flat = np.empty(0)
    elif case == "out_of_range":  # counted nowhere: the step's drop slot
        flat = rng.integers(0, n_bins, 2 * MXU_CHUNK)
        flat[::3] = -1
        flat[1::3] = n_bins
        flat[2::7] = n_bins + 128
    else:  # one bin past what a bf16 or a 16-bit count would hold
        assert case == "heavy_bin"
        flat = rng.integers(0, n_bins, (1 << 16) + 2 * MXU_CHUNK + 5)
        flat[: (1 << 16) + 3] = n_bins - 2
    return flat.astype(np.int32)


class TestBincountMxu:
    @pytest.mark.parametrize(
        "case", ["unaligned", "empty", "out_of_range", "heavy_bin"]
    )
    @pytest.mark.parametrize("n_bins", [129, 4_800, 10_000, 34_000, 65_536])
    def test_parity_with_numpy(self, n_bins, case):
        flat = _mxu_case(case, n_bins)
        counts = np.asarray(bincount_mxu(flat, n_bins))
        assert counts.shape == (n_bins,) and counts.dtype == np.float32
        valid = flat[(flat >= 0) & (flat < n_bins)]
        want = np.bincount(valid, minlength=n_bins)
        np.testing.assert_array_equal(counts, want)
        if case == "heavy_bin":
            assert want.max() > 1 << 16

    def test_bin_bound_enforced(self):
        assert MAX_MXU_BINS == 65_536
        with pytest.raises(ValueError, match="VMEM"):
            bincount_mxu(np.zeros(4, np.int32), MAX_MXU_BINS + 1)


def _q_histogrammer(n_q: int, method: str, backend: str | None = None):
    from esslivedata_tpu.ops.qhistogram import PixelBinMap, QHistogrammer

    table = np.arange(40, dtype=np.int32).reshape(4, 10) % n_q
    kw = dict(
        qmap=PixelBinMap(table=table, id_base=0),
        toa_edges=np.linspace(0, 1e6, 11),
        n_q=n_q,
        method=method,
    )
    if backend is None:
        return QHistogrammer(**kw)
    # the backend is asked at construction alone; the step itself
    # traces for the CPU (interpret mode)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: backend)
        return QHistogrammer(**kw)


class TestQHistogrammerBincountChoice:
    @pytest.mark.parametrize(
        "n_q, method",
        [
            (100, "pallas"),  # LOKI's I(Q): one lane group
            (128 * (MXU_LANE_GROUPS - 1), "pallas"),
            (128 * (MXU_LANE_GROUPS - 1) + 1, "mxu"),
            (4_800, "mxu"),  # BIFROST's S(Q, E)
            (10_000, "mxu"),  # BIFROST's elastic map
            (34_000, "mxu"),  # DREAM's I(d, 2-theta)
            (MAX_MXU_BINS, "mxu"),
            (70_000, "scatter"),
        ],
    )
    def test_auto_on_a_tpu_goes_by_the_bin_space(self, n_q, method):
        assert _q_histogrammer(n_q, "auto", "tpu")._method == method

    @pytest.mark.parametrize("n_q", [100, 4_800, 10_000, 34_000, 70_000])
    def test_auto_on_the_cpu_is_the_scatter(self, n_q):
        assert _q_histogrammer(n_q, "auto")._method == "scatter"
        assert _q_histogrammer(n_q, "auto", "gpu")._method == "scatter"

    def test_the_flat_kernels_bound_stands(self):
        assert MAX_PALLAS_BINS == 8192 and MXU_LANE_GROUPS * 128 < MAX_PALLAS_BINS

    def test_mxu_bin_bound_enforced(self):
        with pytest.raises(ValueError, match="mxu"):
            _q_histogrammer(MAX_MXU_BINS + 1, "mxu")

    def test_every_label_has_a_sample_after_construction(self):
        _q_histogrammer(100, "scatter")
        labels = {labels["method"] for labels, _ in Q_BINCOUNT_STEPS.items()}
        assert {"scatter", "onehot", "mxu"} <= labels

    @pytest.mark.parametrize(
        "method, label",
        [("scatter", "scatter"), ("pallas", "onehot"), ("mxu", "mxu")],
    )
    def test_a_step_counts_under_the_label_it_was_traced_with(self, method, label):
        hist = _q_histogrammer(300, method)
        before = {
            kind: Q_BINCOUNT_STEPS.value(method=kind)
            for kind in ("scatter", "onehot", "mxu")
        }
        batch = EventBatch.from_arrays(
            np.array([0, 1, 2, 3, 9], np.int64),
            np.array([1e5, 2e5, 3e5, 9.5e5, 1e5], np.float32),
        )
        state = hist.step(hist.init_state(), batch)
        assert float(state.window.sum()) == 4.0
        for kind, was in before.items():
            assert Q_BINCOUNT_STEPS.value(method=kind) == was + (kind == label)


class TestHistogrammerMxuMethod:
    """EventHistogrammer's one-block MXU count (ADR 0131), which took the
    place of its flat one-hot method, at monitor-sized bin spaces."""

    def _batches(self, n_batches=3, n=3000, n_pixel=8):
        rng = np.random.default_rng(5)
        return [
            EventBatch.from_arrays(
                rng.integers(-1, n_pixel + 2, n).astype(np.int64),
                rng.uniform(-1e6, 7.3e7, n).astype(np.float32),
            )
            for _ in range(n_batches)
        ]

    @pytest.mark.parametrize("decay", [None, 0.9])
    def test_parity_with_scatter_method(self, decay):
        edges = np.linspace(0.0, 7.1e7, 101)
        kw = dict(toa_edges=edges, n_screen=8, decay=decay)
        ref = EventHistogrammer(method="scatter", **kw)
        mxu = EventHistogrammer(method="mxu", **kw)
        s_ref, s_mxu = ref.init_state(), mxu.init_state()
        for batch in self._batches():
            s_ref = ref.step(s_ref, batch)
            s_mxu = mxu.step(s_mxu, batch)
        cum_ref, win_ref = ref.read(s_ref)
        cum_mxu, win_mxu = mxu.read(s_mxu)
        np.testing.assert_allclose(win_mxu, win_ref, rtol=1e-6)
        np.testing.assert_allclose(cum_mxu, cum_ref, rtol=1e-6)

    def test_step_flat_parity(self):
        edges = np.linspace(0.0, 7.1e7, 1001)
        ref = EventHistogrammer(toa_edges=edges, method="scatter")
        mxu = EventHistogrammer(toa_edges=edges, method="mxu")
        rng = np.random.default_rng(2)
        pid = rng.integers(0, 1, 5000).astype(np.int32)
        toa = rng.uniform(0, 7.1e7, 5000).astype(np.float32)
        flat = ref.flatten_host(pid, toa)
        s_ref = ref.step_flat(ref.init_state(), flat)
        s_mxu = mxu.step_flat(mxu.init_state(), flat)
        # the views: the MXU state is block-padded and leaves the dump empty
        for a, b in zip(ref.read(s_ref), mxu.read(s_mxu), strict=True):
            np.testing.assert_array_equal(a, b)

    def test_weighted_config_falls_back_to_scatter(self):
        # Per-event weight arrays are outside the kernel's contract; the
        # method silently uses the scatter for them — parity must hold.
        edges = np.linspace(0.0, 7.1e7, 51)
        weights = np.linspace(0.5, 2.0, 16).astype(np.float32)
        kw = dict(
            toa_edges=edges, n_screen=4,
            pixel_lut=(np.arange(16) % 4).astype(np.int32),
            pixel_weights=weights,
        )
        ref = EventHistogrammer(method="scatter", **kw)
        mxu = EventHistogrammer(method="mxu", **kw)
        batch = self._batches(1, n=2000, n_pixel=16)[0]
        w_ref = ref.read(ref.step(ref.init_state(), batch))[1]
        w_mxu = mxu.read(mxu.step(mxu.init_state(), batch))[1]
        np.testing.assert_allclose(w_mxu, w_ref, rtol=1e-6)

    def test_the_flat_one_hot_is_no_method_of_the_view(self):
        with pytest.raises(ValueError, match="pallas"):
            EventHistogrammer(
                toa_edges=np.linspace(0, 7.1e7, 101), n_screen=8, method="pallas"
            )


class TestQHistogrammerPallasMethod:
    @pytest.mark.parametrize("method", ["pallas", "mxu"])
    def test_parity_with_scatter(self, method):
        from esslivedata_tpu.ops.qhistogram import (
            QHistogrammer,
            build_dspacing_map,
        )

        rng = np.random.default_rng(4)
        n_pixel = 25
        dmap = build_dspacing_map(
            two_theta=rng.uniform(0.3, 2.4, n_pixel),
            l_total=rng.uniform(60.0, 90.0, n_pixel),
            pixel_ids=np.arange(30, 30 + n_pixel),
            toa_edges=np.linspace(0.0, 7.1e7, 41),
            d_edges=np.linspace(0.4, 2.8, 33),
        )
        kw = dict(qmap=dmap, toa_edges=np.linspace(0.0, 7.1e7, 41), n_q=32)
        ref = QHistogrammer(method="scatter", **kw)
        pal = QHistogrammer(method=method, **kw)
        s_ref, s_pal = ref.init_state(), pal.init_state()
        for seed in range(3):
            r = np.random.default_rng(seed)
            batch = EventBatch.from_arrays(
                r.integers(20, 70, 2000).astype(np.int64),
                r.uniform(-1e6, 7.5e7, 2000).astype(np.float32),
            )
            s_ref = ref.step(s_ref, batch, 10.0)
            s_pal = pal.step(s_pal, batch, 10.0)
        np.testing.assert_array_equal(
            np.asarray(s_ref.window), np.asarray(s_pal.window)
        )
        np.testing.assert_array_equal(
            np.asarray(s_ref.cumulative), np.asarray(s_pal.cumulative)
        )

    def test_bin_bound_enforced(self):
        from esslivedata_tpu.ops.qhistogram import PixelBinMap, QHistogrammer

        table = np.zeros((4, 10), np.int32)
        with pytest.raises(ValueError, match="pallas"):
            QHistogrammer(
                qmap=PixelBinMap(table=table, id_base=0),
                toa_edges=np.linspace(0, 1e6, 11),
                n_q=MAX_PALLAS_BINS + 5,
                method="pallas",
            )

import numpy as np
import pytest

from esslivedata_tpu.ops import (
    EventBatch,
    EventHistogrammer,
    StagingBuffer,
    bucket_size,
)


def np_hist2d(pixel_id, toa, n_screen, edges, lut=None, weights=None):
    """Reference histogram via numpy."""
    pixel_id = np.asarray(pixel_id)
    toa = np.asarray(toa, dtype=np.float64)
    h = np.zeros((n_screen, len(edges) - 1))
    tb = np.searchsorted(edges, toa, side="right") - 1
    for p, t, tbin in zip(pixel_id, toa, tb, strict=True):
        if not (0 <= tbin < len(edges) - 1) or t == edges[-1]:
            continue
        if lut is not None:
            if not (0 <= p < lut.shape[-1]):
                continue
            rows = lut[:, p] if lut.ndim == 2 else [lut[p]]
            for s in rows:
                if s >= 0:
                    w = weights[p] if weights is not None else 1.0
                    h[s, tbin] += w / len(rows)
        else:
            if 0 <= p < n_screen:
                w = weights[p] if weights is not None else 1.0
                h[p, tbin] += w
    return h


class TestBucketing:
    def test_bucket_size(self):
        assert bucket_size(0) == 4096
        assert bucket_size(4096) == 4096
        assert bucket_size(4097) == 8192
        assert bucket_size(100_000) == 131072

    def test_from_arrays_pads_with_invalid(self):
        b = EventBatch.from_arrays(
            np.array([1, 2, 3], dtype=np.int32),
            np.array([10.0, 20.0, 30.0], dtype=np.float32),
        )
        assert b.padded_size == 4096
        assert b.n_valid == 3
        assert (b.pixel_id[3:] == -1).all()


class TestStagingBuffer:
    def test_accumulate_and_take(self):
        buf = StagingBuffer(min_bucket=8)
        buf.add(np.array([1, 2], dtype=np.int32), np.array([1.0, 2.0], dtype=np.float32))
        buf.add(np.array([3], dtype=np.int32), np.array([3.0], dtype=np.float32))
        batch = buf.take()
        assert batch.n_valid == 3
        assert batch.padded_size == 8
        np.testing.assert_array_equal(batch.pixel_id[:3], [1, 2, 3])
        assert (batch.pixel_id[3:] == -1).all()

    def test_in_use_guard(self):
        buf = StagingBuffer(min_bucket=8)
        buf.add(np.array([1], dtype=np.int32), np.array([1.0], dtype=np.float32))
        buf.take()
        with pytest.raises(RuntimeError):
            buf.add(np.array([2], dtype=np.int32), np.array([2.0], dtype=np.float32))
        buf.release()
        buf.add(np.array([2], dtype=np.int32), np.array([2.0], dtype=np.float32))
        assert len(buf) == 1

    def test_growth_preserves_data(self):
        buf = StagingBuffer(min_bucket=4)
        for i in range(100):
            buf.add(
                np.array([i], dtype=np.int32), np.array([float(i)], dtype=np.float32)
            )
        batch = buf.take()
        assert batch.n_valid == 100
        np.testing.assert_array_equal(batch.pixel_id[:100], np.arange(100))

    def test_stale_padding_cleared(self):
        buf = StagingBuffer(min_bucket=8)
        buf.add(np.arange(8, dtype=np.int32), np.zeros(8, dtype=np.float32))
        buf.take()
        buf.release()
        buf.add(np.array([5], dtype=np.int32), np.array([0.0], dtype=np.float32))
        batch = buf.take()
        assert batch.n_valid == 1
        assert (batch.pixel_id[1:] == -1).all()


def make_events(n, n_pixel, rng=None, toa_max=71_000_000.0):
    rng = rng or np.random.default_rng(0)
    pid = rng.integers(0, n_pixel, n).astype(np.int32)
    toa = rng.uniform(0, toa_max, n).astype(np.float32)
    return pid, toa


class TestEventHistogrammer:
    def test_monitor_1d(self):
        edges = np.linspace(0.0, 100.0, 11)
        h = EventHistogrammer(toa_edges=edges, n_screen=1)
        state = h.init_state()
        pid = np.zeros(7, dtype=np.int32)
        toa = np.array([5, 15, 15, 25, 99, 100, -1], dtype=np.float32)
        state = h.step(state, EventBatch.from_arrays(pid, toa, min_bucket=8))
        hist = h.read(state)[1]
        expected = np_hist2d(pid, toa, 1, edges)
        np.testing.assert_allclose(hist, expected)
        assert hist.sum() == 5  # 100 and -1 out of range

    def test_2d_identity_pixels(self):
        edges = np.linspace(0.0, 1000.0, 5)
        h = EventHistogrammer(toa_edges=edges, n_screen=8)
        state = h.init_state()
        pid, toa = make_events(1000, 8, toa_max=1000.0)
        state = h.step(state, EventBatch.from_arrays(pid, toa))
        np.testing.assert_allclose(
            h.read(state)[1], np_hist2d(pid, toa, 8, edges), rtol=1e-6
        )

    def test_padding_dropped(self):
        edges = np.linspace(0.0, 10.0, 3)
        h = EventHistogrammer(toa_edges=edges, n_screen=4)
        state = h.init_state()
        batch = EventBatch.from_arrays(
            np.array([0], dtype=np.int32), np.array([5.0], dtype=np.float32)
        )
        state = h.step(state, batch)
        assert float(h.read(state)[1].sum()) == 1.0

    def test_pixel_lut_projection(self):
        edges = np.linspace(0.0, 10.0, 3)
        lut = np.array([2, 2, 0, -1], dtype=np.int32)  # pixel 3 masked out
        h = EventHistogrammer(toa_edges=edges, n_screen=3, pixel_lut=lut)
        state = h.init_state()
        pid = np.array([0, 1, 2, 3, 7], dtype=np.int32)  # 7 out of LUT range
        toa = np.full(5, 1.0, dtype=np.float32)
        state = h.step(state, EventBatch.from_arrays(pid, toa, min_bucket=8))
        hist = h.read(state)[1]
        np.testing.assert_allclose(hist, np_hist2d(pid, toa, 3, edges, lut=lut))
        assert hist[2, 0] == 2.0 and hist[0, 0] == 1.0 and hist.sum() == 3.0

    def test_replica_lut(self):
        edges = np.linspace(0.0, 10.0, 2)
        lut = np.array([[0, 1], [1, 1]], dtype=np.int32)  # 2 replicas, 2 pixels
        h = EventHistogrammer(toa_edges=edges, n_screen=2, pixel_lut=lut)
        state = h.init_state()
        pid = np.array([0, 1], dtype=np.int32)
        toa = np.full(2, 5.0, dtype=np.float32)
        state = h.step(state, EventBatch.from_arrays(pid, toa, min_bucket=8))
        hist = h.read(state)[1]
        # pixel 0 -> screens {0,1} at half weight; pixel 1 -> screen 1 twice
        np.testing.assert_allclose(hist[:, 0], [0.5, 1.5])

    def test_pixel_weights(self):
        edges = np.linspace(0.0, 10.0, 2)
        weights = np.array([2.0, 0.5], dtype=np.float32)
        h = EventHistogrammer(toa_edges=edges, n_screen=2, pixel_weights=weights)
        state = h.init_state()
        pid = np.array([0, 1], dtype=np.int32)
        toa = np.full(2, 5.0, dtype=np.float32)
        state = h.step(state, EventBatch.from_arrays(pid, toa, min_bucket=8))
        np.testing.assert_allclose(h.read(state)[1][:, 0], [2.0, 0.5])

    def test_nonuniform_edges(self):
        edges = np.array([0.0, 1.0, 10.0, 100.0, 1000.0])
        h = EventHistogrammer(toa_edges=edges, n_screen=1)
        state = h.init_state()
        toa = np.array([0.5, 5.0, 50.0, 500.0, 999.0, 1000.0], dtype=np.float32)
        pid = np.zeros(6, dtype=np.int32)
        state = h.step(state, EventBatch.from_arrays(pid, toa, min_bucket=8))
        np.testing.assert_allclose(h.read(state)[1][0], [1, 1, 1, 2])

    def test_cumulative_vs_window(self):
        edges = np.linspace(0.0, 10.0, 2)
        h = EventHistogrammer(toa_edges=edges, n_screen=1)
        state = h.init_state()
        batch = EventBatch.from_arrays(
            np.zeros(4, dtype=np.int32),
            np.full(4, 5.0, dtype=np.float32),
            min_bucket=8,
        )
        state = h.step(state, batch)
        state = h.clear_window(state)
        state = h.step(state, batch)
        cum, win = h.read(state)
        assert float(win.sum()) == 4.0
        assert float(cum.sum()) == 8.0
        state = h.clear(state)
        assert float(h.read(state)[0].sum()) == 0.0

    def test_decay_window(self):
        edges = np.linspace(0.0, 10.0, 2)
        h = EventHistogrammer(toa_edges=edges, n_screen=1, decay=0.5)
        state = h.init_state()
        batch = EventBatch.from_arrays(
            np.zeros(2, dtype=np.int32),
            np.full(2, 5.0, dtype=np.float32),
            min_bucket=8,
        )
        state = h.step(state, batch)  # window = 2
        state = h.step(state, batch)  # window = 2*0.5 + 2 = 3
        cum, win = h.read(state)
        assert float(win.sum()) == pytest.approx(3.0)
        # In decay mode the cumulative view tracks the decayed EMA (a raw
        # count alongside would cost a second scatter per step).
        assert float(cum.sum()) == pytest.approx(3.0)

    def test_sort_method_matches_scatter(self):
        edges = np.linspace(0.0, 71_000_000.0, 101)
        pid, toa = make_events(50_000, 64)
        batches = [EventBatch.from_arrays(pid, toa)]
        results = []
        for method in ("scatter", "sort"):
            h = EventHistogrammer(toa_edges=edges, n_screen=64, method=method)
            state = h.init_state()
            for b in batches:
                state = h.step(state, b)
            results.append(h.read(state)[1])
        np.testing.assert_allclose(results[0], results[1], rtol=1e-5)

    def test_large_random_vs_numpy(self):
        edges = np.linspace(0.0, 71_000_000.0, 50)
        pid, toa = make_events(20_000, 128)
        h = EventHistogrammer(toa_edges=edges, n_screen=128)
        state = h.init_state()
        state = h.step(state, EventBatch.from_arrays(pid, toa))
        ours = h.read(state)[1]
        ref = np_hist2d(pid, toa, 128, edges)
        # float32 toa binning may place boundary-adjacent events one bin
        # off vs float64 numpy; totals must match exactly, bins closely.
        assert ours.sum() == ref.sum()
        assert np.abs(ours - ref).sum() <= 4

    def test_bad_edges_raise(self):
        with pytest.raises(ValueError):
            EventHistogrammer(toa_edges=np.array([1.0]))
        with pytest.raises(ValueError):
            EventHistogrammer(toa_edges=np.array([1.0, 0.5]))
        with pytest.raises(ValueError):
            EventHistogrammer(
                toa_edges=np.array([0.0, 1.0]),
                n_screen=2,
                pixel_lut=np.array([5], dtype=np.int32),
            )


class TestFlatFastPath:
    def test_flatten_host_matches_device_path(self):
        edges = np.linspace(0.0, 71_000_000.0, 101)
        pid, toa = make_events(10_000, 64)
        pid[:10] = -1  # invalid events must be dropped on both paths
        h = EventHistogrammer(toa_edges=edges, n_screen=64)
        s1 = h.step(h.init_state(), EventBatch.from_arrays(pid, toa))
        flat = h.flatten_host(pid, toa)
        s2 = h.step_flat(h.init_state(), flat)
        np.testing.assert_allclose(h.read(s1)[1], h.read(s2)[1], rtol=1e-6)

    def test_flatten_host_with_lut(self):
        edges = np.linspace(0.0, 10.0, 3)
        lut = np.array([2, 2, 0, -1], dtype=np.int32)
        h = EventHistogrammer(toa_edges=edges, n_screen=3, pixel_lut=lut)
        pid = np.array([0, 1, 2, 3, 7], dtype=np.int32)
        toa = np.full(5, 1.0, dtype=np.float32)
        flat = h.flatten_host(pid, toa)
        state = h.step_flat(h.init_state(), flat)
        np.testing.assert_allclose(
            h.read(state)[1], np_hist2d(pid, toa, 3, edges, lut=lut)
        )

    def test_flatten_host_rejects_replicas_and_weights(self):
        edges = np.linspace(0.0, 10.0, 2)
        h = EventHistogrammer(
            toa_edges=edges,
            n_screen=2,
            pixel_lut=np.array([[0, 1], [1, 1]], dtype=np.int32),
        )
        with pytest.raises(ValueError):
            h.flatten_host(np.array([0]), np.array([1.0]))
        h2 = EventHistogrammer(
            toa_edges=edges,
            n_screen=2,
            pixel_weights=np.array([1.0, 2.0], dtype=np.float32),
        )
        with pytest.raises(ValueError):
            h2.flatten_host(np.array([0]), np.array([1.0]))

    def test_out_of_range_flat_indices_dropped(self):
        edges = np.linspace(0.0, 10.0, 2)
        h = EventHistogrammer(toa_edges=edges, n_screen=2)
        # A buggy producer sending indices beyond the dump bin must not
        # corrupt state (mode='drop' guarantee).
        bad = np.array([0, 1, 2, 3, 999, -7], dtype=np.int32)
        state = h.step_flat(h.init_state(), bad)
        cum, win = h.read(state)
        assert win.sum() == 2.0  # only bins 0 and 1 land

    def test_small_negative_flat_indices_do_not_wrap(self):
        # JAX scatter bounds-checks after one negative wrap: with 3 bins of
        # state (2 screen rows + dump), flat=-2 would wrap to bin 1 and
        # silently corrupt a real count. The kernel must route every
        # negative index to the dump bin instead.
        edges = np.linspace(0.0, 10.0, 2)
        h = EventHistogrammer(toa_edges=edges, n_screen=2)
        bad = np.array([0, -1, -2, -3], dtype=np.int32)
        state = h.step_flat(h.init_state(), bad)
        cum, win = h.read(state)
        np.testing.assert_array_equal(win, [[1.0], [0.0]])

    def test_nonuniform_edges_host_device_bit_identical(self):
        # Host flatten must bin with the same float32 edges the device
        # projection uses, or boundary-adjacent events land one bin apart
        # between the two ingest paths.
        edges = np.array([0.0, 1e7 + 0.3, 2.5e7, 7.1e7])
        h = EventHistogrammer(toa_edges=edges, n_screen=8)
        rng = np.random.default_rng(5)
        pid = rng.integers(0, 8, 20_000).astype(np.int32)
        toa = rng.uniform(0, 7.1e7, 20_000).astype(np.float32)
        # Salt with exact float32 edge values — the adversarial case.
        toa[:3] = np.float32(edges[1])
        s_dev = h.step(h.init_state(), EventBatch.from_arrays(pid, toa))
        s_host = h.step_flat(h.init_state(), h.flatten_host(pid, toa))
        np.testing.assert_array_equal(h.read(s_dev)[1], h.read(s_host)[1])



class TestLazyDecay:
    def test_long_decay_run_with_renormalization(self):
        # decay=0.5 underflows the lazy scale past the renorm floor
        # (~0.5**40 < 1e-12), so this crosses at least one renormalization.
        edges = np.linspace(0.0, 10.0, 2)
        h = EventHistogrammer(toa_edges=edges, n_screen=1, decay=0.5)
        state = h.init_state()
        batch = EventBatch.from_arrays(
            np.zeros(2, dtype=np.int32),
            np.full(2, 5.0, dtype=np.float32),
            min_bucket=8,
        )
        expected = 0.0
        for _ in range(60):
            state = h.step(state, batch)
            expected = expected * 0.5 + 2.0
        cum, win = h.read(state)
        assert float(win.sum()) == pytest.approx(expected, rel=1e-5)

    def test_decay_clear_window_resets_scale(self):
        edges = np.linspace(0.0, 10.0, 2)
        h = EventHistogrammer(toa_edges=edges, n_screen=1, decay=0.5)
        state = h.init_state()
        batch = EventBatch.from_arrays(
            np.zeros(2, dtype=np.int32),
            np.full(2, 5.0, dtype=np.float32),
            min_bucket=8,
        )
        state = h.step(state, batch)
        state = h.clear_window(state)
        assert float(np.asarray(state.scale)) == 1.0
        state = h.step(state, batch)
        cum, win = h.read(state)
        assert float(win.sum()) == pytest.approx(2.0)
        assert float(cum.sum()) == pytest.approx(4.0)  # folded EMA + new window


def test_wide_pixel_ids_beyond_int32_are_dumped():
    # int64 ids outside int32 must dump, not wrap into real bins.
    edges = np.linspace(0.0, 10.0, 3)
    h = EventHistogrammer(toa_edges=edges, n_screen=8)
    pid = np.array([3, 2**32 + 5, -(2**33)], dtype=np.int64)
    toa = np.full(3, 5.0, dtype=np.float32)
    state = h.step_flat(h.init_state(), h.flatten_host(pid, toa))
    cum, win = h.read(state)
    assert win.sum() == 1.0  # only the genuine id lands
    assert win[3].sum() == 1.0


def test_wide_pixel_ids_dump_on_every_ingest_path():
    # The device path (weighted config: host flatten unsupported) and the
    # staging paths must dump out-of-int32 ids, not wrap them.
    edges = np.linspace(0.0, 10.0, 2)
    weights = np.ones(8, dtype=np.float32)
    h = EventHistogrammer(toa_edges=edges, n_screen=8, pixel_weights=weights)
    assert not h.supports_host_flatten
    pid = np.array([3, 2**32 + 5], dtype=np.int64)
    toa = np.full(2, 5.0, dtype=np.float32)
    state = h.step(h.init_state(), EventBatch.from_arrays(pid, toa, min_bucket=8))
    assert float(h.read(state)[1].sum()) == 1.0
    state = h.step_arrays(
        h.init_state(),
        np.where(pid > 2**31, pid, -1),  # padless raw-array path
        toa,
    )
    assert float(h.read(state)[1].sum()) == 0.0
    buf = StagingBuffer(min_bucket=8)
    buf.add(pid, toa)
    assert (buf.take().pixel_id[:2] == [3, -1]).all()


def test_swap_projection_device_path_never_retraces():
    # ADR 0105 uniformly: the device step threads the LUT through jit as
    # an argument, so a live-geometry swap (same-shape LUT) costs one
    # transfer — never a retrace, even if geometry flaps per batch
    # (round-3 advisor weak item: swap_projection used to recreate the
    # jit wrapper).
    edges = np.linspace(0.0, 10.0, 5)
    lut_a = np.array([0, 1, 2, 3], dtype=np.int32)
    lut_b = np.array([0, 0, 0, 0], dtype=np.int32)  # collapse to row 0
    h = EventHistogrammer(toa_edges=edges, n_screen=4, pixel_lut=lut_a)
    traces = 0
    orig = h._step_impl

    def counting(*args, **kw):
        nonlocal traces
        traces += 1
        return orig(*args, **kw)

    import jax

    h._step = jax.jit(counting, donate_argnums=(0,))
    batch = EventBatch.from_arrays(
        np.array([0, 1, 2, 3], np.int64),
        np.full(4, 5.0, np.float32),
        min_bucket=4,
    )
    state = h.step(h.init_state(), batch)
    assert traces == 1
    for flip in (lut_b, lut_a, lut_b):  # geometry flapping per batch
        assert h.swap_projection(flip)
        state = h.step(state, batch)
    assert traces == 1, "LUT swap retraced the device step"
    # And the swaps actually took effect: two batches ran under the
    # collapsed LUT (all pixels -> row 0), two under the identity one.
    img = h.read(state)[0].reshape(4, 4)
    assert img.sum() == 16.0
    row_counts = np.asarray(img).sum(axis=1)
    np.testing.assert_array_equal(row_counts, [10.0, 2.0, 2.0, 2.0])


@pytest.fixture
def small_blocks(monkeypatch):
    """``method="mxu"`` at test size: blocks of 2 048 bins, work items
    of 256 keys (interpret mode on the CPU)."""
    from esslivedata_tpu.ops import pallas_hist2d

    monkeypatch.setattr(pallas_hist2d, "COUNT_BPB", 2048)
    monkeypatch.setattr(pallas_hist2d, "COUNT_CHUNK", 256)
    monkeypatch.setattr(pallas_hist2d, "MAX_MXU_BINS", 2048)


def _mxu_batches(n_pix, n=3000, k=3, seed=38):
    rng = np.random.default_rng(seed)
    return [
        EventBatch.from_arrays(
            rng.integers(-2, n_pix + 2, n).astype(np.int32),
            rng.uniform(-1.0, 73.0, n).astype(np.float32),
        )
        for _ in range(k)
    ]


class TestMxuMethod:
    """The device-partitioned count (ADR 0131) against XLA's scatter and
    numpy, through every step path of the histogrammer."""

    EDGES = np.linspace(0.0, 71.0, 51)

    def _pair(self, **kw):
        return [
            EventHistogrammer(toa_edges=self.EDGES, method=m, **kw)
            for m in ("scatter", "mxu")
        ]

    @pytest.mark.parametrize(
        "n_screen", [8, 300], ids=["one_block", "many_blocks"]
    )
    @pytest.mark.parametrize("path", ["step", "step_batch", "step_many"])
    def test_unit_weights_match_the_scatter(self, small_blocks, n_screen, path):
        batches = _mxu_batches(n_screen)
        views = []
        for h in self._pair(n_screen=n_screen):
            states = (h.init_state(), h.init_state())
            for b in batches:
                if path == "step_many":
                    states = h.step_many(states, b)
                else:
                    states = tuple(getattr(h, path)(s, b) for s in states)
            views.append([h.read(s) for s in states])
        (scatter, _), (mxu, mxu_twin) = views
        for a, b in zip(scatter, mxu):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(mxu, mxu_twin):  # fused K states: each as a private one
            np.testing.assert_array_equal(a, b)

    def test_replica_lut_with_entries_off_screen(self, small_blocks):
        """R = 4, a fifth of the entries -1: every bin a multiple of 1/4,
        exact against the scatter and against numpy."""
        rng = np.random.default_rng(4)
        n_screen, n_pix = 300, 500
        lut = rng.integers(0, n_screen, (4, n_pix)).astype(np.int32)
        lut[rng.random(lut.shape) < 0.2] = -1
        batches = _mxu_batches(n_pix)
        out = []
        for h in self._pair(n_screen=n_screen, pixel_lut=lut):
            s = h.init_state()
            for b in batches:
                s = h.step_batch(s, b)
            out.append(h.read(s)[1])
        np.testing.assert_array_equal(out[0], out[1])
        pid = np.concatenate([np.asarray(b.pixel_id)[: b.n_valid] for b in batches])
        toa = np.concatenate([np.asarray(b.toa)[: b.n_valid] for b in batches])
        ref = np_hist2d(pid, toa, n_screen, self.EDGES, lut=lut)
        assert np.abs(out[1] - ref).sum() <= 1.0  # float32 TOA binning at edges
        assert (out[1] * 4 == np.round(out[1] * 4)).all()

    def test_the_replica_weight_is_a_scalar(self):
        import jax.numpy as jnp

        lut = np.array([[0, 1], [1, -1]], np.int32)
        pid, toa = jnp.array([0, 1], jnp.int32), jnp.array([5.0, 5.0], jnp.float32)
        h = EventHistogrammer(toa_edges=self.EDGES, n_screen=2, pixel_lut=lut)
        flat, w = h._proj.flat_and_weights(pid, toa)
        assert flat.shape == (4,) and w.ndim == 0 and float(w) == 0.5
        weighted = EventHistogrammer(
            toa_edges=self.EDGES, n_screen=2, pixel_lut=lut,
            pixel_weights=np.array([2.0, 1.0], np.float32),
        )
        _, w = weighted._proj.flat_and_weights(pid, toa)
        np.testing.assert_array_equal(np.asarray(w), [1.0, 0.5, 1.0, 0.5])

    def test_decay_against_float64(self, small_blocks):
        """``counts * (1/scale)`` a work item where the scatter added
        ``1/scale`` a slot: not bit-identical, so held to a float64
        rolling window within float32 rounding."""
        decay, n_screen = 0.8, 300
        batches = _mxu_batches(n_screen, k=5)
        h = EventHistogrammer(
            toa_edges=self.EDGES, n_screen=n_screen, method="mxu", decay=decay
        )
        s = h.init_state()
        want = np.zeros((n_screen, 50))
        for b in batches:
            s = h.step_batch(s, b)
            pid = np.asarray(b.pixel_id)[: b.n_valid]
            toa = np.asarray(b.toa)[: b.n_valid]
            want = want * decay + np_hist2d(pid, toa, n_screen, self.EDGES)
        got = h.read(s)[1]
        assert np.abs(got - want).sum() <= 2.0  # a boundary TOA or two
        close = np.isclose(got, want, rtol=1e-6, atol=0)
        assert close.mean() > 0.999

    def test_the_dump_slot_and_the_padding_stay_empty(self, small_blocks):
        h = EventHistogrammer(toa_edges=self.EDGES, n_screen=300, method="mxu")
        s = h.init_state()
        for b in _mxu_batches(300):
            s = h.step_batch(s, b)
        window = np.asarray(s.window)
        assert window.shape[0] % 2048 == 0 and window[:15_000].sum() > 0
        assert not window[15_000:].any()  # dump slot and block padding

    @pytest.mark.parametrize(
        ("backend", "kw", "want"),
        [
            ("tpu", {}, "mxu"),
            ("tpu", {"lut": 4}, "mxu"),
            ("tpu", {"weights": True}, "scatter"),
            ("tpu", {"lut": 4, "weights": True}, "scatter"),
            ("cpu", {}, "scatter"),
            ("cpu", {"lut": 4}, "scatter"),
        ],
        ids=["tpu_unit", "tpu_replicas", "tpu_weights", "tpu_replicas_weights",
             "cpu_unit", "cpu_replicas"],
    )
    def test_auto_resolves_by_what_it_observes(self, monkeypatch, backend, kw, want):
        import jax

        from esslivedata_tpu.telemetry.instruments import VIEW_STEPS

        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        n_pix = 64
        lut = None
        if kw.get("lut"):
            lut = np.random.default_rng(0).integers(-1, 16, (kw["lut"], n_pix)).astype(np.int32)
        weights = np.ones(n_pix, np.float32) if kw.get("weights") else None
        h = EventHistogrammer(
            toa_edges=self.EDGES, n_screen=16 if lut is not None else n_pix,
            pixel_lut=lut, pixel_weights=weights, method="auto",
        )
        assert h.fuse_key[1] == want
        before = VIEW_STEPS.value(kernel=want)
        h._count_scatter(1024)
        assert VIEW_STEPS.value(kernel=want) == before + 1

    def test_the_counter_has_every_label_from_construction(self):
        from esslivedata_tpu.telemetry.instruments import VIEW_STEPS

        EventHistogrammer(toa_edges=self.EDGES, n_screen=4)
        labels = {d["kernel"] for d, _ in VIEW_STEPS.items()}
        assert labels == {"scatter", "mxu"}

    @pytest.mark.parametrize(
        ("path", "want"),
        [("step", "scatter"), ("step_batch", "mxu"), ("step_flat", "mxu")],
    )
    def test_pallas2d_counts_each_step_by_the_kernel_it_ran(self, path, want):
        """pallas2d's (pixel_id, toa) device path runs XLA's scatter; the
        batches it partitions on the host run the MXU kernel."""
        from esslivedata_tpu.telemetry.instruments import VIEW_STEPS

        h = EventHistogrammer(toa_edges=self.EDGES, n_screen=8, method="pallas2d")
        batch = _mxu_batches(8, n=500, k=1)[0]
        arg = batch
        if path == "step_flat":
            n = batch.n_valid
            arg = h.flatten_host(
                np.asarray(batch.pixel_id)[:n], np.asarray(batch.toa)[:n]
            )
        before = {k: VIEW_STEPS.value(kernel=k) for k in ("scatter", "mxu")}
        state = getattr(h, path)(h.init_state(), arg)
        assert float(np.asarray(state.window).sum()) > 0
        for kind, was in before.items():
            assert VIEW_STEPS.value(kernel=kind) == was + (kind == want)

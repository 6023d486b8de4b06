"""pallas2d tiled histogram kernel: parity with the XLA scatter path.

Runs in interpret mode on the CPU test mesh; the compiled path is what
bench.py --all (headline_pallas2d) measures on real TPU hardware. The
partition fast paths (native ld_partition / ld_flatten_partition) and
the numpy fallback are each pinned against the scatter result.
"""

import numpy as np
import pytest

from esslivedata_tpu.ops import EventBatch, EventHistogrammer
from esslivedata_tpu.ops import pallas_hist2d as p2
from esslivedata_tpu.ops.pallas_hist2d import (
    DEFAULT_BPB,
    padded_bins,
    partition_events_host,
    scatter_add_pallas2d,
)


class TestPartition:
    def _check_partition(self, flat, n_incl, events, chunk_map, chunk):
        """Structural invariants + content parity with a plain bincount."""
        n_blocks = -(-n_incl // DEFAULT_BPB)
        assert events.shape[0] == chunk_map.shape[0] * chunk
        assert np.all(np.diff(chunk_map) >= 0), "map must be non-decreasing"
        assert chunk_map.min() >= 0 and chunk_map.max() < n_blocks
        rows = events.reshape(-1, chunk)
        # Every non-pad event sits in its mapped block.
        blk = rows // np.int32(DEFAULT_BPB)
        pad = rows < 0
        assert np.array_equal(rows[pad], np.full(pad.sum(), -1))
        assert np.all(blk[~pad] == np.broadcast_to(chunk_map[:, None], rows.shape)[~pad])
        # Multiset of events == routed input.
        dump = n_incl - 1
        routed = np.where((flat < 0) | (flat >= n_incl), dump, flat)
        np.testing.assert_array_equal(
            np.sort(events[events >= 0]), np.sort(routed)
        )

    @pytest.mark.parametrize("n_events", [0, 17, 4096, 50_000])
    def test_native_partition(self, n_events):
        rng = np.random.default_rng(n_events)
        n_incl = 300_001
        flat = rng.integers(-4, n_incl + 3, n_events).astype(np.int32)
        events, chunk_map = partition_events_host(flat, n_incl)
        self._check_partition(flat, n_incl, events, chunk_map, p2.DEFAULT_CHUNK)

    def test_numpy_fallback_matches_native(self, monkeypatch):
        rng = np.random.default_rng(7)
        n_incl = 300_001
        flat = rng.integers(-4, n_incl + 3, 20_000).astype(np.int32)
        ev_n, cm_n = partition_events_host(flat, n_incl)
        import esslivedata_tpu.native as native

        monkeypatch.setattr(native, "partition_events", lambda *a, **k: None)
        ev_p, cm_p = partition_events_host(flat, n_incl)
        assert np.array_equal(cm_n, cm_p)
        c = p2.DEFAULT_CHUNK
        np.testing.assert_array_equal(
            np.sort(ev_n.reshape(-1, c), axis=1),
            np.sort(ev_p.reshape(-1, c), axis=1),
        )

    def test_skewed_distribution(self):
        # All events in one block: padding stays bounded, map collapses.
        flat = np.full(10_000, 42, np.int32)
        events, chunk_map = partition_events_host(flat, 300_001)
        assert (events == 42).sum() == 10_000
        self._check_partition(flat, 300_001, events, chunk_map, p2.DEFAULT_CHUNK)

    def test_non_pow2_bpb_numpy_path(self):
        rng = np.random.default_rng(11)
        n_incl = 200_001
        flat = rng.integers(0, n_incl, 5000).astype(np.int32)
        bpb = 51200  # pixel-aligned 512 * 100, not a power of two
        events, chunk_map = partition_events_host(flat, n_incl, bpb=bpb)
        rows = events.reshape(-1, p2.DEFAULT_CHUNK)
        pad = rows < 0
        blk = rows // np.int32(bpb)
        assert np.all(
            blk[~pad] == np.broadcast_to(chunk_map[:, None], rows.shape)[~pad]
        )
        np.testing.assert_array_equal(np.sort(events[events >= 0]), np.sort(flat))

    def test_bad_bpb_rejected(self):
        with pytest.raises(ValueError, match="128"):
            partition_events_host(np.zeros(4, np.int32), 1000, bpb=100)

    # -- edge cases: empty / all-overflow / uint16 boundary / rollover ----
    def test_empty_batch(self):
        """Zero events still emit a kernel-legal partition: the chunk
        count buckets up to the minimum shape and every slot is padding."""
        n_incl = 300_001
        events, chunk_map = partition_events_host(
            np.empty(0, np.int32), n_incl
        )
        assert chunk_map.shape[0] == p2._CHUNK_BUCKET
        assert events.shape[0] == p2._CHUNK_BUCKET * p2.DEFAULT_CHUNK
        assert np.all(events == -1)
        n_blocks = -(-n_incl // DEFAULT_BPB)
        # Padding chunks map to the last block (dump's home) — in range,
        # non-decreasing, so the kernel grid stays legal.
        assert np.all(chunk_map == n_blocks - 1)

    def test_all_events_overflow_routed_to_dump(self):
        """Every out-of-range index — negative or past the bin space —
        lands in the dump bin, none are dropped or wrapped."""
        n_incl = 2 * DEFAULT_BPB + 5
        dump = n_incl - 1
        flat = np.concatenate(
            [
                np.full(1000, -7, np.int32),
                np.full(1000, np.iinfo(np.int32).min, np.int32),
                np.full(1000, n_incl, np.int32),
                np.full(1000, np.iinfo(np.int32).max, np.int32),
            ]
        )
        events, chunk_map = partition_events_host(flat, n_incl)
        real = events[events >= 0]
        assert real.shape[0] == flat.shape[0]
        assert np.all(real == dump)
        # All in the dump's block, by construction of the routing.
        assert np.all(chunk_map == dump // DEFAULT_BPB)

    def test_uint16_wire_padding_boundary(self):
        """Compact events at the top of the largest legal block: a real
        local offset of bpb-1 must survive next to the 0xFFFF padding
        sentinel (the collision the bpb <= 0xFFFF bound exists to
        prevent)."""
        bpb = 0xFF80  # 65408 = 511 * 128: largest 128-multiple < 0xFFFF
        n_incl = 3 * bpb
        # Top offset of block 1 plus a handful of low offsets: the padded
        # tail of the same chunk then carries 0xFFFF right beside 0xFF7F.
        flat = np.asarray(
            [bpb + bpb - 1] * 3 + [bpb] * 2 + [2 * bpb + 1], np.int32
        )
        events, chunk_map = partition_events_host(
            flat, n_incl, bpb=bpb, compact=True
        )
        assert events.dtype == np.uint16
        real = events[events != 0xFFFF]
        # Reconstruct globals from block base + local offset.
        rows = events.reshape(-1, p2.DEFAULT_CHUNK)
        mask = rows != 0xFFFF
        blocks = np.broadcast_to(chunk_map[:, None], rows.shape)
        globals_ = rows.astype(np.int64) + blocks.astype(np.int64) * bpb
        np.testing.assert_array_equal(
            np.sort(globals_[mask]), np.sort(flat.astype(np.int64))
        )
        assert real.max() == bpb - 1  # boundary offset intact, not padding

    @pytest.mark.parametrize("extra_blocks", [0, 1])
    def test_chunk_bucket_rollover(self, extra_blocks):
        """Used-chunk counts at exactly _CHUNK_BUCKET and one past it:
        the padded chunk count must step to the next bucket multiple,
        never truncate a used chunk."""
        bpb = 128
        chunk = 8
        n_used_blocks = p2._CHUNK_BUCKET + extra_blocks
        n_blocks = n_used_blocks + 3
        n_incl = n_blocks * bpb
        # One event per used block -> one (partial) chunk per used block.
        flat = (np.arange(n_used_blocks, dtype=np.int32) * bpb).astype(
            np.int32
        )
        events, chunk_map = partition_events_host(
            flat, n_incl, bpb=bpb, chunk=chunk
        )
        used = n_used_blocks
        expected_padded = p2.bucketed_chunks(used)
        assert expected_padded == (
            p2._CHUNK_BUCKET if extra_blocks == 0 else 2 * p2._CHUNK_BUCKET
        )
        assert chunk_map.shape[0] == expected_padded
        assert events.shape[0] == expected_padded * chunk
        # Every real event survived the rollover.
        np.testing.assert_array_equal(
            np.sort(events[events >= 0]), np.sort(flat)
        )


class TestKernel:
    def test_parity_and_unvisited_blocks_preserved(self):
        rng = np.random.default_rng(3)
        n_incl = 4 * DEFAULT_BPB + 17
        padded = padded_bins(n_incl)
        # Events only touch the first two blocks: the rest must keep
        # their prior contents bit-for-bit (in-place aliasing).
        flat = rng.integers(0, 2 * DEFAULT_BPB, 9000).astype(np.int32)
        events, chunk_map = partition_events_host(flat, n_incl)
        base = rng.random(padded).astype(np.float32)
        out = np.asarray(
            scatter_add_pallas2d(np.array(base), events, chunk_map)
        )
        # Visited blocks: counts accumulate chunk-wise, so a float base
        # differs from any single-order reference at the ULP level only.
        ref = base + np.bincount(flat, minlength=padded).astype(np.float32)
        np.testing.assert_allclose(out, ref, rtol=1e-6)
        # Unvisited blocks are preserved bit-for-bit (in-place aliasing).
        np.testing.assert_array_equal(
            out[2 * DEFAULT_BPB :], base[2 * DEFAULT_BPB :]
        )

    def test_counts_exact_on_integer_state(self):
        # The real accumulator holds counts: integer-valued float32, where
        # every partial sum is exact regardless of accumulation order.
        rng = np.random.default_rng(4)
        n_incl = 3 * DEFAULT_BPB + 1
        padded = padded_bins(n_incl)
        flat = rng.integers(0, n_incl, 40_000).astype(np.int32)
        events, chunk_map = partition_events_host(flat, n_incl)
        base = rng.integers(0, 1000, padded).astype(np.float32)
        out = np.asarray(
            scatter_add_pallas2d(np.array(base), events, chunk_map)
        )
        ref = base + np.bincount(flat, minlength=padded).astype(np.float32)
        np.testing.assert_array_equal(out, ref)

    def test_update_scale(self):
        flat = np.array([0, 0, 5, DEFAULT_BPB + 3], np.int32)
        n_incl = 2 * DEFAULT_BPB
        events, chunk_map = partition_events_host(flat, n_incl)
        out = np.asarray(
            scatter_add_pallas2d(
                np.zeros(padded_bins(n_incl), np.float32),
                events,
                chunk_map,
                upd=2.5,
            )
        )
        assert out[0] == 5.0 and out[5] == 2.5 and out[DEFAULT_BPB + 3] == 2.5
        assert out.sum() == 10.0


@pytest.fixture
def small_blocks(monkeypatch):
    """The device-partitioned count at test size: blocks of 2 048 bins,
    one block up to 2 048, work items of 256 keys (the kernel's shapes,
    in interpret mode)."""
    monkeypatch.setattr(p2, "COUNT_BPB", 2048)
    monkeypatch.setattr(p2, "COUNT_CHUNK", 256)
    monkeypatch.setattr(p2, "MAX_MXU_BINS", 2048)
    return 2048


def _count_on_device(window, flat, n_bins, upd=1.0):
    import jax.numpy as jnp

    bpb = p2.count_layout(n_bins)[0]
    part = p2.partition_on_device(jnp.asarray(flat, jnp.int32), n_bins, bpb=bpb)
    return np.asarray(
        p2.count_partitioned(jnp.asarray(window), *part, bpb=bpb, upd=upd)
    )


class TestDevicePartitionedCount:
    """The chip's own partition (key sort + work items) in front of the
    block kernel: what ``EventHistogrammer(method="mxu")`` runs."""

    @pytest.mark.parametrize(
        ("n_bins", "blocks"),
        [(1000, 1), (2047, 1), (2048, 2), (20_000, 10)],
        ids=["one_block", "one_block_full", "dump_slot_past_the_block", "many_blocks"],
    )
    def test_layout(self, small_blocks, n_bins, blocks):
        bpb, n_state = p2.count_layout(n_bins)
        assert n_state == blocks * bpb >= n_bins + 1
        assert bpb % 1024 == 0 and bpb <= small_blocks

    @pytest.mark.parametrize("n_bins", [1000, 20_000], ids=["one_block", "many_blocks"])
    @pytest.mark.parametrize("upd", [1.0, 0.25])
    def test_counts_match_bincount(self, small_blocks, n_bins, upd):
        """Dropped slots (the dump slot, negatives, past the bins) count
        nowhere, the dump slot and the padding tail included; a touched
        bin reads its count times the update, exactly."""
        rng = np.random.default_rng(n_bins)
        flat = rng.integers(-5, n_bins + 5, 5000).astype(np.int32)
        flat[rng.random(5000) < 0.234] = n_bins
        n_state = p2.count_layout(n_bins)[1]
        out = _count_on_device(np.zeros(n_state, np.float32), flat, n_bins, upd)
        ok = flat[(flat >= 0) & (flat < n_bins)]
        np.testing.assert_array_equal(
            out[:n_bins], np.bincount(ok, minlength=n_bins) * upd
        )
        assert not out[n_bins:].any()

    def test_matches_the_scatter_on_block_edges(self, small_blocks):
        """Keys on both sides of every block edge, and the first and the
        last bin, against XLA's scatter into the same window."""
        import jax.numpy as jnp

        n_bins = 20_000
        bpb, n_state = p2.count_layout(n_bins)
        edges = np.arange(bpb, n_bins, bpb)
        flat = np.concatenate(
            [edges - 1, edges, edges, [0, 0, n_bins - 1, n_bins]]
        ).astype(np.int32)
        base = np.random.default_rng(1).integers(0, 9, n_state).astype(np.float32)
        out = _count_on_device(base, flat, n_bins)
        want = np.array(
            jnp.asarray(base).at[jnp.asarray(flat)].add(1.0, mode="drop")
        )
        want[n_bins] = base[n_bins]  # the scatter counts the dump slot
        np.testing.assert_array_equal(out, want)

    @pytest.mark.parametrize("n", [0, 3000], ids=["empty", "all_padding"])
    @pytest.mark.parametrize("n_bins", [1000, 20_000], ids=["one_block", "many_blocks"])
    def test_a_batch_with_nothing_to_count_leaves_the_window(self, small_blocks, n, n_bins):
        """No work item: every block the grid visits is loaded and
        written back as it was (in place), none is counted into."""
        n_state = p2.count_layout(n_bins)[1]
        base = np.random.default_rng(2).random(n_state).astype(np.float32)
        flat = np.full(n, n_bins, np.int32)
        np.testing.assert_array_equal(_count_on_device(base, flat, n_bins), base)

    def test_untouched_blocks_are_not_rewritten(self, small_blocks):
        n_bins = 20_000
        n_state = p2.count_layout(n_bins)[1]
        base = np.random.default_rng(3).integers(0, 50, n_state).astype(np.float32)
        flat = np.random.default_rng(4).integers(3 * 2048, 5 * 2048, 4000).astype(np.int32)
        out = _count_on_device(base, flat, n_bins)
        np.testing.assert_array_equal(
            out, base + np.bincount(flat, minlength=n_state)
        )

    @pytest.mark.parametrize("n_bins", [1000, 20_000], ids=["one_block", "many_blocks"])
    def test_work_items_cover_each_chunk_and_block_once(self, small_blocks, n_bins):
        """Per item a (chunk, block) that one of the chunk's keys lies
        in, each once, blocks in order (the output's revisiting rule);
        dropped keys at the end make no item, and skipped steps repeat
        the last item. One block: no sort, every chunk that holds a key
        is an item."""
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        flat = rng.integers(0, n_bins, 3000).astype(np.int32)
        flat[2500:] = n_bins  # a bucket's padding: at its tail
        bpb = p2.count_layout(n_bins)[0]
        keys, chunk_of, block_of, n_items = map(
            np.asarray, p2.partition_on_device(jnp.asarray(flat), n_bins, bpb=bpb)
        )
        n_items = int(n_items[0])
        items = set(zip(chunk_of[:n_items], block_of[:n_items]))
        assert len(items) == n_items
        valid = keys != np.iinfo(np.int32).max
        wanted = set(zip((np.flatnonzero(valid) // 256), keys[valid] // bpb))
        assert wanted == items
        assert (np.diff(block_of) >= 0).all()
        assert (chunk_of[n_items:] == chunk_of[n_items - 1]).all()
        assert (block_of[n_items:] == block_of[n_items - 1]).all()
        assert sorted(keys[valid]) == sorted(flat[:2500])


class TestHistogrammerPallas2d:
    def _run(self, method, batches, toa_edges=None, **kw):
        if toa_edges is None:
            toa_edges = np.linspace(0.0, 71.0, 101)
        h = EventHistogrammer(toa_edges=toa_edges, **kw, method=method)
        s = h.init_state()
        for b in batches:
            s = h.step_batch(s, b)
        return h, s

    def _batches(self, n_screen, n=20_000, k=3):
        rng = np.random.default_rng(n_screen)
        return [
            EventBatch.from_arrays(
                rng.integers(-2, n_screen + 2, n).astype(np.int32),
                rng.uniform(-1.0, 73.0, n).astype(np.float32),
            )
            for _ in range(k)
        ]

    @pytest.mark.parametrize("n_screen", [700, 5000])
    def test_views_parity_with_scatter(self, n_screen):
        batches = self._batches(n_screen)
        hs, ss = self._run("scatter", batches, n_screen=n_screen)
        hp, sp = self._run("pallas2d", batches, n_screen=n_screen)
        np.testing.assert_allclose(hs.read(ss)[0], hp.read(sp)[0])
        np.testing.assert_allclose(hs.read(ss)[1], hp.read(sp)[1])

    def test_dump_bin_parity(self):
        n_screen = 700
        batches = self._batches(n_screen)
        hs, ss = self._run("scatter", batches, n_screen=n_screen)
        hp, sp = self._run("pallas2d", batches, n_screen=n_screen)
        dump = n_screen * 100
        assert float(np.asarray(ss.window)[-1]) == float(
            np.asarray(sp.window)[dump]
        )

    def test_decay_mode_parity(self):
        n_screen = 700
        batches = self._batches(n_screen)
        hs, ss = self._run("scatter", batches, n_screen=n_screen, decay=0.9)
        hp, sp = self._run("pallas2d", batches, n_screen=n_screen, decay=0.9)
        np.testing.assert_allclose(
            hs.read(ss)[1], hp.read(sp)[1], rtol=1e-6
        )

    def test_fold_and_clear(self):
        n_screen = 700
        batches = self._batches(n_screen)
        hp, sp = self._run("pallas2d", batches, n_screen=n_screen)
        cum_before = hp.read(sp)[0]
        folded = hp.clear_window(sp)  # donates sp
        cum, win = hp.read(folded)
        assert win.sum() == 0
        np.testing.assert_allclose(cum, cum_before)
        assert hp.read(hp.clear(folded))[0].sum() == 0

    def test_step_flat_path(self):
        # step_flat partitions internally (non-fused path).
        n_screen = 700
        rng = np.random.default_rng(0)
        flat = rng.integers(-3, n_screen * 100 + 5, 10_000).astype(np.int32)
        hs = EventHistogrammer(
            toa_edges=np.linspace(0, 71.0, 101), n_screen=n_screen
        )
        hp = EventHistogrammer(
            toa_edges=np.linspace(0, 71.0, 101),
            n_screen=n_screen,
            method="pallas2d",
        )
        ss = hs.step_flat(hs.init_state(), flat)
        sp = hp.step_flat(hp.init_state(), flat)
        np.testing.assert_allclose(hs.read(ss)[0], hp.read(sp)[0])

    def test_single_replica_lut(self):
        n_screen, n_pix = 64, 200
        rng = np.random.default_rng(1)
        lut = rng.integers(-1, n_screen, n_pix).astype(np.int32)
        batches = [
            EventBatch.from_arrays(
                rng.integers(-2, n_pix + 2, 5000).astype(np.int32),
                rng.uniform(0, 71.0, 5000).astype(np.float32),
            )
        ]
        hs, ss = self._run(
            "scatter", batches, n_screen=n_screen, pixel_lut=lut
        )
        hp, sp = self._run(
            "pallas2d", batches, n_screen=n_screen, pixel_lut=lut
        )
        np.testing.assert_allclose(hs.read(ss)[0], hp.read(sp)[0])

    def test_weighted_config_rejected(self):
        with pytest.raises(ValueError, match="host-flattenable"):
            EventHistogrammer(
                toa_edges=np.linspace(0, 71.0, 101),
                n_screen=16,
                pixel_weights=np.ones(16, np.float32),
                method="pallas2d",
            )

    def test_int8_precision_exact_parity(self):
        # int8 one-hots with int32 accumulation are exact for counts —
        # and run at twice the bf16 MXU rate on v5e.
        n_screen = 900
        batches = self._batches(n_screen)
        hs, ss = self._run("scatter", batches, n_screen=n_screen)
        h8, s8 = self._run(
            "pallas2d",
            batches,
            n_screen=n_screen,
            pallas2d_precision="int8",
        )
        np.testing.assert_array_equal(hs.read(ss)[0], h8.read(s8)[0])

    def test_invalid_precision_rejected(self):
        with pytest.raises(ValueError, match="precision"):
            EventHistogrammer(
                toa_edges=np.linspace(0.0, 71.0, 101),
                n_screen=16,
                method="pallas2d",
                pallas2d_precision="fp8",
            )

    @pytest.mark.parametrize(
        ("budget", "chunk"), [(32768, 256), (16384, 1024)]
    )
    def test_tuning_knobs_keep_parity(self, budget, chunk):
        # The hardware-tuning knobs (bench --pallas2d-budget/-chunk)
        # change layout only, never counts.
        n_screen = 700
        batches = self._batches(n_screen)
        hs, ss = self._run("scatter", batches, n_screen=n_screen)
        hp = EventHistogrammer(
            toa_edges=np.linspace(0.0, 71.0, 101),
            n_screen=n_screen,
            method="pallas2d",
            pallas2d_budget=budget,
            pallas2d_chunk=chunk,
        )
        assert hp._bpb <= budget
        sp = hp.init_state()
        for b in batches:
            sp = hp.step_batch(sp, b)
        np.testing.assert_allclose(hs.read(ss)[0], hp.read(sp)[0])

    def test_invalid_tuning_knobs_rejected(self):
        edges = np.linspace(0.0, 71.0, 101)  # n_toa=100
        # budget 96: no 2**k * 100 fits and 96 is not a 128 multiple.
        with pytest.raises(ValueError, match="power-of-two"):
            EventHistogrammer(
                toa_edges=edges,
                n_screen=16,
                method="pallas2d",
                pallas2d_budget=96,
            )
        for chunk in (0, -100, 200):
            with pytest.raises(ValueError, match="multiple of 128"):
                EventHistogrammer(
                    toa_edges=edges,
                    n_screen=16,
                    method="pallas2d",
                    pallas2d_chunk=chunk,
                )

    @pytest.mark.parametrize(
        ("dump_method", "restore_method"),
        [("scatter", "pallas2d"), ("pallas2d", "scatter")],
    )
    def test_snapshot_restores_across_method_switch(
        self, dump_method, restore_method
    ):
        """An operator switching histogram kernels between runs must not
        lose a recovery snapshot: the codec adapts the block-padding
        layout difference (ADR 0107 + round-5 pallas2d)."""
        n_screen = 700
        batches = self._batches(n_screen)
        hd, sd = self._run(dump_method, batches, n_screen=n_screen)
        cum_before = hd.read(sd)[0]
        arrays = EventHistogrammer.dump_state_arrays(sd)

        hr = EventHistogrammer(
            toa_edges=np.linspace(0.0, 71.0, 101),
            n_screen=n_screen,
            method=restore_method,
        )
        restored = hr.restore_state_arrays(hr.init_state(), arrays)
        assert restored is not None, "cross-layout snapshot discarded"
        np.testing.assert_allclose(hr.read(restored)[0], cum_before)
        # And the restored state keeps accumulating on the new kernel.
        after = hr.step_batch(restored, batches[0])
        assert hr.read(after)[0].sum() > cum_before.sum()

    def test_snapshot_with_counts_in_tail_rejected(self):
        # A longer array whose tail carries counts is NOT padding —
        # adopting it would silently drop data.
        h = EventHistogrammer(
            toa_edges=np.linspace(0.0, 71.0, 101), n_screen=700
        )
        want = h.init_state().folded.shape[0]
        bad = {
            "folded": np.zeros(want + 128, np.float32),
            "window": np.zeros(want + 128, np.float32),
        }
        bad["folded"][-1] = 5.0
        assert h.restore_state_arrays(h.init_state(), bad) is None

    def test_nonuniform_edges(self):
        # Non-uniform edges skip the fused native pass but keep parity.
        edges = np.concatenate([[0.0], np.cumsum(np.linspace(0.5, 2.0, 50))])
        n_screen = 300
        rng = np.random.default_rng(9)
        batches = [
            EventBatch.from_arrays(
                rng.integers(0, n_screen, 8000).astype(np.int32),
                rng.uniform(0, edges[-1] + 1, 8000).astype(np.float32),
            )
        ]
        hs, ss = self._run("scatter", batches, toa_edges=edges, n_screen=n_screen)
        hp, sp = self._run("pallas2d", batches, toa_edges=edges, n_screen=n_screen)
        np.testing.assert_allclose(hs.read(ss)[0], hp.read(sp)[0])


class TestCompactWire:
    """uint16 block-local wire (2 B/event): same partition + kernel
    semantics at half the host->device bytes."""

    N_INCL = 300_001
    BPB = 51_200  # headline-style pixel-aligned block, < 0xFFFF

    def _events(self, n=40_000, seed=3):
        rng = np.random.default_rng(seed)
        # Includes out-of-range negatives and overshoots: dump-routed.
        return rng.integers(-50, self.N_INCL + 50, n).astype(np.int32)

    def test_compact_partition_matches_int32_partition(self):
        flat = self._events()
        e32, m32 = partition_events_host(
            flat, self.N_INCL, bpb=self.BPB, chunk=512
        )
        e16, m16 = partition_events_host(
            flat, self.N_INCL, bpb=self.BPB, chunk=512, compact=True
        )
        assert e16.dtype == np.uint16
        np.testing.assert_array_equal(m16, m32)
        # Reconstruct global indices from the local wire; padding maps
        # -1 <-> 0xFFFF.
        blk = np.repeat(m16, 512).astype(np.int64)
        pad16 = e16 == 0xFFFF
        np.testing.assert_array_equal(pad16, e32 < 0)
        recon = e16.astype(np.int64) + blk * self.BPB
        np.testing.assert_array_equal(recon[~pad16], e32[~pad16])

    def test_numpy_fallback_compact_matches_native(self, monkeypatch):
        flat = self._events(seed=4)
        native = partition_events_host(
            flat, self.N_INCL, bpb=self.BPB, chunk=512, compact=True
        )
        import esslivedata_tpu.native as nat

        monkeypatch.setattr(nat, "partition_events", None)
        fallback = partition_events_host(
            flat, self.N_INCL, bpb=self.BPB, chunk=512, compact=True
        )
        assert fallback[0].dtype == np.uint16
        # Same chunk map; same multiset of (block, local) events.
        np.testing.assert_array_equal(native[1], fallback[1])

        def multiset(ev, mp):
            blk = np.repeat(mp, 512).astype(np.int64)
            keep = ev != 0xFFFF
            return np.sort(ev[keep].astype(np.int64) + blk[keep] * self.BPB)
        np.testing.assert_array_equal(
            multiset(*native), multiset(*fallback)
        )

    def test_kernel_parity_compact_vs_int32(self):
        import jax.numpy as jnp

        flat = self._events(seed=5)
        pb = padded_bins(self.N_INCL, self.BPB)
        e32, m32 = partition_events_host(
            flat, self.N_INCL, bpb=self.BPB, chunk=512
        )
        e16, m16 = partition_events_host(
            flat, self.N_INCL, bpb=self.BPB, chunk=512, compact=True
        )
        out32 = scatter_add_pallas2d(
            jnp.zeros(pb, jnp.float32), e32, m32, bpb=self.BPB
        )
        out16 = scatter_add_pallas2d(
            jnp.zeros(pb, jnp.float32), e16, m16, bpb=self.BPB
        )
        np.testing.assert_array_equal(
            np.asarray(out32), np.asarray(out16)
        )

    def test_compact_rejected_for_oversize_bpb(self):
        with pytest.raises(ValueError, match="0xFFFF|65535|<="):
            partition_events_host(
                self._events(), self.N_INCL, bpb=65536, compact=True
            )

    def test_histogrammer_autocompacts_when_blocks_fit(self):
        h = EventHistogrammer(
            toa_edges=np.linspace(0.0, 71.0, 101),
            n_screen=3000,
            method="pallas2d",
        )
        assert h._p2_compact is (h._bpb <= 0xFFFF)
        events, _ = h.flatten_partition_host(
            np.zeros(64, np.int32), np.full(64, 5.0, np.float32)
        )
        if h._p2_compact:
            assert events.dtype == np.uint16

    def test_histogrammer_compact_parity_with_scatter(self):
        rng = np.random.default_rng(11)
        n_screen = 3000
        edges = np.linspace(0.0, 71.0, 101)
        batch = EventBatch.from_arrays(
            rng.integers(0, n_screen, 30_000).astype(np.int32),
            rng.uniform(0.0, 72.0, 30_000).astype(np.float32),
        )
        hs = EventHistogrammer(
            toa_edges=edges, n_screen=n_screen, method="scatter"
        )
        hp = EventHistogrammer(
            toa_edges=edges, n_screen=n_screen, method="pallas2d"
        )
        assert hp._p2_compact
        ss = hs.step_batch(hs.init_state(), batch)
        sp = hp.step_batch(hp.init_state(), batch)
        np.testing.assert_array_equal(
            np.asarray(hs.read(ss)[0]), np.asarray(hp.read(sp)[0])
        )

    def test_compact_power_of_two_bpb_shift_path(self):
        """Power-of-two bpb takes the native shift path; compact output
        must agree with the int32 wire there too."""
        bpb = 32_768  # pow2, <= 0xFFFF, multiple of 128
        flat = self._events(seed=6)
        e32, m32 = partition_events_host(
            flat, self.N_INCL, bpb=bpb, chunk=512
        )
        e16, m16 = partition_events_host(
            flat, self.N_INCL, bpb=bpb, chunk=512, compact=True
        )
        np.testing.assert_array_equal(m16, m32)
        blk = np.repeat(m16, 512).astype(np.int64)
        pad = e16 == 0xFFFF
        np.testing.assert_array_equal(pad, e32 < 0)
        np.testing.assert_array_equal(
            e16.astype(np.int64)[~pad] + blk[~pad] * bpb, e32[~pad]
        )


class TestCompactWireProperty:
    """Randomized partition sweep: the compact wire reconstructs the
    int32 wire exactly for every (bpb, chunk, distribution) combination
    the constructor accepts."""

    @pytest.mark.parametrize("seed", range(6))
    def test_random_configs_reconstruct(self, seed):
        rng = np.random.default_rng(seed)
        bpb = int(rng.choice([128, 1024, 12800, 32768, 51200, 65408]))
        chunk = int(rng.choice([128, 256, 512, 1024]))
        n_incl = int(rng.integers(1, 8) * bpb + rng.integers(1, bpb))
        n = int(rng.integers(0, 30_000))
        flat = rng.integers(-10, n_incl + 10, n).astype(np.int32)
        e32, m32 = partition_events_host(
            flat, n_incl, bpb=bpb, chunk=chunk
        )
        e16, m16 = partition_events_host(
            flat, n_incl, bpb=bpb, chunk=chunk, compact=True
        )
        assert e16.dtype == np.uint16
        np.testing.assert_array_equal(m16, m32)
        blk = np.repeat(m16, chunk).astype(np.int64)
        pad = e16 == 0xFFFF
        np.testing.assert_array_equal(pad, e32 < 0)
        np.testing.assert_array_equal(
            e16.astype(np.int64)[~pad] + blk[~pad] * bpb, e32[~pad]
        )

"""ResultCache (serving/result_cache.py, ADR 0117): epoch/ring/locking.

The satellite fix this PR carries: the cache snapshot must follow the
ONE-acquisition discipline — a scraping subscriber can never pair a
frame with the wrong epoch tag.
The lock hammer at the bottom pins that under a real writer/reader
race.
"""

from __future__ import annotations

import struct
import threading

import pytest

from esslivedata_tpu.serving import ResultCache


class TestEpochSemantics:
    def test_same_token_keeps_epoch_and_advances_seq(self):
        cache = ResultCache()
        first = cache.put("s", b"f0", token=("layout", 0))
        second = cache.put("s", b"f1", token=("layout", 0))
        assert (first.epoch, first.seq) == (0, 0)
        assert (second.epoch, second.seq) == (0, 1)

    def test_token_change_bumps_epoch_and_resets_ring(self):
        cache = ResultCache(ring=4)
        cache.put("s", b"f0", token=("layout-a", 0))
        cache.put("s", b"f1", token=("layout-a", 0))
        bumped = cache.put("s", b"f2", token=("layout-b", 0))
        assert bumped.epoch == 1
        # Frames across a generation boundary must not look contiguous.
        assert [c.frame for c in cache.recent("s")] == [b"f2"]

    def test_state_epoch_component_bumps_too(self):
        cache = ResultCache()
        cache.put("s", b"f0", token=(0, "layout"))
        bumped = cache.put("s", b"f0", token=(1, "layout"))
        assert bumped.epoch == 1

    def test_streams_are_independent(self):
        cache = ResultCache()
        cache.put("a", b"x", token=1)
        cache.put("a", b"y", token=2)  # epoch 1
        first_b = cache.put("b", b"z", token=1)
        assert first_b.epoch == 0 and first_b.seq == 0


class TestRingAndIndex:
    def test_ring_is_bounded_oldest_dropped(self):
        cache = ResultCache(ring=3)
        for i in range(6):
            cache.put("s", bytes([i]), token="t")
        assert [c.frame for c in cache.recent("s")] == [
            b"\x03",
            b"\x04",
            b"\x05",
        ]
        assert cache.latest("s").frame == b"\x05"
        assert cache.latest("s").seq == 5

    def test_latest_none_for_unknown_stream(self):
        assert ResultCache().latest("nope") is None

    def test_streams_index_lists_latest(self):
        cache = ResultCache()
        cache.put("a", b"aa", token=1)
        cache.put("b", b"bb", token=1)
        index = cache.streams()
        assert set(index) == {"a", "b"}
        assert index["a"].frame == b"aa"

    def test_invalidate_drops_one_or_all(self):
        cache = ResultCache()
        cache.put("a", b"aa", token=1)
        cache.put("b", b"bb", token=1)
        cache.invalidate("a")
        assert cache.latest("a") is None
        assert cache.latest("b") is not None
        cache.invalidate()
        assert cache.streams() == {}

    def test_ring_must_hold_at_least_one(self):
        with pytest.raises(ValueError):
            ResultCache(ring=0)


class TestEpochFrameCoherence:
    def test_lock_hammer_frame_never_pairs_with_wrong_epoch(self):
        """A writer bumps the token (→ epoch) on every put, encoding
        the expected epoch INSIDE the frame; concurrent readers assert
        every snapshot's frame decodes to exactly its epoch tag. The
        pre-fix shape (latest() reading frame and epoch in separate
        acquisitions) fails this within a few thousand iterations."""
        cache = ResultCache(ring=2)
        stop = threading.Event()
        errors: list[str] = []

        def writer():
            i = 0
            while not stop.is_set():
                # token == i, changes every put → epoch == i.
                cache.put("s", struct.pack("<I", i), token=i)
                i += 1

        def reader():
            while not stop.is_set():
                cached = cache.latest("s")
                if cached is None:
                    continue
                (embedded,) = struct.unpack("<I", cached.frame)
                if embedded != cached.epoch:
                    errors.append(
                        f"frame says epoch {embedded}, tag says "
                        f"{cached.epoch}"
                    )
                    return

        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader) for _ in range(3)
        ]
        for t in threads:
            t.start()
        try:
            import time

            time.sleep(0.5)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5.0)
        assert not errors, errors[0]

    def test_seq_epoch_pairing_under_mixed_tokens(self):
        """Same hammer, alternating token flips mid-stream: seq resets
        never tear against the epoch (each put's CachedFrame return and
        later latest() reads agree)."""
        cache = ResultCache(ring=4)
        stop = threading.Event()
        errors: list[str] = []

        def writer():
            i = 0
            while not stop.is_set():
                token = i // 7  # epoch bumps every 7 puts
                cached = cache.put(
                    "s", struct.pack("<II", token, i), token=token
                )
                if cached.epoch != token:
                    errors.append(
                        f"put returned epoch {cached.epoch} for token "
                        f"{token}"
                    )
                    return
                i += 1

        def reader():
            while not stop.is_set():
                cached = cache.latest("s")
                if cached is None:
                    continue
                token, _i = struct.unpack("<II", cached.frame)
                if token != cached.epoch:
                    errors.append(
                        f"frame token {token} != epoch {cached.epoch}"
                    )
                    return

        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader) for _ in range(2)
        ]
        for t in threads:
            t.start()
        try:
            import time

            time.sleep(0.3)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5.0)
        assert not errors, errors[0]

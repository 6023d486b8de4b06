"""Delta codec (serving/delta.py, ADR 0117): exact round-trip.

The codec's one promise: a subscriber applying keyframes and deltas in
order reconstructs every tick's frame BYTE-IDENTICALLY. These tests pin
the sparse/dense crossover, the epoch discipline (layout swap /
``state_lost`` → keyframe), the decoder's continuity rules (stale
deltas idempotent, gaps loud), and property-style round-trips over
randomized mutation patterns.
"""

from __future__ import annotations

import struct

import numpy as np
import pytest

from esslivedata_tpu.serving.delta import (
    HEADER_SIZE,
    DeltaDecoder,
    DeltaEncoder,
    DeltaError,
    decode_header,
    encode_delta,
    encode_keyframe,
)


def mutate(rng, frame: bytes, n_sites: int) -> bytes:
    out = bytearray(frame)
    for i in rng.integers(0, len(out), n_sites):
        out[i] = (out[i] + 1) % 256
    return bytes(out)


class TestBlobFormat:
    def test_keyframe_header_and_payload(self):
        blob = encode_keyframe(b"abcdef", epoch=3, seq=7)
        header = decode_header(blob)
        assert header.keyframe
        assert header.epoch == 3
        assert header.seq == 7
        assert header.frame_len == 6
        assert blob[HEADER_SIZE:] == b"abcdef"

    def test_bad_magic_and_truncation_raise(self):
        with pytest.raises(DeltaError):
            decode_header(b"XX" + b"\x00" * 20)
        with pytest.raises(DeltaError):
            decode_header(b"LD\x01")

    def test_unsupported_version_raises(self):
        blob = bytearray(encode_keyframe(b"x", epoch=0, seq=0))
        blob[2] = 99
        with pytest.raises(DeltaError):
            decode_header(bytes(blob))


class TestRoundTrip:
    def test_sparse_mutations_round_trip_byte_identical(self):
        rng = np.random.default_rng(1)
        frame = rng.integers(0, 256, 40_000).astype(np.uint8).tobytes()
        encoder, decoder = DeltaEncoder(), DeltaDecoder()
        assert decoder.apply(encoder.encode(frame, epoch=0, seq=0)) == frame
        for seq in range(1, 30):
            frame = mutate(rng, frame, int(rng.integers(1, 60)))
            blob = encoder.encode(frame, epoch=0, seq=seq)
            header = decode_header(blob)
            assert not header.keyframe
            assert len(blob) < len(frame)
            assert decoder.apply(blob) == frame

    def test_identical_frame_is_a_tiny_delta(self):
        frame = bytes(10_000)
        encoder, decoder = DeltaEncoder(), DeltaDecoder()
        decoder.apply(encoder.encode(frame, epoch=0, seq=0))
        blob = encoder.encode(frame, epoch=0, seq=1)
        assert not decode_header(blob).keyframe
        assert len(blob) == HEADER_SIZE + 4  # zero runs
        assert decoder.apply(blob) == frame

    def test_dense_fallback_emits_keyframe(self):
        rng = np.random.default_rng(2)
        a = rng.integers(0, 256, 5000).astype(np.uint8).tobytes()
        b = rng.integers(0, 256, 5000).astype(np.uint8).tobytes()
        blob = encode_delta(a, b, epoch=0, seq=1)
        assert decode_header(blob).keyframe
        # A delta blob is never larger than the keyframe for the tick.
        assert len(blob) == HEADER_SIZE + len(b)

    def test_length_change_forces_keyframe(self):
        blob = encode_delta(b"short", b"rather longer", epoch=0, seq=1)
        assert decode_header(blob).keyframe

    def test_crossover_scan_never_exceeds_keyframe_size(self):
        """Property: across the sparse→dense spectrum the emitted blob
        round-trips exactly and never beats the keyframe bound."""
        rng = np.random.default_rng(3)
        base = rng.integers(0, 256, 8192).astype(np.uint8).tobytes()
        for n_sites in (0, 1, 8, 64, 512, 4096, 8192):
            cur = mutate(rng, base, n_sites) if n_sites else base
            blob = encode_delta(base, cur, epoch=0, seq=1)
            assert len(blob) <= HEADER_SIZE + len(cur)
            decoder = DeltaDecoder()
            decoder.apply(encode_keyframe(base, epoch=0, seq=0))
            assert decoder.apply(blob) == cur

    def test_randomized_stream_round_trip(self):
        """Property-style: random walk of mutation densities, epoch
        bumps and frame-length changes — decoder output equals the
        published frame at every step."""
        rng = np.random.default_rng(4)
        encoder, decoder = DeltaEncoder(), DeltaDecoder()
        frame = rng.integers(0, 256, 2048).astype(np.uint8).tobytes()
        epoch = 0
        for seq in range(60):
            roll = rng.random()
            if roll < 0.1:
                epoch += 1  # generation change
            if roll < 0.05:
                frame = (
                    rng.integers(0, 256, int(rng.integers(512, 4096)))
                    .astype(np.uint8)
                    .tobytes()
                )
            else:
                frame = mutate(rng, frame, int(rng.integers(0, 300)))
            blob = encoder.encode(frame, epoch=epoch, seq=seq)
            assert decoder.apply(blob) == frame
            assert decoder.epoch == epoch


class TestEpochDiscipline:
    def test_epoch_bump_forces_keyframe(self):
        encoder = DeltaEncoder()
        frame = bytes(1000)
        encoder.encode(frame, epoch=0, seq=0)
        # Same bytes, new epoch (state_lost reset to zeros): keyframe.
        blob = encoder.encode(frame, epoch=1, seq=1)
        assert decode_header(blob).keyframe
        assert decode_header(blob).epoch == 1

    def test_delta_across_epochs_rejected_by_decoder(self):
        a, b = bytes(1000), b"\x01" + bytes(999)
        decoder = DeltaDecoder()
        decoder.apply(encode_keyframe(a, epoch=0, seq=0))
        blob = encode_delta(a, b, epoch=1, seq=1)
        assert not decode_header(blob).keyframe
        with pytest.raises(DeltaError, match="epoch"):
            decoder.apply(blob)

    def test_encoder_keyframe_reemits_current_state(self):
        encoder = DeltaEncoder()
        assert encoder.keyframe() is None
        rng = np.random.default_rng(5)
        frame = rng.integers(0, 256, 500).astype(np.uint8).tobytes()
        encoder.encode(frame, epoch=2, seq=9)
        blob = encoder.keyframe()
        header = decode_header(blob)
        assert header.keyframe and header.epoch == 2 and header.seq == 9
        decoder = DeltaDecoder()
        assert decoder.apply(blob) == frame


class TestDecoderContinuity:
    def _pair(self):
        rng = np.random.default_rng(6)
        a = rng.integers(0, 256, 2000).astype(np.uint8).tobytes()
        b = mutate(rng, a, 10)
        c = mutate(rng, b, 10)
        return a, b, c

    def test_delta_before_keyframe_raises(self):
        a, b, _c = self._pair()
        with pytest.raises(DeltaError, match="before any keyframe"):
            DeltaDecoder().apply(encode_delta(a, b, epoch=0, seq=1))

    def test_stale_delta_is_idempotent_noop(self):
        """The attach race: keyframe seq N from the cache, then the
        in-flight fan-out's delta seq N — held frame unchanged."""
        a, b, _c = self._pair()
        decoder = DeltaDecoder()
        decoder.apply(encode_keyframe(b, epoch=0, seq=1))
        out = decoder.apply(encode_delta(a, b, epoch=0, seq=1))
        assert out == b
        assert decoder.seq == 1

    def test_a_length_change_rebases_and_its_replay_is_a_noop(self):
        """8 -> 9 bytes in one epoch: the encoder emits a keyframe (no
        delta can bridge two lengths), the decoder rebases on it, and
        the same blob delivered again returns the held frame. A delta
        of the NEXT tick against a decoder that never saw that keyframe
        is refused: the decoder cannot patch a frame it does not hold."""
        old, new = b"8 bytes.", b"9 bytes.."
        encoder, decoder, late = DeltaEncoder(), DeltaDecoder(), DeltaDecoder()
        first = encoder.encode(old, epoch=0, seq=0)
        decoder.apply(first)
        late.apply(first)
        blob = encoder.encode(new, epoch=0, seq=1)
        assert decode_header(blob).keyframe
        assert decoder.apply(blob) == new
        assert decoder.apply(blob) == new  # the replay
        assert (decoder.epoch, decoder.seq) == (0, 1)
        unchanged = encoder.encode(new, epoch=0, seq=1)
        assert not decode_header(unchanged).keyframe
        assert decoder.apply(unchanged) == new  # stale: seq 1 is held
        with pytest.raises(DeltaError, match="length 9 != held 8"):
            late.apply(unchanged)

    def test_seq_gap_raises(self):
        a, b, c = self._pair()
        decoder = DeltaDecoder()
        decoder.apply(encode_keyframe(a, epoch=0, seq=0))
        with pytest.raises(DeltaError, match="gap"):
            decoder.apply(encode_delta(b, c, epoch=0, seq=2))

    def test_corrupt_run_bounds_raise(self):
        a, b, _c = self._pair()
        decoder = DeltaDecoder()
        decoder.apply(encode_keyframe(a, epoch=0, seq=0))
        blob = bytearray(encode_delta(a, b, epoch=0, seq=1))
        # Point the first run's offset past the frame end.
        struct.pack_into("<I", blob, HEADER_SIZE + 4, len(a) + 100)
        with pytest.raises(DeltaError):
            decoder.apply(bytes(blob))

"""Property sweeps for the delta codec (ADR 0117/0124): arbitrary
frame streams round-trip byte-identically, an epoch change ALWAYS
produces a keyframe (the serving half of the JGL204 epoch discipline
the protocol pass model-checks — ``encoder.keyframes_on_epoch_change``
is the same guard the ``epoch`` model binds), and a sequence gap can
never splice: a non-keyframe blob the decoder cannot prove contiguous
raises, it never patches.

Hypothesis is optional tooling (not baked into every environment);
the module skips wholesale where it is absent — the deterministic
codec suite (``delta_codec_test.py``) still covers the fixed cases.
"""

from __future__ import annotations

import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from esslivedata_tpu.serving.delta import (  # noqa: E402
    DeltaDecoder,
    DeltaEncoder,
    DeltaError,
    decode_header,
)

#: (frame bytes, bump-epoch-before-this-frame) stream steps. Frame
#: lengths vary freely: the encoder's dense/keyframe fallbacks are part
#: of the contract under test, not something to engineer around.
_STREAMS = st.lists(
    st.tuples(st.binary(min_size=0, max_size=96), st.booleans()),
    min_size=1,
    max_size=24,
)


def _drive(steps):
    """Run one encoder/decoder pair over the stream; yields
    (frame, epoch, bumped, blob, reconstructed)."""
    enc, dec = DeltaEncoder(), DeltaDecoder()
    epoch = 0
    for seq, (frame, bump) in enumerate(steps):
        if bump:
            epoch += 1
        blob = enc.encode(frame, epoch=epoch, seq=seq)
        yield frame, epoch, bump, blob, dec.apply(blob)


@settings(max_examples=200, deadline=None)
@given(_STREAMS)
def test_any_stream_round_trips_byte_identical(steps):
    for frame, _epoch, _bump, _blob, got in _drive(steps):
        assert got == frame


@settings(max_examples=200, deadline=None)
@given(_STREAMS)
def test_epoch_change_always_keyframes(steps):
    # The JGL204 discipline at the wire: a delta across an epoch bump
    # would bridge two unrelated accumulations. The encoder must never
    # emit one — the protocol model assumes exactly this guard.
    for _frame, _epoch, bump, blob, _got in _drive(steps):
        if bump:
            assert decode_header(blob).keyframe


@settings(max_examples=200, deadline=None)
@given(_STREAMS)
def test_decoder_tracks_encoder_epoch_and_seq(steps):
    enc, dec = DeltaEncoder(), DeltaDecoder()
    epoch = 0
    for seq, (frame, bump) in enumerate(steps):
        if bump:
            epoch += 1
        dec.apply(enc.encode(frame, epoch=epoch, seq=seq))
        assert dec.epoch == epoch
        assert dec.seq == seq


@settings(max_examples=200, deadline=None)
@given(
    st.binary(min_size=64, max_size=64),
    st.lists(
        st.tuples(st.integers(0, 63), st.integers(0, 255)),
        min_size=1,
        max_size=4,
    ),
)
def test_seq_gap_never_splices(base, edits):
    """Drop one tick from a delta stream: the decoder must either see
    a self-contained keyframe (dense fallback — fine, it rebases) or
    REFUSE the gapped delta. Silently patching a non-contiguous delta
    is the splice failure JGL203/JGL204 model at the protocol layer."""
    frames = [base]
    for offset, value in edits:
        prev = bytearray(frames[-1])
        prev[offset] = value
        frames.append(bytes(prev))
    enc, dec = DeltaEncoder(), DeltaDecoder()
    dec.apply(enc.encode(frames[0], epoch=0, seq=0))
    # Encode the middle of the stream but never deliver it...
    for seq, frame in enumerate(frames[1:-1], start=1):
        enc.encode(frame, epoch=0, seq=seq)
    # ...then deliver the final blob with the gap in front of it.
    blob = enc.encode(frames[-1], epoch=0, seq=len(frames) - 1)
    if len(frames) == 2 or decode_header(blob).keyframe:
        assert dec.apply(blob) == frames[-1]
    else:
        with pytest.raises(DeltaError):
            dec.apply(blob)


@settings(max_examples=100, deadline=None)
@given(st.binary(min_size=8, max_size=64), st.binary(min_size=8, max_size=64))
def test_stale_delta_returns_held_frame_unchanged(old, new):
    # The attach race: the cache's keyframe and the in-flight fan-out
    # may both carry one tick, so a subscriber sees that tick's blob
    # twice. The second delivery must be a no-op, never an error, be
    # the blob a delta or (a length change, the dense fallback) a
    # keyframe.
    enc, dec = DeltaEncoder(), DeltaDecoder()
    dec.apply(enc.encode(old, epoch=0, seq=0))
    blob = enc.encode(new, epoch=0, seq=1)
    held = dec.apply(blob)
    assert held == new
    assert dec.apply(blob) == held
    assert dec.seq == 1

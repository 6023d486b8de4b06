"""A camera's frames on their way to the view: decoded as a view of the
message, kept one by one by the detector and reduction services'
preprocessor (``WindowFrames``), none summed there (ADR 0132)."""

import numpy as np
import pytest

from esslivedata_tpu.core.message import Message, StreamId, StreamKind
from esslivedata_tpu.core.orchestrating_processor import MessagePreprocessor
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.kafka import wire
from esslivedata_tpu.kafka.message_adapter import KafkaToAd00Adapter
from esslivedata_tpu.kafka.source import FakeKafkaMessage
from esslivedata_tpu.kafka.stream_mapping import InputStreamKey, StreamMapping
from esslivedata_tpu.preprocessors import Cumulative, WindowFrames
from esslivedata_tpu.preprocessors.factories import (
    DetectorPreprocessorFactory,
    MonitorPreprocessorFactory,
    ReductionPreprocessorFactory,
)
from esslivedata_tpu.utils import DataArray, Variable

T = Timestamp.from_ns
CAMERA = StreamId(kind=StreamKind.AREA_DETECTOR, name="orca_camera")


def frame(value, shape=(3, 4), dtype=np.uint16):
    return DataArray(Variable(np.full(shape, value, dtype), ("y", "x"), "counts"))


class TestWindowFrames:
    def test_keeps_the_windows_frames_in_order_and_sums_none(self):
        acc = WindowFrames()
        frames = [frame(40_000), frame(40_000), frame(7)]
        for i, f in enumerate(frames):
            acc.add(T(i), f)
        got = acc.get()
        assert [g is f for g, f in zip(got, frames, strict=True)] == [True] * 3
        # two uint16 frames of 40 000 stay two: no sum that wraps to 14 464
        assert all(g.values.dtype == np.uint16 for g in got)

    def test_get_hands_the_window_over_and_starts_the_next(self):
        acc = WindowFrames()
        acc.add(T(0), frame(1))
        assert len(acc.get()) == 1
        assert acc.get() == []
        acc.add(T(1), frame(2))
        acc.clear()
        assert acc.get() == []
        assert WindowFrames.is_context is False

    def test_a_frame_of_another_shape_is_kept_as_it_came(self):
        acc = WindowFrames()
        acc.add(T(0), frame(1))
        acc.add(T(1), frame(1, shape=(8, 8)))  # the camera's ROI changed
        assert [f.shape for f in acc.get()] == [(3, 4), (8, 8)]


@pytest.mark.parametrize(
    "factory", [DetectorPreprocessorFactory, ReductionPreprocessorFactory]
)
def test_the_services_keep_camera_frames_one_by_one(factory):
    assert isinstance(factory().make_preprocessor(CAMERA), WindowFrames)


def test_monitor_histograms_are_still_summed_where_they_were():
    histograms = StreamId(kind=StreamKind.MONITOR_COUNTS, name="mon")
    for factory in (MonitorPreprocessorFactory, ReductionPreprocessorFactory):
        assert isinstance(factory().make_preprocessor(histograms), Cumulative)
    assert MonitorPreprocessorFactory().make_preprocessor(CAMERA) is None


def test_the_ad00_decode_hands_over_a_view_of_the_message():
    mapping = StreamMapping(
        instrument="odin",
        area_detectors={InputStreamKey(topic="odin_camera", source_name="odin_orca"): "orca_camera"},
    )
    image = np.arange(12, dtype=np.uint16).reshape(3, 4)
    buf = wire.encode_ad00("odin_orca", 5, image)
    msg = KafkaToAd00Adapter(mapping).adapt(FakeKafkaMessage(buf, "odin_camera"))
    values = msg.value.values
    assert values.dtype == np.uint16 and not values.flags.writeable
    assert np.shares_memory(values, np.frombuffer(buf, np.uint8))
    np.testing.assert_array_equal(values, image)


def test_a_window_of_frames_reaches_the_view_as_the_messages_decoded_them():
    preprocessor = MessagePreprocessor(DetectorPreprocessorFactory())
    frames = [frame(40_000), frame(30_000)]
    preprocessor.preprocess(Message(timestamp=T(i), stream=CAMERA, value=f) for i, f in enumerate(frames))
    window = preprocessor.collect_window()
    assert list(window) == ["orca_camera"]
    assert [f is g for f, g in zip(window["orca_camera"], frames, strict=True)] == [True, True]
    preprocessor.release()
    assert preprocessor.collect_window() == {}

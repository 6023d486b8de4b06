"""End-to-end service smoke tests for the DREAM / ODIN instrument packages:
real adapters, preprocessors, jitted workflows, serializers — broker faked
at the bytes level (the reference's central test pattern, SURVEY.md 4.2).
"""

from __future__ import annotations

import json

import numpy as np

from esslivedata_tpu.config import JobId, WorkflowConfig
from esslivedata_tpu.core.message_batcher import NaiveMessageBatcher
from esslivedata_tpu.kafka import wire
from esslivedata_tpu.kafka.sink import (
    FakeProducer,
    KafkaSink,
    make_default_serializer,
)
from esslivedata_tpu.kafka.source import FakeKafkaMessage
from esslivedata_tpu.services.detector_data import make_detector_service_builder
from esslivedata_tpu.services.fake_sources import (
    FakeDetectorStream,
    PulsedRawSource,
)


def start_command(workflow_id, source_name, topic, params=None):
    config = WorkflowConfig(
        identifier=workflow_id,
        job_id=JobId(source_name=source_name),
        params=params or {},
    )
    return FakeKafkaMessage(
        json.dumps(
            {"kind": "start_job", "config": config.model_dump(mode="json")}
        ).encode(),
        topic,
    )


def decoded(producer, topic):
    out = {}
    for m in producer.messages:
        if m.topic != topic:
            continue
        msg = wire.decode_da00(m.value)
        out[msg.source_name.split("|")[-1]] = msg
    return out


class TestDreamDetectorService:
    def test_mantle_front_layer_end_to_end(self):
        from esslivedata_tpu.config.instruments.dream import INSTRUMENT
        from esslivedata_tpu.config.instruments.dream.specs import (
            MANTLE_VIEW_HANDLES,
        )

        det = INSTRUMENT.detectors["mantle_detector"]
        stream = FakeDetectorStream(
            topic="dream_detector",
            source_name="dream_mantle_detector",
            detector_ids=det.detector_number.reshape(-1),
            events_per_pulse=2000,
        )
        builder = make_detector_service_builder(
            instrument="dream", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([stream])
        producer = FakeProducer()
        sink = KafkaSink(
            producer,
            make_default_serializer(builder.stream_mapping.livedata, "dream_d"),
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                MANTLE_VIEW_HANDLES["mantle_front_layer"].workflow_id,
                "mantle_detector",
                "dream_livedata_commands",
            )
        )
        for _ in range(4):
            service.step()
        outputs = decoded(producer, "dream_livedata_data")
        img = next(
            v
            for v in outputs["image_cumulative"].variables
            if v.name == "signal"
        )
        assert img.data.shape == (60, 256)
        # Only wire-0 voxels land on the front-layer view: 1/32 of events.
        total = img.data.sum()
        assert 0 < total < 2000 * 4

    def test_wire_view_conserves_all_events(self):
        from esslivedata_tpu.config.instruments.dream import INSTRUMENT
        from esslivedata_tpu.config.instruments.dream.specs import (
            MANTLE_VIEW_HANDLES,
        )

        det = INSTRUMENT.detectors["mantle_detector"]
        stream = FakeDetectorStream(
            topic="dream_detector",
            source_name="dream_mantle_detector",
            detector_ids=det.detector_number.reshape(-1),
            events_per_pulse=1000,
        )
        builder = make_detector_service_builder(
            instrument="dream", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([stream])
        producer = FakeProducer()
        sink = KafkaSink(
            producer,
            make_default_serializer(builder.stream_mapping.livedata, "dream_w"),
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                MANTLE_VIEW_HANDLES["mantle_wire_view"].workflow_id,
                "mantle_detector",
                "dream_livedata_commands",
            )
        )
        for _ in range(3):
            service.step()
        outputs = decoded(producer, "dream_livedata_data")
        img = next(
            v
            for v in outputs["image_cumulative"].variables
            if v.name == "signal"
        )
        assert img.data.shape == (32, 60)
        # Summed view: every event lands somewhere.
        assert img.data.sum() == 3 * 1000


class TestOdinCameraService:
    def test_ad00_frames_accumulate(self):
        from esslivedata_tpu.config.instruments.odin.specs import CAMERA_HANDLE

        builder = make_detector_service_builder(
            instrument="odin", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([])
        producer = FakeProducer()
        sink = KafkaSink(
            producer,
            make_default_serializer(builder.stream_mapping.livedata, "odin_c"),
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                CAMERA_HANDLE.workflow_id,
                "orca_camera",
                "odin_livedata_commands",
            )
        )
        service.step()
        frame = np.full((8, 10), 2.0, dtype=np.float32)
        t0 = 1_700_000_000_000_000_000
        for i in range(3):
            raw.inject(
                FakeKafkaMessage(
                    wire.encode_ad00("odin_orca", t0 + i * 10**9, frame),
                    "odin_camera",
                )
            )
            service.step()
        service.step()
        outputs = decoded(producer, "odin_livedata_data")
        cum = next(
            v for v in outputs["cumulative"].variables if v.name == "signal"
        )
        assert cum.data.shape == (8, 10)
        assert cum.data.sum() == 3 * 2.0 * 8 * 10

    def test_uint16_frames_past_65535_publish_exact_uint32_sums(self):
        """The Orca's uint16 frames through the detector service: each
        summed on the device into uint32, so a cumulative past 65 535
        counts a pixel is published exactly, as uint32."""
        from esslivedata_tpu.config.instruments.odin.specs import CAMERA_HANDLE
        from esslivedata_tpu.telemetry.instruments import CAMERA_FRAMES

        builder = make_detector_service_builder(
            instrument="odin", batcher=NaiveMessageBatcher(), job_threads=1
        )
        raw = PulsedRawSource([])
        producer = FakeProducer()
        sink = KafkaSink(
            producer,
            make_default_serializer(builder.stream_mapping.livedata, "odin_u"),
        )
        service = builder.from_raw_source(raw, sink)
        raw.inject(
            start_command(
                CAMERA_HANDLE.workflow_id,
                "orca_camera",
                "odin_livedata_commands",
                params={"transpose": True},
            )
        )
        service.step()
        device_frames = CAMERA_FRAMES.value(path="device")
        rng = np.random.default_rng(42)
        frames = [
            rng.integers(20_000, 40_000, (6, 10)).astype(np.uint16)
            for _ in range(5)
        ]
        t0 = 1_700_000_000_000_000_000
        for i, frame in enumerate(frames):
            raw.inject(
                FakeKafkaMessage(
                    wire.encode_ad00("odin_orca", t0 + i * 10**9, frame),
                    "odin_camera",
                )
            )
            service.step()
        service.step()
        outputs = decoded(producer, "odin_livedata_data")
        cum = next(
            v for v in outputs["cumulative"].variables if v.name == "signal"
        )
        current = next(
            v for v in outputs["current"].variables if v.name == "signal"
        )
        expected = np.sum(frames, axis=0, dtype=np.int64).T
        assert expected.min() > 65_535
        assert cum.data.dtype == np.uint32 and cum.data.shape == (10, 6)
        np.testing.assert_array_equal(cum.data, expected)
        np.testing.assert_array_equal(current.data, frames[-1].T)
        assert CAMERA_FRAMES.value(path="device") == device_frames + 5

import time

import numpy as np
import pytest

from esslivedata_tpu.core.message import Message, StreamId, StreamKind
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.kafka import wire
from esslivedata_tpu.kafka.sink import (
    FakeProducer,
    KafkaSink,
    UnrollingSinkAdapter,
    make_default_serializer,
)
from esslivedata_tpu.kafka.source import (
    BackgroundMessageSource,
    ConsumerHealth,
    FakeConsumer,
    FakeKafkaMessage,
)
from esslivedata_tpu.kafka.stream_mapping import LivedataTopics
from esslivedata_tpu.utils import DataArray, Variable, linspace


class FailingConsumer:
    def __init__(self, fail_times: int, then: list) -> None:
        self.fail_times = fail_times
        self.then = list(then)

    def consume(self, num_messages, timeout):
        if self.fail_times > 0:
            self.fail_times -= 1
            raise RuntimeError("broker down")
        return self.then.pop(0) if self.then else []


class TestBackgroundSource:
    def test_drains_in_order(self):
        msgs = [FakeKafkaMessage(b"x", "t") for _ in range(5)]
        consumer = FakeConsumer([msgs[:2], msgs[2:]])
        with BackgroundMessageSource(consumer, timeout_s=0.001) as source:
            deadline = time.monotonic() + 2.0
            got = []
            while len(got) < 5 and time.monotonic() < deadline:
                got.extend(source.get_messages())
                time.sleep(0.01)
        assert got == msgs

    def test_circuit_breaker_opens(self):
        consumer = FailingConsumer(fail_times=1000, then=[])
        source = BackgroundMessageSource(
            consumer, timeout_s=0.001, max_consecutive_errors=3
        )
        source.start()
        deadline = time.monotonic() + 5.0
        while source.health != ConsumerHealth.STOPPED and time.monotonic() < deadline:
            time.sleep(0.01)
        assert source.health == ConsumerHealth.STOPPED
        with pytest.raises(RuntimeError, match="circuit breaker"):
            source.get_messages()
        source.stop()

    def test_transient_errors_recover(self):
        consumer = FailingConsumer(
            fail_times=2, then=[[FakeKafkaMessage(b"ok", "t")]]
        )
        with BackgroundMessageSource(
            consumer, timeout_s=0.001, max_consecutive_errors=10
        ) as source:
            deadline = time.monotonic() + 3.0
            got = []
            while not got and time.monotonic() < deadline:
                got = source.get_messages()
                time.sleep(0.01)
        assert len(got) == 1

    def test_queue_bounded_drop_oldest(self):
        batches = [[FakeKafkaMessage(str(i).encode(), "t")] for i in range(20)]
        consumer = FakeConsumer(batches)
        source = BackgroundMessageSource(
            consumer, timeout_s=0.0, max_queued_batches=5
        )
        source.start()
        deadline = time.monotonic() + 2.0
        while consumer._batches and time.monotonic() < deadline:
            time.sleep(0.01)
        source.stop()
        remaining = source.get_messages()
        assert len(remaining) <= 5
        assert source.metrics["dropped_batches"] >= 15


def hist_message(name="bank0/image_current"):
    da = DataArray(
        Variable(np.arange(4.0).reshape(2, 2), ("y", "x"), "counts"),
        coords={"x": linspace("x", 0, 2, 3, "mm"), "y": linspace("y", 0, 2, 3, "mm")},
    )
    return Message(
        timestamp=Timestamp.from_ns(123),
        stream=StreamId(kind=StreamKind.LIVEDATA_DATA, name=name),
        value=da,
    )


class TestKafkaSink:
    def test_publishes_da00(self):
        producer = FakeProducer()
        topics = LivedataTopics.for_instrument("dummy")
        sink = KafkaSink(producer, make_default_serializer(topics))
        sink.publish_messages([hist_message()])
        [sent] = producer.messages
        assert sent.topic == "dummy_livedata_data"
        da00 = wire.decode_da00(sent.value)
        assert da00.source_name == "bank0/image_current"
        assert wire.get_schema(sent.value) == "da00"

    def test_drop_on_buffer_error(self):
        producer = FakeProducer(buffer_errors=1)
        topics = LivedataTopics.for_instrument("dummy")
        sink = KafkaSink(producer, make_default_serializer(topics))
        sink.publish_messages([hist_message(), hist_message()])
        assert sink.dropped == 1
        assert len(producer.messages) == 1

    def test_phase_seconds_and_bytes_are_counted(self):
        """``livedata_sink_seconds_total{phase}`` and
        ``livedata_sink_bytes_total``: summed over a publish's
        messages, the serialize and produce times each land in their
        own phase and the bytes are the payloads handed over."""
        import time

        from esslivedata_tpu.telemetry import REGISTRY

        seconds = REGISTRY.get("livedata_sink_seconds_total")
        nbytes = REGISTRY.get("livedata_sink_bytes_total")

        class SlowProducer(FakeProducer):
            def produce(self, topic, value, key=None):
                time.sleep(0.02)
                super().produce(topic, value, key)

            def flush(self, timeout=0.0):
                time.sleep(0.01)

        class SlowSerializer:
            def __init__(self, inner):
                self._inner = inner

            def serialize(self, message):
                time.sleep(0.005)
                return self._inner.serialize(message)

        producer = SlowProducer()
        topics = LivedataTopics.for_instrument("dummy")
        sink = KafkaSink(
            producer, SlowSerializer(make_default_serializer(topics))
        )
        before = {
            phase: seconds.value(phase=phase)
            for phase in ("serialize", "produce", "flush")
        }
        bytes0 = nbytes.total()
        sink.publish_messages([hist_message(), hist_message("bank0/b")])
        added = {
            phase: seconds.value(phase=phase) - before[phase]
            for phase in before
        }
        assert 0.010 <= added["serialize"] < 0.040 <= added["produce"]
        assert 0.010 <= added["flush"] < 0.040
        assert nbytes.total() - bytes0 == sum(
            len(m.value) for m in producer.messages
        )

    def test_serialize_is_split_into_da00_build_and_wire_encode(self, monkeypatch):
        """``livedata_sink_serialize_seconds_total{step}``: both steps
        are on the scrape at 0 from the serializer's construction; a
        publish adds to both, and together they stay inside the
        ``serialize`` phase of the same publish."""
        from esslivedata_tpu.kafka import sink as sink_module
        from esslivedata_tpu.telemetry import REGISTRY, Counter

        assert REGISTRY.get("livedata_sink_serialize_seconds_total") is (
            sink_module.SINK_SERIALIZE_SECONDS
        )
        parts = Counter("test_sink_serialize_seconds_total", "", labelnames=("step",))
        monkeypatch.setattr(sink_module, "_DA00_S", parts.labels(step="da00"))
        monkeypatch.setattr(sink_module, "_WIRE_S", parts.labels(step="wire"))
        assert parts.items() == []
        serializer = make_default_serializer(LivedataTopics.for_instrument("dummy"))
        assert parts.items() == [({"step": "da00"}, 0.0), ({"step": "wire"}, 0.0)]

        real_build, real_encode = sink_module.dataarray_to_da00, wire.encode_da00

        def slow_build(value):
            time.sleep(0.004)
            return real_build(value)

        def slow_encode(*args):
            time.sleep(0.008)
            return real_encode(*args)

        monkeypatch.setattr(sink_module, "dataarray_to_da00", slow_build)
        monkeypatch.setattr(wire, "encode_da00", slow_encode)
        phases = REGISTRY.get("livedata_sink_seconds_total")
        serialize0 = phases.value(phase="serialize")
        producer = FakeProducer()
        KafkaSink(producer, serializer).publish_messages(
            [hist_message(), hist_message("bank0/b")]
        )
        serialize = phases.value(phase="serialize") - serialize0
        da00, encode = parts.value(step="da00"), parts.value(step="wire")
        assert 0.008 <= da00 and 0.016 <= encode  # each step holds its own sleeps
        assert da00 + encode <= serialize
        assert [wire.get_schema(m.value) for m in producer.messages] == ["da00", "da00"]

    def test_serialize_error_contained(self):
        producer = FakeProducer()
        topics = LivedataTopics.for_instrument("dummy")
        sink = KafkaSink(producer, make_default_serializer(topics))
        bad = Message(
            timestamp=Timestamp.from_ns(1),
            stream=StreamId(kind=StreamKind.LIVEDATA_DATA, name="x"),
            value=object(),  # unserializable
        )
        sink.publish_messages([bad, hist_message()])
        assert sink.serialize_errors == 1
        assert len(producer.messages) == 1

    def test_unrolling_adapter(self):
        producer = FakeProducer()
        topics = LivedataTopics.for_instrument("dummy")
        sink = UnrollingSinkAdapter(KafkaSink(producer, make_default_serializer(topics)))
        da = hist_message().value
        group = Message(
            timestamp=Timestamp.from_ns(5),
            stream=StreamId(kind=StreamKind.LIVEDATA_DATA, name="job1"),
            value={"image": da, "counts": da},
        )
        sink.publish_messages([group])
        names = {wire.decode_da00(m.value).source_name for m in producer.messages}
        assert names == {"job1/image", "job1/counts"}

    def test_status_x5f2(self):
        from pydantic import BaseModel

        class ServiceStatus(BaseModel):
            state: str = "running"

        producer = FakeProducer()
        topics = LivedataTopics.for_instrument("dummy")
        sink = KafkaSink(producer, make_default_serializer(topics, "svc1"))
        sink.publish_messages(
            [
                Message(
                    timestamp=Timestamp.from_ns(1),
                    stream=StreamId(kind=StreamKind.LIVEDATA_STATUS, name=""),
                    value=ServiceStatus(),
                )
            ]
        )
        [sent] = producer.messages
        status = wire.decode_x5f2(sent.value)
        assert status.service_id == "svc1"
        assert '"running"' in status.status_json


class TestSinkProduceBreaker:
    """Transient produce/flush exceptions are contained (a broker hiccup
    must not crash the service worker per message); the breaker opens
    after MAX_CONSECUTIVE_ERRORS and propagates for a supervisor
    restart (reference kafka_sink_test's fatal/non-fatal split)."""

    class _FlakyProducer:
        def __init__(self, fail_times):
            self.fail_times = fail_times
            self.produced = []

        def produce(self, topic, value, key=None):
            if self.fail_times > 0:
                self.fail_times -= 1
                raise RuntimeError("transient broker error")
            self.produced.append((topic, value))

        def flush(self, timeout):
            return 0

    def _msg(self):
        from esslivedata_tpu.core.message import Message, StreamId, StreamKind
        from esslivedata_tpu.core.timestamp import Timestamp
        from esslivedata_tpu.utils.labeled import DataArray, Variable
        import numpy as np

        return Message(
            timestamp=Timestamp.from_ns(1),
            stream=StreamId(kind=StreamKind.LIVEDATA_DATA, name="w/j|out"),
            value=DataArray(Variable(np.ones(3), ("x",), "counts")),
        )

    def _sink(self, producer):
        from esslivedata_tpu.kafka.sink import KafkaSink, make_default_serializer
        from esslivedata_tpu.kafka.stream_mapping import LivedataTopics

        return KafkaSink(
            producer,
            make_default_serializer(
                LivedataTopics.for_instrument("dummy", False), "t"
            ),
        )

    def test_transient_error_contained_and_next_message_flows(self):
        producer = self._FlakyProducer(fail_times=2)
        sink = self._sink(producer)
        for _ in range(3):
            sink.publish_messages([self._msg()])
        assert sink.produce_errors == 2
        assert sink.flush_errors == 0  # metrics stay split by path
        assert len(producer.produced) == 1  # the third one made it

    def test_breaker_opens_after_consecutive_failures(self):
        from esslivedata_tpu.kafka.sink import KafkaSink

        producer = self._FlakyProducer(fail_times=10**6)
        sink = self._sink(producer)
        with pytest.raises(RuntimeError, match="transient broker error"):
            for _ in range(KafkaSink.MAX_CONSECUTIVE_ERRORS + 1):
                sink.publish_messages([self._msg()])
        assert sink.produce_errors == KafkaSink.MAX_CONSECUTIVE_ERRORS

    def test_sustained_buffer_full_trips_the_breaker(self):
        # An extended broker outage surfaces as BufferError from the
        # async producer's full local queue: sustained drops must open
        # the breaker, not black-hole messages forever.
        from esslivedata_tpu.kafka.sink import KafkaSink

        class _FullQueueProducer:
            def produce(self, topic, value, key=None):
                raise BufferError("queue full")

            def flush(self, timeout):
                return 1

        sink = self._sink(_FullQueueProducer())
        with pytest.raises(BufferError):
            for _ in range(KafkaSink.MAX_CONSECUTIVE_ERRORS + 1):
                sink.publish_messages([self._msg()])
        assert sink.dropped == KafkaSink.MAX_CONSECUTIVE_ERRORS

    def test_success_resets_the_breaker(self):
        producer = self._FlakyProducer(fail_times=5)
        sink = self._sink(producer)
        for _ in range(6):
            sink.publish_messages([self._msg()])
        assert len(producer.produced) == 1
        # Another burst below the threshold: still contained.
        producer.fail_times = 5
        for _ in range(6):
            sink.publish_messages([self._msg()])
        assert len(producer.produced) == 2

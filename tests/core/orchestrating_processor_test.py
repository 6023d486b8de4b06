"""OrchestratingProcessor + MessagePreprocessor unit scenarios
(reference granularity: tests/core/orchestrating_processor_test.py —
idle ticks, context-accumulator routing, containment, heartbeat cadence,
idempotent finalize).
"""

from __future__ import annotations

from esslivedata_tpu.core.fakes import FakeMessageSink, FakeMessageSource
from esslivedata_tpu.core.job import JobStatus, ServiceStatus
from esslivedata_tpu.core.job_manager import JobManager
from esslivedata_tpu.core.message import (
    Message,
    StreamId,
    StreamKind,
)
from esslivedata_tpu.core.message_batcher import (
    AdaptiveMessageBatcher,
    NaiveMessageBatcher,
    SimpleMessageBatcher,
)
from esslivedata_tpu.core.orchestrating_processor import (
    MessagePreprocessor,
    OrchestratingProcessor,
)
from esslivedata_tpu.core.timestamp import Timestamp


def data_stream(name: str) -> StreamId:
    return StreamId(kind=StreamKind.DETECTOR_EVENTS, name=name)


def msg(name: str, value=1.0, ns: int = 1_000) -> Message:
    return Message(
        timestamp=Timestamp.from_ns(ns),
        stream=data_stream(name),
        value=value,
    )


class RecordingAccumulator:
    is_context = False
    also_context = False

    def __init__(self, fail_on_add: bool = False) -> None:
        self.added: list = []
        self.released = 0
        self.fail_on_add = fail_on_add

    def add(self, timestamp, value) -> None:
        if self.fail_on_add:
            raise RuntimeError("hostile payload")
        self.added.append(value)

    def get(self):
        return list(self.added)

    def release_buffers(self) -> None:
        self.released += 1
        self.added.clear()


class ContextAccumulator(RecordingAccumulator):
    is_context = True

    def __init__(self) -> None:
        super().__init__()

    @property
    def has_value(self) -> bool:
        return bool(self.added)

    def get(self):
        return self.added[-1]

    def release_buffers(self) -> None:
        # Context accumulators are latest-value: release keeps the cache.
        self.released += 1


class StubFactory:
    """PreprocessorFactory double: a fixed accumulator per stream name,
    None for undeclared streams."""

    def __init__(self, accumulators: dict) -> None:
        self.accumulators = accumulators
        self.calls: list[StreamId] = []

    def make_preprocessor(self, stream: StreamId):
        self.calls.append(stream)
        return self.accumulators.get(stream.name)


class TestMessagePreprocessor:
    def test_window_collects_only_touched_primary_streams(self):
        acc_a, acc_b = RecordingAccumulator(), RecordingAccumulator()
        pre = MessagePreprocessor(StubFactory({"a": acc_a, "b": acc_b}))
        pre.preprocess([msg("a", 1.0), msg("a", 2.0)])
        window = pre.collect_window()
        assert window == {"a": [1.0, 2.0]}  # b untouched: absent

    def test_context_accumulator_excluded_from_window(self):
        ctx = ContextAccumulator()
        pre = MessagePreprocessor(StubFactory({"c": ctx}))
        pre.preprocess([msg("c", 42.0)])
        assert pre.collect_window() == {}
        assert pre.collect_context() == {"c": 42.0}

    def test_unpopulated_context_not_reported(self):
        ctx = ContextAccumulator()
        pre = MessagePreprocessor(StubFactory({"c": ctx}))
        assert pre.collect_context() == {}

    def test_context_value_persists_across_batches(self):
        """Context is LATEST-value: a batch without fresh context still
        reports the cached value, but not as fresh."""
        ctx, prim = ContextAccumulator(), RecordingAccumulator()
        pre = MessagePreprocessor(StubFactory({"c": ctx, "a": prim}))
        pre.preprocess([msg("c", 7.0)])
        assert pre.fresh_context_names() == {"c"}
        pre.release()
        pre.preprocess([msg("a", 1.0)])
        assert pre.collect_context() == {"c": 7.0}
        assert pre.fresh_context_names() == set()

    def test_undeclared_stream_dropped_and_drop_cached(self):
        factory = StubFactory({})
        pre = MessagePreprocessor(factory)
        pre.preprocess([msg("ghost"), msg("ghost")])
        assert pre.collect_window() == {}
        # Factory consulted once; the drop decision is cached.
        assert len(factory.calls) == 1

    def test_hostile_add_contained_and_other_streams_survive(self):
        bad, good = RecordingAccumulator(fail_on_add=True), RecordingAccumulator()
        pre = MessagePreprocessor(StubFactory({"bad": bad, "good": good}))
        pre.preprocess([msg("bad"), msg("good", 3.0)])
        assert pre.collect_window() == {"good": [3.0]}

    def test_release_clears_touched_and_releases_buffers(self):
        acc = RecordingAccumulator()
        pre = MessagePreprocessor(StubFactory({"a": acc}))
        pre.preprocess([msg("a")])
        pre.release()
        assert acc.released == 1
        assert pre.collect_window() == {}  # nothing touched anymore


def make_processor(
    *,
    source=None,
    factory=None,
    clock=None,
    heartbeat_interval_s: float = 2.0,
    batcher=None,
):
    sink = FakeMessageSink()
    processor = OrchestratingProcessor(
        source=source or FakeMessageSource(),
        sink=sink,
        preprocessor_factory=factory or StubFactory({}),
        job_manager=JobManager(job_threads=1),
        batcher=batcher or NaiveMessageBatcher(),
        instrument="dummy",
        service_name="detector_data",
        clock=clock or (lambda: 0.0),
        heartbeat_interval_s=heartbeat_interval_s,
    )
    return processor, sink


class TestProcessorCycle:
    def test_idle_tick_publishes_status_only(self):
        processor, sink = make_processor()
        processor.process()
        kinds = {m.stream.kind for m in sink.messages}
        assert kinds == {StreamKind.LIVEDATA_STATUS}
        assert not any(
            m.stream.kind is StreamKind.LIVEDATA_DATA for m in sink.messages
        )

    def test_heartbeat_respects_cadence_with_fake_clock(self):
        now = {"t": 0.0}
        source = FakeMessageSource([[], [], []])
        processor, sink = make_processor(
            source=source, clock=lambda: now["t"]
        )
        processor.process()  # t=0: first heartbeat (last=-inf)
        n0 = len(sink.messages)
        now["t"] = 1.0
        processor.process()  # within 2 s: no new heartbeat
        assert len(sink.messages) == n0
        now["t"] = 2.5
        processor.process()  # past 2 s: heartbeat again
        assert len(sink.messages) > n0

    def test_an_idle_loop_still_sweeps_the_staging_pool(self, monkeypatch):
        """Beam off: no staging looks at the pool any more, so the 30 s
        metrics line does (ADR 0130)."""
        from esslivedata_tpu.ops import staging_pool

        swept = []
        monkeypatch.setattr(
            staging_pool.POOL, "sweep", lambda: swept.append(now["t"])
        )
        now = {"t": 0.0}
        processor, _ = make_processor(
            source=FakeMessageSource([[], [], []]), clock=lambda: now["t"]
        )
        processor.process()
        now["t"] = 10.0
        processor.process()
        now["t"] = 31.0
        processor.process()
        assert swept == [31.0]

    def test_data_batch_reaches_accumulator_and_buffers_release(self):
        acc = RecordingAccumulator()
        source = FakeMessageSource([[msg("a", 5.0)]])
        processor, _ = make_processor(
            source=source, factory=StubFactory({"a": acc})
        )
        processor.process()
        # The window was collected and buffers released after publish.
        assert acc.released == 1

    def test_status_document_shape(self):
        processor, sink = make_processor()
        processor.process()
        status = sink.messages[0].value
        assert isinstance(status, ServiceStatus)
        assert status.service_name == "detector_data"
        assert status.instrument == "dummy"
        assert status.state == "running"
        assert status.source_health == "ok"  # fakes: no breaker = ok

    def test_finalize_publishes_stopped_once(self):
        processor, sink = make_processor()
        processor.finalize()
        processor.finalize()  # idempotent
        stopped = [
            m
            for m in sink.messages
            if isinstance(m.value, ServiceStatus)
            and m.value.state == "stopped"
        ]
        assert len(stopped) == 1

    def test_finalize_marks_job_heartbeats_stopped(self):
        import uuid

        from esslivedata_tpu.config.instrument import instrument_registry
        from esslivedata_tpu.config.instruments.dummy.specs import (
            DETECTOR_VIEW_HANDLE,
        )
        from esslivedata_tpu.config.workflow_spec import (
            JobId,
            WorkflowConfig,
        )

        instrument_registry["dummy"].load_factories()
        processor, sink = make_processor()
        processor._job_manager.schedule_job(
            WorkflowConfig(
                identifier=DETECTOR_VIEW_HANDLE.workflow_id,
                job_id=JobId(
                    source_name="panel_0", job_number=uuid.uuid4()
                ),
                params={},
            )
        )
        processor.finalize()
        job_beats = [
            m.value for m in sink.messages if isinstance(m.value, JobStatus)
        ]
        assert job_beats, "per-job heartbeat expected on finalize"
        assert all(j.state == "stopped" for j in job_beats)


class ClockedSource:
    """A source whose polls return at stated times of a fake
    ``perf_counter``: ``polls`` is [(return time, messages)]."""

    def __init__(self, polls, now: dict) -> None:
        self._polls = list(polls)
        self._now = now

    def get_messages(self):
        if not self._polls:
            return []
        self._now["t"], messages = self._polls.pop(0)
        return messages


class TestBatchHoldAndScale:
    def pulse(self, index: int) -> Message:
        return Message(
            timestamp=Timestamp.from_pulse_index(index),
            stream=data_stream("a"),
            value=float(index),
        )

    def test_hold_from_the_poll_that_delivered_the_last_message(
        self, monkeypatch
    ):
        """A batcher fed two polls under a fake clock: the window's
        pulses at 10.000, the pulse that closes it at 10.071. The hold
        is observed once, as the 71 ms between them, and rides the
        ``decode`` span of that tick."""
        import time
        from types import SimpleNamespace

        from esslivedata_tpu.core import orchestrating_processor as module
        from esslivedata_tpu.telemetry import REGISTRY, TRACER

        now = {"t": 0.0}
        monkeypatch.setattr(
            module,
            "time",
            SimpleNamespace(
                perf_counter=lambda: now["t"],
                monotonic=time.monotonic,
                time_ns=time.time_ns,
            ),
        )
        source = ClockedSource(
            [
                (10.000, [self.pulse(p) for p in range(14)]),
                (10.071, [self.pulse(14)]),
            ],
            now,
        )
        processor, _ = make_processor(
            source=source,
            factory=StubFactory({"a": RecordingAccumulator()}),
            batcher=SimpleMessageBatcher(),
        )
        hold = REGISTRY.get("livedata_batch_hold_seconds")
        count0, sum0 = hold.total_count(), hold.sum()
        TRACER.enabled = True
        TRACER.clear()
        processor.process()
        assert hold.total_count() == count0  # the window is still open
        processor.process()
        assert hold.total_count() == count0 + 1
        assert abs(hold.sum() - sum0 - 0.071) < 1e-9
        (decode,) = [s for s in TRACER.spans() if s.name == "decode"]
        assert decode.args == {"hold_us": 71000}

    def gauge(self, processor) -> list[float]:
        return [
            sample.value
            for family in processor._telemetry_families()
            if family.name == "livedata_batcher_window_scale"
            for sample in family.samples
        ]

    def test_scale_gauge_follows_the_adaptive_batcher(self):
        from esslivedata_tpu.core.timestamp import Duration

        batcher = AdaptiveMessageBatcher()
        processor, _ = make_processor(batcher=batcher)
        assert self.gauge(processor) == [1.0]
        for _ in range(2):
            batcher.report_processing_time(Duration.from_s(0.9))
        assert self.gauge(processor) == [2.0]

    def test_a_batcher_without_a_scale_exposes_no_sample(self):
        processor, _ = make_processor()
        assert self.gauge(processor) == []

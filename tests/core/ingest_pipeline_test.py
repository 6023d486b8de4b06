"""IngestPipeline: bounded backpressure, ordered drain on stop, failure
latching, prestage warming, and the pipelined OrchestratingProcessor
end to end (ADR 0111)."""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.ingest_pipeline import IngestPipeline
from esslivedata_tpu.core.job_manager import JobFactory, JobManager
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.ops import EventBatch
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.workflows import WorkflowFactory
from esslivedata_tpu.workflows.detector_view import (
    DetectorViewWorkflow,
    project_logical,
)

T = Timestamp.from_ns


def make_manager(n_jobs: int = 1, side: int = 8) -> JobManager:
    det = np.arange(side * side).reshape(side, side)
    reg = WorkflowFactory()
    spec = WorkflowSpec(
        instrument="test", name="dv_pipe", source_names=["det0"]
    )
    reg.register_spec(spec).attach_factory(
        lambda *, source_name, params: DetectorViewWorkflow(
            projection=project_logical(det)
        )
    )
    mgr = JobManager(job_factory=JobFactory(reg), job_threads=2)
    for _ in range(n_jobs):
        mgr.schedule_job(
            WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name="det0")
            )
        )
    return mgr


def staged_window(seed: int, n: int = 500, n_pixel: int = 64) -> dict:
    rng = np.random.default_rng(seed)
    return {
        "det0": StagedEvents(
            batch=EventBatch.from_arrays(
                rng.integers(-2, n_pixel + 5, n).astype(np.int64),
                rng.uniform(-1e5, 8e7, n).astype(np.float32),
            ),
            first_timestamp=None,
            last_timestamp=None,
            n_chunks=1,
        )
    }


class TestBackpressure:
    def test_slow_consumer_throttles_submit(self):
        """With the step stage pinned slow, submit must block once the
        pipeline reaches depth — bounded memory, not a growing queue."""
        mgr = make_manager()
        release = threading.Event()
        real_process = mgr.process_jobs

        def slow_process(*args, **kwargs):
            release.wait(timeout=10.0)
            return real_process(*args, **kwargs)

        mgr.process_jobs = slow_process
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: None,
            depth=2,
        )
        try:
            for i in range(2):  # fills the in-flight bound
                pipe.submit(staged_window(i), start=T(0), end=T(i + 1))
            t0 = time.monotonic()
            blocked = threading.Event()

            def submit_third():
                pipe.submit(staged_window(2), start=T(0), end=T(3))
                blocked.set()

            thread = threading.Thread(target=submit_third)
            thread.start()
            # The third submit must NOT complete while the consumer is
            # stuck — that is the throttle.
            assert not blocked.wait(timeout=0.5)
            release.set()
            assert blocked.wait(timeout=10.0)
            thread.join()
            assert time.monotonic() - t0 >= 0.5
            assert pipe.flush(timeout=10.0)
        finally:
            release.set()
            pipe.stop(drain=True)
            mgr.shutdown()

    def test_inflight_never_exceeds_depth(self):
        mgr = make_manager()
        max_seen = 0
        lock = threading.Lock()
        real_process = mgr.process_jobs

        def counting_process(*args, **kwargs):
            time.sleep(0.01)
            return real_process(*args, **kwargs)

        mgr.process_jobs = counting_process
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: None,
            depth=3,
        )
        try:
            for i in range(10):
                pipe.submit(staged_window(i), start=T(0), end=T(i + 1))
                with lock:
                    max_seen = max(max_seen, pipe.stats()["inflight"])
            assert pipe.flush(timeout=30.0)
            assert max_seen <= 3
        finally:
            pipe.stop(drain=True)
            mgr.shutdown()


class TestShutdownDrain:
    def test_stop_drains_all_windows_in_order(self):
        """Service stop: every accepted window flushes through step and
        publish, in submission order — no drops, no reorders — even with
        a randomized slow-stage schedule."""
        mgr = make_manager()
        rng = np.random.default_rng(7)
        real_prestage = mgr.prestage_window
        real_process = mgr.process_jobs

        def slow_prestage(*args, **kwargs):
            time.sleep(float(rng.uniform(0, 0.02)))
            return real_prestage(*args, **kwargs)

        def slow_process(*args, **kwargs):
            time.sleep(float(rng.uniform(0, 0.02)))
            return real_process(*args, **kwargs)

        mgr.prestage_window = slow_prestage
        mgr.process_jobs = slow_process
        published_ends = []
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: published_ends.append(end),
            depth=2,
        )
        n = 12
        for i in range(n):
            pipe.submit(staged_window(i), start=T(0), end=T(i + 1))
        assert pipe.stop(drain=True, timeout=60.0)
        mgr.shutdown()
        assert published_ends == [T(i + 1) for i in range(n)]
        with pytest.raises(RuntimeError, match="stopped"):
            pipe.submit(staged_window(99))

    def test_stop_without_drain_abandons_quietly(self):
        mgr = make_manager()
        gate = threading.Event()
        real_process = mgr.process_jobs

        def gated(*args, **kwargs):
            gate.wait(timeout=5.0)
            return real_process(*args, **kwargs)

        mgr.process_jobs = gated
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: None,
            depth=2,
        )
        pipe.submit(staged_window(0), start=T(0), end=T(1))
        pipe.submit(staged_window(1), start=T(0), end=T(2))
        gate.set()
        pipe.stop(drain=False)
        assert pipe.failure is None
        mgr.shutdown()


class TestFailureLatch:
    def test_worker_failure_surfaces_on_submit(self):
        mgr = make_manager()

        def broken_process(*args, **kwargs):
            raise RuntimeError("step exploded")

        mgr.process_jobs = broken_process
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: None,
            depth=2,
        )
        try:
            pipe.submit(staged_window(0), start=T(0), end=T(1))
            deadline = time.monotonic() + 5.0
            while pipe.failure is None and time.monotonic() < deadline:
                time.sleep(0.01)
            assert pipe.failure is not None
            with pytest.raises(RuntimeError, match="worker failed"):
                pipe.submit(staged_window(1), start=T(0), end=T(2))
        finally:
            pipe.stop(drain=False)
            mgr.shutdown()


class TestPrestageWarming:
    def test_step_hits_prestaged_slots(self):
        """The stage worker's prestage must warm exactly the keys the
        step-time workflows request: with K=2 fused jobs the window's
        staging is ONE miss (the prestage) and the fused step a hit."""
        mgr = make_manager(n_jobs=2)
        published = []
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: published.append(results),
            depth=2,
        )
        try:
            for i in range(3):
                pipe.submit(staged_window(i), start=T(0), end=T(i + 1))
            assert pipe.flush(timeout=30.0)
            stats = mgr.event_cache_stats()
            assert stats["misses"] == 3  # one staging per window
            assert stats["hits"] >= 3  # fused step consumed the warm slot
            assert len(published) == 3
            assert all(len(results) == 2 for results in published)
        finally:
            pipe.stop(drain=True)
            mgr.shutdown()

    @pytest.mark.parametrize("depth", [1, 2, 4])
    def test_depth_is_the_configured_depth(self, depth):
        """With the step stage stuck, exactly ``depth`` windows are
        admitted (no fewer, no more), the next submit waits, and both
        introspection surfaces report the configured bound."""
        mgr = make_manager()
        release = threading.Event()
        real_process = mgr.process_jobs

        def stuck_process(*args, **kwargs):
            release.wait(timeout=10.0)
            return real_process(*args, **kwargs)

        mgr.process_jobs = stuck_process
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: None,
            depth=depth,
        )
        try:
            for i in range(depth):  # every one admitted without waiting
                pipe.submit(staged_window(i), start=T(0), end=T(i + 1))
            assert pipe.depth == depth
            assert pipe.stats()["depth"] == depth
            telemetry = pipe.telemetry()
            assert telemetry["depth"] == depth
            assert telemetry["inflight"] == depth
            admitted = threading.Event()

            def submit_one_more():
                pipe.submit(staged_window(depth), start=T(0), end=T(depth + 1))
                admitted.set()

            thread = threading.Thread(target=submit_one_more)
            thread.start()
            assert not admitted.wait(timeout=0.3)
            assert pipe.telemetry()["inflight"] == depth
            release.set()
            assert admitted.wait(timeout=10.0)
            thread.join()
            assert pipe.flush(timeout=30.0)
        finally:
            release.set()
            pipe.stop(drain=True)
            mgr.shutdown()

    def test_empty_window_flushes_in_order(self):
        mgr = make_manager()
        order = []
        pipe = IngestPipeline(
            job_manager=mgr,
            decode=lambda payload: (payload, {}, None),
            publish=lambda results, end: order.append(end),
            depth=2,
        )
        try:
            pipe.submit(staged_window(0), start=T(0), end=T(1))
            pipe.submit(None)  # finishing-jobs flush rides the pipeline
            pipe.submit(staged_window(1), start=T(1), end=T(2))
            assert pipe.flush(timeout=30.0)
            # The empty window published nothing; the two data windows
            # published in order around it.
            assert order == [T(1), T(2)]
        finally:
            pipe.stop(drain=True)
            mgr.shutdown()


class TestPipelinedProcessor:
    def test_detector_service_end_to_end(self):
        """A real detector service with pipelined=True: inject pulses,
        step the loop, and require every publish of the serial service
        to appear — same count, same order — plus a clean finalize
        (drain before the stopped statuses)."""
        from esslivedata_tpu.config.instruments.dummy.specs import (
            DETECTOR_VIEW_HANDLE,
            INSTRUMENT,
        )
        from esslivedata_tpu.core.message_batcher import NaiveMessageBatcher
        from esslivedata_tpu.kafka import wire
        from esslivedata_tpu.kafka.sink import (
            FakeProducer,
            KafkaSink,
            make_default_serializer,
        )
        from esslivedata_tpu.kafka.source import FakeKafkaMessage
        from esslivedata_tpu.services.detector_data import (
            make_detector_service_builder,
        )
        from esslivedata_tpu.services.fake_sources import PulsedRawSource

        def run(pipelined: bool):
            builder = make_detector_service_builder(
                instrument="dummy",
                batcher=NaiveMessageBatcher(),
                job_threads=1,
            )
            builder.pipelined = pipelined
            raw = PulsedRawSource([])
            producer = FakeProducer()
            sink = KafkaSink(
                producer,
                make_default_serializer(
                    builder.stream_mapping.livedata, "pipe"
                ),
            )
            service = builder.from_raw_source(raw, sink)
            import uuid

            config = WorkflowConfig(
                identifier=DETECTOR_VIEW_HANDLE.workflow_id,
                # Pinned job number: the output keys carry it, and the
                # serial/pipelined runs must be byte-comparable.
                job_id=JobId(
                    source_name="panel_0",
                    job_number=uuid.UUID(int=7),
                ),
                params={},
            )
            raw.inject(
                FakeKafkaMessage(
                    json.dumps(
                        {
                            "kind": "start_job",
                            "config": config.model_dump(mode="json"),
                        }
                    ).encode(),
                    "dummy_livedata_commands",
                )
            )
            service.step()
            det = INSTRUMENT.detectors["panel_0"]
            ids_space = det.detector_number.reshape(-1)
            rng = np.random.default_rng(3)
            period_ns = int(1e9 / 14)
            for pulse in range(12):
                t_pulse = 1_700_000_000_000_000_000 + pulse * period_ns
                ids = rng.choice(ids_space, 256).astype(np.int32)
                toa = rng.uniform(0, 7.0e7, 256).astype(np.int32)
                payload = wire.encode_ev44(
                    det.source_name,
                    pulse,
                    np.array([t_pulse]),
                    np.array([0]),
                    toa,
                    pixel_id=ids,
                )
                raw.inject(FakeKafkaMessage(payload, "dummy_detector"))
                service.step()
            processor = service.processor
            if pipelined:
                assert processor._pipeline.flush(timeout=60.0)
            processor.finalize()
            return [
                message
                for message in producer.messages
                if message.key is not None
                and (b"image" in message.key or b"spectrum" in message.key)
            ]

        serial = run(pipelined=False)
        pipelined = run(pipelined=True)
        assert len(pipelined) == len(serial) > 0
        assert [m.key for m in pipelined] == [m.key for m in serial]
        assert [m.value for m in pipelined] == [m.value for m in serial]

"""Flight-recorder acceptance at the service level (ADR 0116):

- one scrape of a running service's registry exposes the publish
  dispatch counters (incl. the per-slice family), publish RTT
  histograms, pipeline queue depths, kafka/stream counters, HBM gauges
  and the jit compile-event histograms;
- the per-window trace correlates decode → prestage → tick_execute →
  fetch spans under shared trace ids and loads as Chrome trace_event;
- the da00 wire is byte-identical with telemetry on vs off (tracer
  enabled + scrapes racing the run vs tracer disabled) — the flight
  recorder observes the serving path, it must never perturb it.
"""

from __future__ import annotations

import json
import uuid

import numpy as np
import pytest

from esslivedata_tpu.config import JobId, WorkflowConfig
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
from esslivedata_tpu.services.detector_data import make_detector_service_builder
from esslivedata_tpu.services.fake_sources import PulsedRawSource
from esslivedata_tpu.telemetry import (
    REGISTRY,
    TRACER,
    parse_prometheus_text,
    render_text,
)


def run_service(*, pipelined: bool, scrape_every: int = 0):
    """Drive a real detector service over fakes; returns (data messages,
    scrapes collected mid-run)."""
    builder = make_detector_service_builder(
        instrument="dummy", batcher=NaiveMessageBatcher(), job_threads=1
    )
    builder.pipelined = pipelined
    raw = PulsedRawSource([])
    producer = FakeProducer()
    sink = KafkaSink(
        producer,
        make_default_serializer(builder.stream_mapping.livedata, "telem"),
    )
    service = builder.from_raw_source(raw, sink)
    config = WorkflowConfig(
        identifier=DETECTOR_VIEW_HANDLE.workflow_id,
        # Pinned job number: output keys carry it and the on/off runs
        # must be byte-comparable.
        job_id=JobId(source_name="panel_0", job_number=uuid.UUID(int=9)),
        params={},
    )
    raw.inject(
        FakeKafkaMessage(
            json.dumps(
                {"kind": "start_job", "config": config.model_dump(mode="json")}
            ).encode(),
            "dummy_livedata_commands",
        )
    )
    service.step()
    det = INSTRUMENT.detectors["panel_0"]
    ids_space = det.detector_number.reshape(-1)
    rng = np.random.default_rng(11)
    period_ns = int(1e9 / 14)
    scrapes = []
    for pulse in range(10):
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
        if scrape_every and pulse % scrape_every == 0:
            scrapes.append(render_text(REGISTRY.collect()))
    processor = service.processor
    if pipelined:
        assert processor._pipeline.flush(timeout=60.0)
    processor.finalize()
    data = [
        m
        for m in producer.messages
        if m.key is not None
        and (b"image" in m.key or b"spectrum" in m.key)
    ]
    return data, scrapes


class TestScrapeExposesTheStack:
    def test_one_scrape_carries_every_migrated_producer(self):
        TRACER.enabled = True
        try:
            _data, scrapes = run_service(pipelined=True, scrape_every=3)
        finally:
            TRACER.enabled = True
        assert scrapes
        parsed = parse_prometheus_text(scrapes[-1])
        # The acceptance list: dispatch counters (+ per-slice family),
        # RTT histograms, pipeline queue depths, kafka/stream counts,
        # HBM gauges, compile-event histograms, span decomposition.
        for family in (
            "livedata_publish_events",
            "livedata_publish_slice_events",
            "livedata_publish_rtt_seconds",
            "livedata_pipeline_queue_depth",
            "livedata_pipeline_stage_busy_seconds",
            "livedata_stream_messages",
            "livedata_kafka_sink_events",
            "livedata_hbm_bytes",
            "livedata_device_info",
            "livedata_jit_compiles_total",
            "livedata_jit_compile_seconds",
            "livedata_tick_span_seconds",
            "livedata_link_rtt_ewma_seconds",
            "livedata_link_policy",
        ):
            assert family in parsed, f"scrape missing {family}"
        # The producers actually produced: compile events fired for the
        # tick program, spans decomposed the windows, the pipeline
        # reported its stages.
        assert parse_one_total(parsed, "livedata_jit_compiles_total") >= 1
        # Every live processor names its device (tests pin the CPU).
        infos = parsed["livedata_device_info"].samples
        assert infos
        for _name, device, value in infos:
            assert value == 1 and device["platform"] == "cpu"
            assert device["device_kind"] and int(device["count"]) >= 1
        span_names = {
            labels.get("span")
            for _n, labels, _v in parsed["livedata_tick_span_seconds"].samples
        }
        assert {"decode", "prestage", "fetch"} <= span_names
        stages = {
            labels.get("stage")
            for _n, labels, _v in parsed[
                "livedata_pipeline_queue_depth"
            ].samples
        }
        assert {"decode", "stage", "step"} <= stages

    def test_trace_correlates_window_phases(self):
        TRACER.enabled = True
        TRACER.clear()
        run_service(pipelined=True)
        spans = TRACER.spans()
        by_trace: dict[int, list[str]] = {}
        for span in spans:
            by_trace.setdefault(span.trace_id, []).append(span.name)
        # At least one traced window shows the full decode -> prestage
        # -> device tick -> fetch chain under ONE id.
        full = [
            names
            for names in by_trace.values()
            if {"decode", "prestage", "tick_execute", "fetch"} <= set(names)
        ]
        assert full, f"no fully-correlated window: {by_trace}"
        # And the ring exports as Chrome trace_event JSON.
        doc = TRACER.chrome_trace()
        assert {e["name"] for e in doc["traceEvents"]} >= {
            "decode",
            "prestage",
            "tick_execute",
            "fetch",
        }


def parse_one_total(parsed, family: str) -> float:
    return sum(value for _n, _l, value in parsed[family].samples)


class TestWireParityTelemetryOnOff:
    @pytest.mark.parametrize("pipelined", [False, True])
    def test_da00_wire_byte_identical(self, pipelined):
        """Telemetry on (tracer recording + scrapes racing the run) vs
        off: same message keys, same bytes, same order."""
        TRACER.enabled = True
        try:
            on, _ = run_service(pipelined=pipelined, scrape_every=2)
            TRACER.enabled = False
            off, _ = run_service(pipelined=pipelined)
        finally:
            TRACER.enabled = True
        assert len(on) == len(off) > 0
        assert [m.key for m in on] == [m.key for m in off]
        assert [m.value for m in on] == [m.value for m in off]

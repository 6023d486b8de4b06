"""Flight-recorder acceptance at the service level (ADR 0116):

- one scrape of a running service's registry exposes the publish
  dispatch counters (incl. the per-slice family), publish RTT
  histograms, pipeline queue depths, kafka/stream counters, HBM gauges
  and the jit compile-event histograms;
- the per-window trace correlates decode → prestage → tick_execute →
  fetch spans under shared trace ids and loads as Chrome trace_event;
- the da00 wire is byte-identical with telemetry on vs off (tracer
  enabled + scrapes racing the run vs tracer disabled) — the flight
  recorder observes the serving path, it must never perturb it;
- the serial loop's spans tile its tick: the ring stays flat, ``tick``
  is the leaf spans plus ``unspanned``, ``d2h`` lies inside ``fetch``,
  and a profiler session holds every span's ``TraceAnnotation`` twin.
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
        # pipeline queue depths, kafka/stream counts,
        # HBM gauges, compile-event histograms, span decomposition.
        for family in (
            "livedata_publish_events",
            "livedata_publish_slice_events",
            "livedata_pipeline_queue_depth",
            "livedata_pipeline_stage_busy_seconds",
            "livedata_stream_messages",
            "livedata_kafka_sink_events",
            "livedata_hbm_bytes",
            "livedata_device_info",
            "livedata_jit_compiles_total",
            "livedata_jit_compile_seconds",
            "livedata_tick_span_seconds",
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


#: Every span the serial loop records into the ring (``publish_execute``
#: is the combined-publish path's dispatch, not on this service's tick).
SERIAL_RING_SPANS = (
    "decode", "flatten", "h2d", "tick_execute", "publish_execute",
    "fetch", "finalize", "sink",
)


def span_sums() -> dict[str, tuple[float, int]]:
    family = REGISTRY.get("livedata_tick_span_seconds")
    return {
        name: (family.sum(span=name), family.count(span=name))
        for name in (*SERIAL_RING_SPANS, "tick", "unspanned", "d2h")
    }


def serial_run_deltas():
    """(ring spans, span name -> (seconds, count) added) of one serial
    run of the toy detector service."""
    TRACER.enabled = True
    TRACER.clear()
    before = span_sums()
    run_service(pipelined=False)
    after = span_sums()
    deltas = {
        name: (after[name][0] - before[name][0],
               after[name][1] - before[name][1])
        for name in after
    }
    return TRACER.spans(), deltas


class TestSerialTickIsTiledBySpans:
    def test_ring_stays_flat(self):
        """No two ring spans of one thread overlap: the benchmark's
        ``name_gap`` adds the ring up, and a nested span would count
        twice. Enclosing and contained phases are aggregates."""
        spans, _ = serial_run_deltas()
        assert {"decode", "flatten", "h2d", "tick_execute", "fetch",
                "finalize", "sink"} <= {s.name for s in spans}
        assert not {"tick", "unspanned", "d2h"} & {s.name for s in spans}
        by_thread: dict[str, list] = {}
        for span in spans:
            by_thread.setdefault(span.thread, []).append(span)
        for thread_spans in by_thread.values():
            thread_spans.sort(key=lambda s: s.start_s)
            for earlier, later in zip(thread_spans, thread_spans[1:]):
                assert (
                    earlier.start_s + earlier.duration_s <= later.start_s
                ), f"{earlier.name} overlaps {later.name}"

    def test_tick_is_the_leaf_spans_plus_unspanned(self):
        spans, deltas = serial_run_deltas()
        ticks = {s.trace_id for s in spans}
        assert deltas["tick"][1] == deltas["unspanned"][1] == len(ticks)
        leaves = sum(deltas[name][0] for name in SERIAL_RING_SPANS)
        assert deltas["tick"][0] == pytest.approx(
            leaves + deltas["unspanned"][0], abs=1e-9
        )
        # ... and tick by tick from the ring: the spans of one tick lie
        # inside its wall time, so they never sum to more than it.
        assert leaves <= deltas["tick"][0]

    def test_d2h_is_the_copy_inside_fetch(self):
        _, deltas = serial_run_deltas()
        assert deltas["d2h"][1] == deltas["fetch"][1] > 0
        assert 0.0 < deltas["d2h"][0] <= deltas["fetch"][0]

    def test_a_one_group_service_dispatches_every_group_alone(self):
        """``livedata_tick_groups_total``: one count per tick group at
        its dispatch. This service has one group a tick, so nothing is
        ever dispatched ahead (the benchmark's ``groups_ahead_share``
        then reads 0, not nothing)."""
        from esslivedata_tpu.ops.publish import METRICS

        family = REGISTRY.get("livedata_tick_groups_total")
        before = {
            how: family.value(dispatched=how) for how in ("ahead", "alone")
        }
        ticks_before = METRICS.snapshot()["tick_publishes"]
        _, deltas = serial_run_deltas()
        ticks = METRICS.snapshot()["tick_publishes"] - ticks_before
        assert family.value(dispatched="ahead") == before["ahead"]
        assert family.value(dispatched="alone") - before["alone"] == ticks
        # Compile rounds record no spans; every other group has its pair.
        assert 0 < deltas["tick_execute"][1] == deltas["fetch"][1] <= ticks

    def test_a_one_group_service_publishes_every_result_at_the_end(self):
        """``livedata_job_publishes_total``: one count per job result
        by the way it left (ADR 0128). One group a tick has no group
        behind it to publish under: ``end`` alone, one ``finalize`` and
        one ``sink`` a tick (the benchmark's ``publishes_ahead_share``
        then reads 0, not nothing)."""
        family = REGISTRY.get("livedata_job_publishes_total")
        before = {when: family.value(when=when) for when in ("ahead", "end")}
        spans, deltas = serial_run_deltas()
        ticks = len({s.trace_id for s in spans})
        assert family.value(when="ahead") == before["ahead"]
        assert family.value(when="end") - before["end"] == ticks
        assert deltas["finalize"][1] == deltas["sink"][1] == ticks

    def test_fetch_enqueues_the_copies_before_it_waits(self):
        """``device_get`` alone enqueues every copy and then waits; the
        split into wait and copy must not lose that, or each fetch pays
        a host wake-up with the chip idle (PERF.md section 6)."""
        import numpy as np

        from esslivedata_tpu.ops.publish import fetch_outputs

        calls = []

        class Output:
            def __init__(self, name):
                self.name = name

            def copy_to_host_async(self):
                calls.append(("enqueue", self.name))

            def block_until_ready(self):
                calls.append(("wait", self.name))
                return self

            def __array__(self, *args, **kwargs):
                calls.append(("read", self.name))
                return np.zeros(1)

        fetch_outputs((Output("packed"), (Output("static"),)))
        first_wait = calls.index(("wait", "packed"))
        assert calls[:first_wait] == [
            ("enqueue", "packed"), ("enqueue", "static")
        ]
        assert [c for c in calls if c[0] == "read"] == [
            ("read", "packed"), ("read", "static")
        ]
        assert calls.index(("read", "packed")) > calls.index(
            ("wait", "static")
        )

    def test_hold_is_observed_once_per_batch_and_rides_decode(self):
        hold = REGISTRY.get("livedata_batch_hold_seconds")
        before = hold.total_count()
        spans, deltas = serial_run_deltas()
        decodes = [s for s in spans if s.name == "decode"]
        assert hold.total_count() - before == deltas["decode"][1]
        assert deltas["decode"][1] == len(decodes) > 0
        assert all(s.args["hold_us"] >= 0 for s in decodes)

    def test_staging_spans_carry_their_counts(self):
        spans, _ = serial_run_deltas()
        flattens = [s for s in spans if s.name == "flatten"]
        h2ds = [s for s in spans if s.name == "h2d"]
        assert len(flattens) == len(h2ds) > 0
        for span in flattens:
            assert span.args == {"events": 256, "padded": 4096}
        for span in h2ds:
            assert span.args == {"bytes": 4096 * 4}  # int32 flat indices

    def test_profiler_session_holds_every_span_twin(self, tmp_path):
        """While a ``jax.profiler`` session runs, each ``TRACER.span``
        is in the profiler's own trace as a ``TraceAnnotation`` with the
        span's trace id and counts, on the profiler's clock."""
        import jax
        from jax.profiler import ProfileData

        TRACER.enabled = True
        run_service(pipelined=False)  # compiles stay out of the session
        TRACER.clear()
        jax.profiler.start_trace(str(tmp_path))
        try:
            run_service(pipelined=False)
        finally:
            jax.profiler.stop_trace()
        found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            pytest.skip("the CPU backend wrote no trace")
        ring = {}
        for span in TRACER.spans():
            ring[span.name, span.trace_id] = (
                ring.get((span.name, span.trace_id), 0) + 1
            )
        twins, stats_of = {}, {}
        for plane in ProfileData.from_file(str(found[-1])).planes:
            for line in plane.lines:
                for event in line.events:
                    if event.name not in SERIAL_RING_SPANS:
                        continue
                    stats = dict(event.stats)
                    if "trace_id" not in stats:
                        continue
                    key = (event.name, int(stats["trace_id"]))
                    twins[key] = twins.get(key, 0) + 1
                    stats_of[event.name] = stats
        if not twins:
            pytest.skip("the CPU backend's trace has no host plane")
        assert twins == ring
        assert stats_of["flatten"]["events"] == 256
        assert stats_of["h2d"]["bytes"] == 4096 * 4
        assert "hold_us" in stats_of["decode"]


def parse_one_total(parsed, family: str) -> float:
    return sum(value for _n, _l, value in parsed[family].samples)


class TestWireParityTelemetryOnOff:
    @pytest.mark.parametrize("pipelined", [False, True])
    def test_da00_wire_byte_identical(self, pipelined):
        """Telemetry on (tracer recording + scrapes racing the run) vs
        off: same message keys, same bytes, same order."""
        TRACER.enabled = True
        staged = REGISTRY.get("livedata_staged_events_total")
        sink_bytes = REGISTRY.get("livedata_sink_bytes_total")
        try:
            before = span_sums()
            counted = (staged.total(), sink_bytes.total())
            on, _ = run_service(pipelined=pipelined, scrape_every=2)
            recorded = span_sums()
            TRACER.enabled = False
            off, _ = run_service(pipelined=pipelined)
            silent = span_sums()
        finally:
            TRACER.enabled = True
        # The "on" run went through every new site (staging spans, the
        # split fetch, the tick totals, the sink's phase counters); the
        # "off" run recorded no span at any of them.
        for name in ("flatten", "h2d", "fetch", "d2h", "tick", "sink"):
            assert recorded[name][1] > before[name][1], name
        assert staged.total() > counted[0]
        assert sink_bytes.total() > counted[1]
        assert silent == recorded
        assert len(on) == len(off) > 0
        assert [m.key for m in on] == [m.key for m in off]
        assert [m.value for m in on] == [m.value for m in off]

"""The host-bound Q tick from inside, as files of the benchmark: seven
per-layer metrics that read the accumulate phase's aggregates
(``accumulate_wait``, ``accumulate``, ``pool_queue``, ``stage_wait``,
``h2d_copy`` of ``livedata_tick_span_seconds``) and the sink's
``livedata_sink_serialize_seconds_total{step}``. Data only: each is a
``prometheus`` reader over the ``decode`` span's count, lists the cells
whose traced line carries it, and reads nothing from a program that
lacks the span or the counter. The last test drives the program's own
code paths on the CPU (counts and inequalities only, no timing claim)
and reads its exposition through the harness's reader."""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest
from bench_support import REPO
from harness import manifest, prom
from harness import metrics as layer_metrics

Q_CELLS = ["loki_iq.paced14", "dream_powder.paced14", "bifrost_qe.paced14"]
ALL_CELLS = ["nmx_panels.paced14", "dream_banks.paced14", *Q_CELLS]
HOST, SINK = "stage + tick program, host side", "finalize + da00 encode + sink"
SPANS, STEPS = "livedata_tick_span_seconds", "livedata_sink_serialize_seconds"
PER = {"family": SPANS, "part": "count", "labels": {"span": "decode"}}

#: metric -> (layer, source, the one term it reads, the cells that must list it)
METRICS = {
    "accumulate_wait_ms.paced": (HOST, "program_span", (SPANS, "span", "accumulate_wait"), Q_CELLS),
    "accumulate_ms.paced": (HOST, "program_span", (SPANS, "span", "accumulate"), Q_CELLS),
    "pool_queue_ms.paced": (HOST, "program_span", (SPANS, "span", "pool_queue"), Q_CELLS),
    "stage_wait_ms.paced": (HOST, "program_span", (SPANS, "span", "stage_wait"), ["bifrost_qe.paced14"]),
    "h2d_copy_ms.paced": (HOST, "program_span", (SPANS, "span", "h2d_copy"), ALL_CELLS),
    "sink_da00_ms.paced": (SINK, "program_counter", (STEPS, "step", "da00"), ALL_CELLS),
    "sink_wire_ms.paced": (SINK, "program_counter", (STEPS, "step", "wire"), ALL_CELLS),
}


def spec_of(metric: str) -> dict:
    return {**json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text()), "name": metric}


@pytest.mark.parametrize("metric", METRICS)
def test_the_metric_is_data_only_and_lists_its_cells(metric):
    layer, source, (family, label, value), cells = METRICS[metric]
    entry = next(m for m in manifest.load_manifest(REPO)["per_layer"] if m["name"] == metric)
    assert set(cells) <= set(entry["workloads"])  # a later cell may join: no equality here
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"]) == (
        layer, "ms", "lower", source, "freshness_p50_ms")
    reader = spec_of(metric)["reader"]
    assert reader["kind"] == "prometheus" and "absent_is_zero" not in reader  # a program without it: no value
    term = {"family": family, "labels": {label: value}}
    if family == SPANS:
        term["part"] = "sum"
    assert (reader["terms"], reader["per"], reader["scale"]) == ([term], PER, 1000)


@pytest.mark.parametrize("metric", METRICS)
def test_the_program_has_the_family_and_the_label(metric):
    from esslivedata_tpu.kafka.sink import make_default_serializer
    from esslivedata_tpu.kafka.stream_mapping import LivedataTopics
    from esslivedata_tpu.telemetry import REGISTRY

    _, _, (family, label, value), _ = METRICS[metric]
    if family == SPANS:
        histogram = REGISTRY.get(family)
        assert histogram.collect().kind == "histogram" and histogram._labelnames == (label,)
        assert f"/{value}" in histogram.help  # named among the aggregates on the scrape's HELP line
    else:
        make_default_serializer(LivedataTopics.for_instrument("dummy"))  # seeds both steps with 0
        counter = REGISTRY.get(f"{family}_total")
        assert counter.collect().kind == "counter"
        assert {label: value} in [labels for labels, _ in counter.items()]


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_each_cell_loads_the_metrics_listed_for_it(cell):
    listed = {m["name"] for m in manifest.load_cell(REPO, cell).per_layer}
    expected = {name for name, (*_, cells) in METRICS.items() if cell in cells}
    assert expected <= listed
    if cell not in Q_CELLS:  # the detector cells leave one job a window to the serial branch
        assert not listed & {"accumulate_wait_ms.paced", "pool_queue_ms.paced", "stage_wait_ms.paced"}


def test_a_program_without_the_spans_or_the_counter_reads_nothing():
    """The parent's scrape has ``h2d`` and the sink's phases and none
    of what this PR adds: every one of the seven readers returns None,
    and the line leaves the metric out."""

    def scrape(windows):
        return [
            (f"{SPANS}_count", {"span": "decode"}, float(windows)),
            (f"{SPANS}_sum", {"span": "h2d"}, 0.150 * windows),
            (f"{SPANS}_sum", {"span": "unspanned"}, 0.160 * windows),
            ("livedata_sink_seconds_total", {"phase": "serialize"}, 0.085 * windows),
        ]

    specs = [spec_of(metric) for metric in METRICS]
    ctx = {"scrape_start": scrape(4), "scrape_end": scrape(55), "window_s": 51.0}
    assert layer_metrics.evaluate(specs, ctx) == {}
    added = {"accumulate_wait": 0.155, "accumulate": 0.320, "pool_queue": 0.001,
             "stage_wait": 0.150, "h2d_copy": 0.020}
    for at in ("scrape_start", "scrape_end"):  # and the change's: all seven are there
        windows = ctx[at][0][2]
        ctx[at] = ctx[at] + [(f"{SPANS}_sum", {"span": name}, s * windows) for name, s in added.items()] + [
            (f"{STEPS}_total", {"step": "da00"}, 0.070 * windows),
            (f"{STEPS}_total", {"step": "wire"}, 0.004 * windows),
        ]
    got = {name: entry["value"] for name, entry in layer_metrics.evaluate(specs, ctx).items()}
    assert got == {
        "accumulate_wait_ms.paced": pytest.approx(155.0), "accumulate_ms.paced": pytest.approx(320.0),
        "pool_queue_ms.paced": pytest.approx(1.0), "stage_wait_ms.paced": pytest.approx(150.0),
        "h2d_copy_ms.paced": pytest.approx(20.0), "sink_da00_ms.paced": pytest.approx(70.0),
        "sink_wire_ms.paced": pytest.approx(4.0),
    }


def test_the_readers_find_what_the_program_records(monkeypatch):
    """One window of the program's own code on the CPU: two private
    jobs on a two-thread pool, both reading one stream's wire through
    the stage-once slot (one ships it, one waits), and their two da00
    results through the sink. The harness's readers, over the
    registry's own exposition, find all seven and the inequalities the
    acceptance criteria name hold."""
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.core.message import Message, StreamId, StreamKind
    from esslivedata_tpu.core.timestamp import Timestamp
    from esslivedata_tpu.kafka.sink import FakeProducer, KafkaSink, make_default_serializer
    from esslivedata_tpu.kafka.stream_mapping import LivedataTopics
    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.event_batch import stage_raw
    from esslivedata_tpu.preprocessors.event_data import StagedEvents
    from esslivedata_tpu.telemetry import REGISTRY, TRACER, render_text
    from esslivedata_tpu.utils import DataArray, Variable
    from esslivedata_tpu.workflows import WorkflowFactory

    monkeypatch.setattr(TRACER, "enabled", True)
    both_in = threading.Barrier(2)

    class RawReader:
        """A workflow that stages its stream's raw wire, as a Q job does."""

        def __init__(self):
            self.events = 0

        def accumulate(self, data):
            for staged in data.values():
                both_in.wait(30)  # both jobs are on their pool thread before either stages
                pid, _ = stage_raw(staged.batch, staged.cache)
                self.events += int(pid.shape[0])

        def finalize(self):
            return {"total": DataArray(Variable(np.asarray(float(self.events)), (), "counts"), name="total")}

        def clear(self):
            self.events = 0

        def set_context(self, ctx):
            pass

    factory = WorkflowFactory()
    specs = [WorkflowSpec(instrument="dummy", name=name, source_names=["det0"]) for name in ("first", "second")]
    for spec in specs:
        factory.register_spec(spec).attach_factory(lambda *, source_name, params: RawReader())
    manager = JobManager(job_factory=JobFactory(factory), job_threads=2)
    for spec in specs:
        manager.schedule_job(WorkflowConfig(identifier=spec.identifier, job_id=JobId(source_name="det0")))
    rng = np.random.default_rng(35)
    batch = EventBatch.from_arrays(
        rng.integers(0, 64, 5000).astype(np.int32), rng.uniform(0, 7e7, 5000).astype(np.float32))
    window = StagedEvents(batch=batch, first_timestamp=None, last_timestamp=None, n_chunks=1)
    sink = KafkaSink(FakeProducer(), make_default_serializer(LivedataTopics.for_instrument("dummy")))

    def scrape():
        return prom.parse(render_text(REGISTRY.collect()))

    before = scrape()
    trace_id = TRACER.new_trace()
    started = time.perf_counter()
    with TRACER.bind(trace_id):
        with TRACER.span("decode"):
            pass
        results = manager.process_jobs({"det0": window}, start=Timestamp.from_ns(0), end=Timestamp.from_ns(10))
        with TRACER.span("sink"):
            sink.publish_messages([
                Message(timestamp=Timestamp.from_ns(10),
                        stream=StreamId(kind=StreamKind.LIVEDATA_DATA, name=f"job{i}/total"),
                        value=result.outputs["total"])
                for i, result in enumerate(results)
            ])
        TRACER.finish_tick(trace_id, time.perf_counter() - started, tiled=True)
    manager.shutdown()
    assert len(results) == 2
    # the ring has no new span name: the aggregates are histogram only
    assert {s.name for s in TRACER.spans(trace_id)} == {"decode", "h2d", "finalize", "sink"}
    names = [*METRICS, "h2d_ms.paced", "sink_encode_ms.paced", "tick_unspanned_ms.paced", "tick_ms.paced"]
    ctx = {"scrape_start": before, "scrape_end": scrape(), "window_s": 1.0}
    got = {name: entry["value"] for name, entry in layer_metrics.evaluate([spec_of(n) for n in names], ctx).items()}
    assert set(got) == set(names)  # all seven found something, beside the four they are read against
    assert 0.0 < got["h2d_copy_ms.paced"] <= got["h2d_ms.paced"]
    assert 0.0 < got["sink_da00_ms.paced"] + got["sink_wire_ms.paced"] <= got["sink_encode_ms.paced"]
    assert got["sink_da00_ms.paced"] > 0.0 and got["sink_wire_ms.paced"] > 0.0
    assert 0.0 < got["accumulate_ms.paced"] <= 2 * got["accumulate_wait_ms.paced"]
    assert 0.0 <= got["pool_queue_ms.paced"] <= 2 * got["accumulate_wait_ms.paced"]
    # one job ships the wire, the other stands at the slot's latch for (most of) that h2d
    assert 0.0 < got["stage_wait_ms.paced"] <= got["accumulate_wait_ms.paced"]
    # the wait counts toward the loop thread's coverage: what is left is the glue between the phases
    assert abs(got["tick_unspanned_ms.paced"]) < got["tick_ms.paced"] - got["accumulate_wait_ms.paced"] + 1e-6

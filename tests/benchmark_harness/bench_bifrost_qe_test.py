"""BIFROST whole, as files of the benchmark: the configuration, its cell
and its reference kind ``spectrometer_qe_merged``, and the package
against that reference: every (pixel, TOA bin) of both tables at full
size, and the data-reduction service itself (real adapters, the merged
route, the adaptive batcher, the job manager, the sink) fed 45 sources
and the monitor through the harness's plumbing on the CPU (counts and
exactness only)."""

from __future__ import annotations

import ast
import copy
import dataclasses
import json
import time

import numpy as np
import pytest
from bench_support import REPO
from harness import bench, manifest, reference, results
from harness.traffic import Traffic

CELL = "bifrost_qe.paced14"
KIND = "spectrometer_qe_merged"
ARCS, CHANNELS, TRIPLET = 5, 9, 300
N_PIXELS = ARCS * CHANNELS * TRIPLET
TRAFFIC = Traffic(pulse_hz=14, events_per_pulse=512, out_of_range_probes=6, pool_pulses=5, toa_bins=320)
WINDOWS = ((0, 3), (3, 7))  # the pulses of two publishes
FAULTS = ["source_dropped", "frame_offset_zero", "axes_transposed", "monitor_twice",
          "toa_bin_off_by_one", "quotient_bfloat16"]
OLD_CELLS = ["nmx_panels.paced14", "dream_banks.paced14", "loki_iq.paced14", "dream_powder.paced14"]
QE_OUTPUTS = ["sqw_current", "sqw_cumulative", "sqw_normalized", "counts_current", "monitor_counts_current"]
ELASTIC_OUTPUTS = ["qmap_current", "qmap_cumulative", "qmap_normalized", "counts_current"]


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(REPO, CELL)


@pytest.fixture(scope="module")
def kind(cell):
    return cell.kinds[KIND]


def test_the_cell_is_in_the_manifest_whole_and_as_files(cell):
    assert manifest.check(REPO) == []
    doc = manifest.load_manifest(REPO)
    entry = next(c for c in doc["configs"] if c["name"] == "bifrost_qe")
    assert entry["reduced"] == [] and cell.config["reduced"] == {}  # every triplet, every pixel
    assert len(entry["source"]) <= 200 and "bifrost/specs.py" in entry["source"] and "45 triplets" in entry["source"]
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "bifrost_qe", "paced14_toa320")
    assert cell.config["service"] == "data_reduction" and cell.config["service_flags"] == []
    assert cell.config["toa_bins"] == cell.traffic.toa_bins == 320 and cell.traffic.pool_pulses == 13
    assert cell.traffic.messages_per_pulse == 1 and cell.traffic.out_of_range_probes == 6
    # a multiple of 64, so that the monitor's share is whole; the merged batch stays inside its bucket
    events = cell.traffic.events_per_pulse
    assert events % 64 == 0
    merged = 45 * 14 * events
    bucket = 1 << (merged - 1).bit_length()
    assert 0.75 * bucket <= merged * 5 / 4 <= bucket  # four fifths of the bucket's top, rounded down
    detectors = [s for s in cell.config["streams"] if s.get("kind", "detector") == "detector"]
    assert [s["name"] for s in detectors] == [f"triplet_{a}_{c}" for a in range(ARCS) for c in range(CHANNELS)]
    assert [(s["first_id"], s["n_pixels"]) for s in detectors] == [(1 + TRIPLET * i, TRIPLET) for i in range(45)]
    assert sum(s["n_pixels"] for s in detectors) == N_PIXELS == 13_500
    (monitor,) = [s for s in cell.config["streams"] if s.get("kind") == "monitor"]
    assert (monitor["name"], monitor["topic"], monitor["rate_share"]) == ("monitor_1", "bifrost_monitor", 5.625)
    assert monitor["rate_share"] * events * 8 == merged / 14  # an eighth of the merged detector rate
    qe, elastic = cell.config["jobs"]
    for job, workflow, which, outputs in ((qe, "qe_map", "qe", QE_OUTPUTS), (elastic, "elastic_qmap", "elastic", ELASTIC_OUTPUTS)):
        assert job["workflow"] == ["spectrometer", workflow] and job["view"]["map"] == which
        assert job["job_source"] == "detector" and job["stream"] == "triplet_0_0"
        assert job["params"] == {}  # started as a dashboard starts it
        assert job["aux_source_names"] == {"monitor": "monitor_1"}
        assert job["view"]["streams"] == [s["name"] for s in detectors]
        assert job["outputs"] == {"arrays": outputs}
    assert cell.config["outputs"]["prefix_total"] == "counts_current"
    assert cell.config["state_bytes"] == 2 * (2 * 320 * 13_568 * 2) + (2 * 4_800 + 2 + 2 * 10_000 + 2) * 4
    for key in ("deployment", "guarantees", "left_out", "assumed", "state_reckoning"):
        assert cell.config[key], key
    assert any("contiguous block of 300" in text for text in cell.config["assumed"])  # the declared departure
    assert set(cell.limits) == set(reference.check_names(cell.config, cell.kinds))
    assert cell.limits == manifest.load_cell(REPO, "nmx_panels.paced14").limits  # the same exact limits
    why = json.loads((REPO / "benchmark" / "limits" / f"{CELL}.json").read_text())["why"]
    assert set(why) == set(cell.limits)
    for metric in ("freshness_p50_ms", "freshness_p95_ms"):
        assert CELL in next(m for m in doc["end_to_end"] if m["name"] == metric)["workloads"]
    listed = {m["name"] for m in cell.per_layer}
    # every general metric the Q cells report, the three shares of the Q kernels' choices, and this PR's
    # three; not the two metrics that bench_loki_iq_test.py holds to LOKI's cell alone, nor what a Q cell
    # has no sample for
    assert listed >= {f"{name}.paced" for name in (
        "decode_ms", "jobs_ms", "device_wait_ms", "sink_ms", "compiles_in_window", "tick_device_ms",
        "tick_roofline", "device_idle", "tick_ms", "tick_unspanned_ms", "hold_ms", "escalations_in_window",
        "h2d_ms", "pad_share", "d2h_ms", "sink_encode_ms", "sink_write_ms", "sink_mb", "publishes_ahead_share",
        "q_lookup_windowed_share", "q_lookup_gather_share", "q_bincount_scatter_share",
        "land_ms", "messages_per_tick", "stage_once_hit_share",
    )} | {"generator_late_p95_ms"}
    assert not listed & {"private_windows_share.paced", "q_step_ms.paced", "flatten_ms.paced", "groups_ahead_share.paced"}


@pytest.mark.parametrize(
    "metric, layer, unit, better, source, terms, per, scale, cells",
    [
        ("land_ms.paced", "consume + decode + batch", "ms", "lower", "program_span",
         [{"family": "livedata_tick_span_seconds", "part": "sum", "labels": {"span": "land"}}],
         {"family": "livedata_tick_span_seconds", "part": "count", "labels": {"span": "decode"}}, 1000,
         [CELL, *OLD_CELLS]),
        ("messages_per_tick.paced", "consume + decode + batch", "messages", "lower", "program_counter",
         [{"family": "livedata_preprocessed_messages"}],
         {"family": "livedata_tick_span_seconds", "part": "count", "labels": {"span": "decode"}}, None,
         [CELL, *OLD_CELLS]),
        ("stage_once_hit_share.paced", "stage + tick program, host side", "%", "higher", "program_counter",
         [{"family": "livedata_event_cache_events", "labels": {"kind": "hits"}}],
         {"family": "livedata_event_cache_events", "labels": {"kind": "lookups"}}, 100,
         [CELL]),
    ],
)
def test_the_new_metrics_are_data_only(metric, layer, unit, better, source, terms, per, scale, cells):
    entry = next(m for m in manifest.load_manifest(REPO)["per_layer"] if m["name"] == metric)
    assert set(cells) <= set(entry["workloads"])  # a later cell may join: no equality here
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"], entry["moves"]) == (
        layer, unit, better, source, "freshness_p50_ms")
    doc = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    reader = doc["reader"]
    assert reader["kind"] == "prometheus" and "absent_is_zero" not in reader  # a program without it: no value
    assert (reader["terms"], reader["per"], reader.get("scale")) == (terms, per, scale)


def test_a_program_without_the_span_or_the_kind_reads_nothing():
    """The parent's scrape: no ``land`` span, no ``lookups`` kind. The
    two readers return None, and the third reads what was always there."""
    from harness import metrics as layer_metrics

    def scrape(windows):
        return [
            ("livedata_tick_span_seconds_count", {"span": "decode"}, float(windows)),
            ("livedata_tick_span_seconds_sum", {"span": "decode"}, 0.02 * windows),
            ("livedata_preprocessed_messages_total", {"stream": "detector"}, 630.0 * windows),
            ("livedata_preprocessed_messages_total", {"stream": "monitor_1"}, 14.0 * windows),
            ("livedata_event_cache_events_total", {"kind": "hits"}, 1.0 * windows),
            ("livedata_event_cache_events_total", {"kind": "misses"}, 1.0 * windows),
        ]

    specs = [
        {**json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text()), "name": name}
        for name in ("land_ms.paced", "messages_per_tick.paced", "stage_once_hit_share.paced")
    ]
    ctx = {"scrape_start": scrape(4), "scrape_end": scrape(55), "window_s": 51.0}
    assert layer_metrics.evaluate(specs, ctx) == {"messages_per_tick.paced": {"value": 644.0, "unit": "messages"}}
    for at in ("scrape_start", "scrape_end"):  # and the change's: both are there
        windows = ctx[at][0][2]
        ctx[at] = ctx[at] + [
            ("livedata_tick_span_seconds_sum", {"span": "land"}, 0.012 * windows),
            ("livedata_event_cache_events_total", {"kind": "lookups"}, 2.0 * windows),
        ]
    got = {name: entry["value"] for name, entry in layer_metrics.evaluate(specs, ctx).items()}
    assert got == {"land_ms.paced": pytest.approx(12.0), "messages_per_tick.paced": 644.0,
                   "stage_once_hit_share.paced": 50.0}


def test_the_kinds_module_imports_nothing_of_the_program():
    tree = ast.parse((REPO / "benchmark" / "references" / f"{KIND}.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "numpy", "harness"}


def test_the_kind_states_its_protocol(kind, cell):
    assert list(kind.faults()) == FAULTS
    assert set(cell.config["outputs"]["arrays"]) == set(kind.CHECKS)
    for output in kind.CHECKS:
        if output.endswith("_normalized"):
            assert kind.check(output) == "image_bins_wrong"
            rel, abs_, reason = kind.tolerance(output)
            assert 2.0**-24 < rel < 2.0**-12 and abs_ == 0.0 and "bfloat16" in reason  # between the two precisions
        else:
            assert kind.tolerance(output) is None and kind.check(output) == "spectrum_bins_wrong"
    qe, elastic = cell.config["jobs"]
    # 8 B in, a 2 B entry, 8 B of bin, over all 45 merged streams: the harness hands over one stream's events
    assert kind.work_bytes(qe, cell.config, 1000, 0) == kind.work_bytes(elastic, cell.config, 1000, 0) == 45 * 1000 * 18
    assert kind.work_bytes(qe, cell.config, 0, 1) == 4 * 4_800 * 4 + 4 * (2 * 4_800 + 2)
    assert kind.work_bytes(elastic, cell.config, 0, 1) == 4 * 10_000 * 4 + 4 * (2 * 10_000 + 2)
    assert bench.events_per_pulse(cell.config, cell.traffic) == dict.fromkeys(
        ("qe_map", "elastic_qmap"), cell.traffic.events_per_pulse)


def test_the_configuration_mirrors_the_package(cell):
    from esslivedata_tpu.config.instrument import instrument_registry
    from esslivedata_tpu.config.instruments.bifrost import specs
    from esslivedata_tpu.config.streams import get_stream_mapping
    from esslivedata_tpu.kafka.stream_mapping import MERGED_DETECTOR_STREAM, InputStreamKey
    from esslivedata_tpu.ops import qhistogram
    from esslivedata_tpu.workflows.elastic_qmap import ElasticQMapParams
    from esslivedata_tpu.workflows.qe_spectroscopy import QESpectroscopyParams
    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    assert instrument_registry["bifrost"] is specs.INSTRUMENT and specs.INSTRUMENT.merge_detectors
    mapping = get_stream_mapping(specs.INSTRUMENT)
    detectors = [s for s in cell.config["streams"] if s.get("kind", "detector") == "detector"]
    assert [s["name"] for s in detectors] == list(specs.INSTRUMENT.detectors) == list(specs.BANK_DETECTOR_NUMBERS)
    for stream in detectors:  # the 45 source names, as the package's stream LUT has them
        key = InputStreamKey(topic=cell.config["detector_topic"], source_name=stream["wire_source"])
        assert mapping.detectors[key] == stream["name"]
        ids = specs.INSTRUMENT.detectors[stream["name"]].detector_number.reshape(-1)
        assert (int(ids[0]), int(ids[-1]), ids.size) == (
            stream["first_id"], stream["first_id"] + stream["n_pixels"] - 1, stream["n_pixels"])
    (monitor,) = [s for s in cell.config["streams"] if s.get("kind") == "monitor"]
    assert mapping.monitors[InputStreamKey(topic=monitor["topic"], source_name=monitor["wire_source"])] == monitor["name"]
    qe, elastic = cell.config["jobs"]
    assert qe["job_source"] == elastic["job_source"] == MERGED_DETECTOR_STREAM == specs.MERGED_STREAM
    for job, handle in ((qe, specs.QE_HANDLE), (elastic, specs.ELASTIC_QMAP_HANDLE)):
        spec = workflow_registry[handle.workflow_id]
        assert [spec.namespace, spec.name] == job["workflow"] and spec.service == "data_reduction"
        assert spec.source_names == [job["job_source"]] and spec.aux_source_names == {"monitor": ["monitor_1"]}
        assert set(spec.outputs) == set(job["outputs"]["arrays"])
    # the views state the numbers that the params models default to
    period = 1e9 / 14
    p, view = QESpectroscopyParams(), qe["view"]
    assert view["q"] == {"bins": p.q_bins, "min": p.q_min, "max": p.q_max} == {"bins": 80, "min": 0.2, "max": 2.6}
    assert view["e"] == {"bins": p.e_bins, "min": p.e_min, "max": p.e_max} == {"bins": 60, "min": -3.0, "max": 6.0}
    assert (view["toa_bins"], view["toa_offset_ns"], view["l1_m"]) == (p.toa_bins, p.toa_offset_ns, p.l1) == (
        320, 2 * period, 162.0)
    assert (p.toa_range.low, p.toa_range.high) == (0.0, period)  # an axis a wire event can fall into
    p, view = ElasticQMapParams(), elastic["view"]
    for axis, params in ((view["axis1"], p.axis1), (view["axis2"], p.axis2)):
        assert axis == {"component": params.component, "bins": params.bins, "low": params.low, "high": params.high}
    assert (view["axis1"]["component"], view["axis2"]["component"]) == ("Qx", "Qz")
    assert (view["e_window_mev"], view["toa_bins"], view["toa_offset_ns"], view["l1_m"]) == (
        p.e_window_mev, p.toa_bins, p.toa_offset_ns, p.l1) == (0.25, 320, 2 * period, 162.0)
    assert (p.toa_range.low, p.toa_range.high) == (0.0, period)
    for job in (qe, elastic):
        assert (job["view"]["e_from_v2"], job["view"]["k_from_v"]) == (qhistogram.E_FROM_V2, qhistogram.K_FROM_V)
        geometry = job["view"]["geometry"]
        assert tuple(geometry["arcs"]["ef_mev"]) == specs.ARC_EF_MEV
        assert (geometry["arcs"]["l2_m"]["first"], geometry["arcs"]["l2_m"]["step"]) == specs.ARC_L2_M
        assert tuple(geometry["channels"]["two_theta_centre_deg"]) == specs.CHANNEL_TWO_THETA_DEG
        assert geometry["channels"]["half_spread_deg"] == specs.CHANNEL_HALF_SPREAD_DEG
        assert tuple(geometry["tubes"]["azimuth_deg"]) == specs.TUBE_AZIMUTH_DEG
        assert (geometry["channels"]["count"], geometry["pixels_per_tube"]) == (specs.N_CHANNELS, specs.PIXELS_PER_TUBE)


@pytest.mark.parametrize("which", ["qe", "elastic"])
def test_the_references_bins_are_the_packages_tables_at_full_size(cell, kind, which):
    """Every pixel's geometry and every (pixel, TOA bin) of the table a
    default job builds, against the reference's formula: 4.32 M entries
    a map, equal one for one. So a bin on an edge is on the same side
    of it in both, and the comparison on the chip can be exact."""
    from esslivedata_tpu.config.instruments.bifrost.specs import analyzer_geometry
    from esslivedata_tpu.ops.qhistogram import build_elastic_q2d_map, build_qe_map
    from esslivedata_tpu.workflows.elastic_qmap import ElasticQMapParams
    from esslivedata_tpu.workflows.qe_spectroscopy import QESpectroscopyParams

    theirs = analyzer_geometry()
    view = next(j for j in cell.config["jobs"] if j["view"]["map"] == which)["view"]
    mine = kind.pixel_geometry(view["geometry"])
    for name, values in mine.items():
        assert np.array_equal(values, theirs[name]), name
    assert np.array_equal(theirs["pixel_ids"], np.arange(1, N_PIXELS + 1))
    if which == "qe":
        p = QESpectroscopyParams()
        built = build_qe_map(
            **{k: v for k, v in theirs.items() if k != "azimuth"},
            toa_edges=np.linspace(p.toa_range.low, p.toa_range.high, p.toa_bins + 1),
            q_edges=np.linspace(p.q_min, p.q_max, p.q_bins + 1),
            e_edges=np.linspace(p.e_min, p.e_max, p.e_bins + 1), l1=p.l1, toa_offset_ns=p.toa_offset_ns)
    else:
        p = ElasticQMapParams()
        built = build_elastic_q2d_map(
            **theirs, toa_edges=np.linspace(p.toa_range.low, p.toa_range.high, p.toa_bins + 1),
            axis1=p.axis1.component, axis1_edges=p.axis1.edges(), axis2=p.axis2.component,
            axis2_edges=p.axis2.edges(), l1=p.l1, e_window_mev=p.e_window_mev, toa_offset_ns=p.toa_offset_ns)
    assert (built.id_base, built.table.shape, built.table.dtype) == (1, (N_PIXELS, 320), np.int16)
    pixel, toa_bin = np.meshgrid(np.arange(N_PIXELS), np.arange(320), indexing="ij")
    flat = kind.flat_bin(view, pixel.ravel(), toa_bin.ravel()).reshape(N_PIXELS, 320)
    assert np.array_equal(built.table, flat)
    counted = (flat >= 0).mean()
    # most of the frame lands in S(Q, E); the elastic line is an eighth of it, and the 2.7 meV arc's
    # lies outside the frame that two periods select
    assert (0.8 < counted < 0.9) if which == "qe" else (0.1 < counted < 0.15)
    if which == "elastic":
        assert (flat[: CHANNELS * TRIPLET] < 0).all() and (flat[CHANNELS * TRIPLET:] >= 0).any()


def test_the_tables_take_two_planes_and_the_windowed_lookup_at_the_cells_rate(cell):
    """``packable`` and ``lookup_kind`` at BIFROST's table shape: both
    bin spaces are past one byte plane, the pixel axis pads to 106
    windows, and the merged batch is windowed at E and at a quarter of
    it; only a batch under 2**16 events gathers."""
    from esslivedata_tpu.ops import pallas_hist, pallas_lookup

    table = np.zeros((N_PIXELS, 320), np.int16)
    assert pallas_lookup.packable(table, 80 * 60) == pallas_lookup.packable(table, 100 * 100) == 2
    assert pallas_lookup.packable(table, 255) == 1
    packed = (2, 320, -(-N_PIXELS // pallas_lookup.WINDOW) * pallas_lookup.WINDOW)
    assert packed[2] == 13_568 and packed[2] // pallas_lookup.WINDOW == 106
    merged = 45 * 14 * cell.traffic.events_per_pulse
    bucket = 1 << (merged - 1).bit_length()
    assert pallas_lookup.lookup_kind(bucket, packed) == "windowed"
    assert pallas_lookup.lookup_kind(bucket // 4, packed) == "windowed"
    assert pallas_lookup.lookup_kind(pallas_lookup.MIN_EVENTS, packed) == "windowed"
    assert pallas_lookup.lookup_kind(pallas_lookup.MIN_EVENTS // 2, packed) == "gather"
    assert 80 * 60 + 1 <= pallas_hist.MAX_PALLAS_BINS < 100 * 100 + 1  # one job each side of the bincount's choice


def window_of(pools, index, lo, hi):
    pool, _ = pools[index]
    ids = np.concatenate([pool[k % len(pool)][0] for k in range(lo, hi)])
    toa = np.concatenate([pool[k % len(pool)][1] for k in range(lo, hi)])
    return ids, toa


@pytest.fixture(scope="module")
def small(cell):
    """(pools, job -> the package's outputs of the two windows, what
    the run counted): both jobs at full size in one ``JobManager``, each
    window one merged batch of the 45 sources' events and the monitor's."""
    from esslivedata_tpu.config import JobId, WorkflowConfig
    from esslivedata_tpu.config.instrument import instrument_registry
    from esslivedata_tpu.config.instruments.bifrost import specs
    from esslivedata_tpu.core import Timestamp
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.preprocessors import DetectorEvents, MonitorEvents, ToEventBatch
    from esslivedata_tpu.telemetry.instruments import JOB_WINDOWS, Q_BINCOUNT_STEPS, Q_LOOKUP_STEPS, TABLE_BYTES

    instrument_registry["bifrost"].load_factories()
    config = cell.config
    pools = reference.make_pools(config, TRAFFIC, 2**31 + 33)
    n_detectors = len(config["streams"]) - 1

    def counters(manager):
        return {
            "private": JOB_WINDOWS.value(path="private"),
            "not_private": JOB_WINDOWS.value(path="tick") + JOB_WINDOWS.value(path="fused"),
            "steps": Q_LOOKUP_STEPS.value(lookup="gather") + Q_LOOKUP_STEPS.value(lookup="windowed"),
            "scatter": Q_BINCOUNT_STEPS.value(method="scatter"),
            "qe_bytes": TABLE_BYTES.value(family="qe"),
            "elastic_bytes": TABLE_BYTES.value(family="elastic_q2d"),
            **{f"cache_{k}": v for k, v in manager.event_cache_cumulative_stats().items()},
        }

    manager = JobManager(job_factory=JobFactory(), job_threads=2, combine_publish=True, tick_program=True)
    try:
        before = counters(manager)
        by_number = {}
        handles = {"qe_map": specs.QE_HANDLE, "elastic_qmap": specs.ELASTIC_QMAP_HANDLE}
        for job in config["jobs"]:
            job_id = JobId(source_name=job["job_source"])
            by_number[job_id.job_number] = job["name"]
            manager.schedule_job(WorkflowConfig(
                identifier=handles[job["workflow"][1]].workflow_id, job_id=job_id, params=job["params"],
                aux_source_names=job["aux_source_names"]))
        built = counters(manager)
        outputs = {job["name"]: [] for job in config["jobs"]}
        for number, (lo, hi) in enumerate(WINDOWS):
            merged = ToEventBatch(min_bucket=16)
            for index in range(n_detectors):  # one chunk a source, as the merged route lands them
                ids, toa = window_of(pools, index, lo, hi)
                merged.add(Timestamp.from_ns(number), DetectorEvents(pixel_id=ids, time_of_arrival=toa.astype(np.float32)))
            monitor = ToEventBatch(min_bucket=16)
            monitor.add(Timestamp.from_ns(number), MonitorEvents(
                time_of_arrival=window_of(pools, n_detectors, lo, hi)[1].astype(np.float32)))
            published = manager.process_jobs(
                {"detector": merged.get(), "monitor_1": monitor.get()},
                start=Timestamp.from_ns(number), end=Timestamp.from_ns(number + 1))
            assert len(published) == 2
            for result in published:
                outputs[by_number[result.job_id.job_number]].append(
                    {k: np.asarray(v.values) for k, v in result.outputs.items()})
        stepped = counters(manager)
    finally:
        manager.shutdown()
    counted = {
        "build": {k: built[k] - before[k] for k in before},
        "steps": {k: stepped[k] - built[k] for k in before},
    }
    return pools, outputs, counted


def test_two_jobs_on_one_stream_stage_it_once_a_window(small):
    """Private windows (every one carries monitor events), two Q steps
    a window over one transfer: one miss and one hit of the stage-once
    cache, of which ``lookups`` is the sum."""
    _, _, counted = small
    steps, windows = counted["steps"], len(WINDOWS)
    assert (steps["private"], steps["not_private"], steps["steps"]) == (2 * windows, 0, 2 * windows)
    assert (steps["cache_misses"], steps["cache_hits"], steps["cache_lookups"]) == (windows, windows, 2 * windows)
    assert steps["scatter"] == 2 * windows  # on the CPU both; on a TPU the 4 800-bin job takes the one-hot kernel
    assert counted["build"]["qe_bytes"] == counted["build"]["elastic_bytes"] == N_PIXELS * 320 * 2  # int16 on the host


def misses(kind, ref, published) -> dict[str, int]:
    """check -> bins of the two publishes that miss ``ref``, judged as
    ``results.compare`` judges them."""
    out: dict[str, int] = {}
    previous = 0
    for (_lo, hi), outputs in zip(WINDOWS, published, strict=True):
        for output, got in outputs.items():
            lo = previous if output.endswith("_current") else 0
            want = np.asarray(ref.expected(output, lo, hi))
            assert got.shape == want.shape, output
            tolerance = kind.tolerance(output)
            if tolerance is None:
                miss = results.bins_off(got, want)
            else:
                miss, _share = results.bins_outside(got, want, tolerance[0], tolerance[1])
            out[kind.check(output)] = out.get(kind.check(output), 0) + miss
        previous = hi
    return out


def test_the_package_publishes_what_the_reference_says_for_both_jobs(kind, cell, small):
    pools, outputs, _ = small
    shapes = {"qe_map": (80, 60), "elastic_qmap": (100, 100)}
    per_window = 45 * TRAFFIC.events_per_pulse
    for job in cell.config["jobs"]:
        ref = kind.build(job, cell.config, TRAFFIC, pools)
        assert ref.per_pulse.min() > 0, "every pulse counts events in both maps"
        last = outputs[job["name"]][-1]
        assert set(last) == set(job["outputs"]["arrays"])
        assert misses(kind, ref, outputs[job["name"]]) == {"spectrum_bins_wrong": 0, "image_bins_wrong": 0}, job["name"]
        window, run, _normalized = kind.OUTPUTS[job["view"]["map"]]
        assert last[window].shape == last[run].shape == shapes[job["name"]] and last[run].dtype == np.float32
        lo, hi = WINDOWS[-1]
        assert float(last["counts_current"]) == ref.counts(lo, hi) == last[window].sum()
        assert 0 < ref.counts(lo, hi) < (hi - lo) * per_window  # some (Q, E) lie outside the edges: dropped
    qe, elastic = (outputs[name][-1] for name in ("qe_map", "elastic_qmap"))
    assert float(qe["monitor_counts_current"]) == (WINDOWS[-1][1] - WINDOWS[-1][0]) * per_window // 8
    assert elastic["qmap_cumulative"].sum() < 0.25 * qe["sqw_cumulative"].sum()  # the elastic line is a slice


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_kind_is_outside_the_tolerance(kind, cell, small, fault):
    pools, outputs, _ = small
    for job in cell.config["jobs"]:
        broken = kind.faults()[fault](job, cell.config, TRAFFIC, pools)
        assert sum(misses(kind, broken, outputs[job["name"]]).values()) > 0, f"{fault} is not seen in {job['name']}"


def test_a_stray_id_of_one_source_is_its_neighbours_pixel_once_merged(kind, cell, small):
    """The generator's probes just below and just above a source's own
    ids are pixels of the merged detector: counted, where a job on the
    one source would drop them. A source left out of the view shows."""
    pools, _, _ = small
    job = cell.config["jobs"][0]
    whole = kind.build(job, cell.config, TRAFFIC, pools)
    alone = copy.deepcopy(job)
    alone["view"]["streams"] = job["view"]["streams"][:1]
    with pytest.raises(ValueError, match="does not cover"):
        kind.build(alone, cell.config, TRAFFIC, pools)
    shifted = copy.deepcopy(job)
    shifted["view"]["geometry"]["first_id"] = 2  # a wrong id base shows before any comparison
    with pytest.raises(ValueError, match="does not cover"):
        kind.build(shifted, cell.config, TRAFFIC, pools)
    probes = 0
    for index in range(1, 44):  # every inner source's pulses hold ids of both neighbours
        (first_id, n_pixels) = pools[index][1]
        ids = pools[index][0][0][0]
        probes += int(np.count_nonzero((ids == first_id - 1) | (ids == first_id + n_pixels)))
    assert probes > 0 and whole.per_pulse[0] > 0


def toy(cell: manifest.Cell) -> manifest.Cell:
    """The cell whole (46 sources, both jobs, 644 messages a window) at
    an eighty-third of the rate."""
    return dataclasses.replace(cell, traffic=dataclasses.replace(cell.traffic, events_per_pulse=64))


def test_the_harness_runs_the_whole_cell_on_the_service_and_finds_it_correct(cell):
    """``run.py``'s plumbing on the CPU: the bifrost data-reduction
    service started with no flag, 45 sources on the detector topic
    merged into one stream and the monitor on its own, both jobs
    started **with their default parameters**, every output compared
    with the reference. On the parent the default TOA axis was one no
    wire event can fall into, so both jobs would publish zeros and the
    run would read ``correct`` false."""
    line, report = bench.run_cell(toy(cell), 2**31 + 33, 3.0, True, REPO, time.monotonic(), allow_cpu=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 6
    checks = line["checks"]
    assert [checks[name]["value"] for name in cell.limits] == [0, 0, 0.0]
    normalized = checks["image_bins_wrong"]
    assert normalized["tolerance"] == {"rel": 2.0**-18, "abs": 0.0} and normalized["worst_share"] <= 2.0**-6
    publishes = line["pulses"]["publishes"]
    assert publishes["qe_map"] == publishes["elastic_qmap"] >= 5
    assert checks["compared"]["arrays"] == (5 + 4) * publishes["qe_map"]
    metrics = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert metrics["messages_per_tick.paced"] == 14 * 46 == 644  # base windows of 45 sources and the monitor
    assert metrics["stage_once_hit_share.paced"] == 50.0  # one miss and one hit a window
    assert 0.0 < metrics["land_ms.paced"] < metrics["decode_ms.paced"]
    assert metrics["compiles_in_window.paced"] == 0.0 and metrics["escalations_in_window.paced"] == 0.0
    assert metrics["publishes_ahead_share.paced"] == 0.0
    # on the CPU no table is packed and no kernel is Pallas: the shares read the other side
    assert (metrics["q_lookup_gather_share.paced"], metrics["q_lookup_windowed_share.paced"]) == (100.0, 0.0)
    assert metrics["q_bincount_scatter_share.paced"] == 100.0
    assert any(text.startswith("check image_bins_wrong: ") and "bfloat16" in text for text in report)

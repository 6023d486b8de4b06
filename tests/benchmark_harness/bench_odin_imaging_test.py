"""ODIN whole on its detector service, as files of the benchmark: the
configuration (the Timepix3's 512 x 512 view beside the ORCA camera's
2048 x 2048 uint16 frames), its cell and its two new metrics; that the
configuration is the package's ODIN; and the cell at toy size through
the harness on the CPU: correct, with each of the kind ``frames``' four
faults caught as ``control.py`` puts them in the program's place."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import time

import numpy as np
import pytest
from bench_support import REPO
from harness import bench, manifest, metrics, reference

CELL = "odin_imaging.paced14"
CONFIG = "odin_imaging"
FRAME_FAULTS = ("frame_dropped", "frame_twice", "frame_transposed", "frame_uint8")
NEW_METRICS = {
    "frame_ms.paced": (
        "stage + tick program, host side", "ms", "lower", "program_span",
        [{"family": "livedata_tick_span_seconds", "part": "sum", "labels": {"span": "frame"}}],
        {"family": "livedata_tick_span_seconds", "part": "count", "labels": {"span": "decode"}}, 1000),
    "frames_device_share.paced": (
        "kernels", "%", "higher", "program_counter",
        [{"family": "livedata_camera_frames", "labels": {"path": "device"}}],
        {"family": "livedata_camera_frames"}, 100),
}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(REPO, CELL)


def test_the_cell_is_in_the_manifest_whole_and_as_files(cell):
    assert manifest.check(REPO) == []
    doc = manifest.load_manifest(REPO)
    entry = next(c for c in doc["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and cell.config["reduced"] == {}  # both detectors, every pixel
    assert entry["source"] == cell.config["source"] and len(entry["source"]) <= 200
    assert "odin/specs.py" in entry["source"]
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, CONFIG, "paced14_orca")
    assert cell.config["service"] == "detector_data" and cell.config["service_flags"] == []
    timepix, camera = cell.config["streams"]
    assert (timepix["wire_source"], timepix["first_id"], timepix["n_pixels"]) == ("odin_timepix3", 1, 512 * 512)
    assert "kind" not in timepix and "topic" not in timepix  # on the detector topic
    assert (camera["kind"], camera["wire_source"], camera["topic"]) == ("camera", "odin_orca", "odin_camera")
    assert (camera["frame_shape"], camera["dtype"]) == ([2048, 2048], "uint16")
    view, orca = cell.config["jobs"]
    assert view["workflow"] == ["detector_view", "odin_detector_xy"] and view["params"] == {}
    assert view["view"] == {"kind": "grid", "shape": [512, 512]}
    assert orca["workflow"] == ["detector_view", "camera_view"] and orca["params"] == {}
    assert orca["outputs"] == {"arrays": ["current", "cumulative"]} and orca["view"] == {"kind": "frames"}
    assert cell.config["state_bytes"] == 512**2 * 100 * 4 * 2 + 2048**2 * 4 * 2 == 243_269_632
    assert len(cell.config["guarantees"]) >= 4
    assert "odin_mon_1" in cell.config["left_out"] and "timeseries" in cell.config["left_out"]
    assumed = " ".join(cell.config["assumed"])
    assert "2048 x 2048 uint16" in assumed and "3000" in assumed and "512 x 512" in assumed
    # paced14's shape, with the camera beside it
    paced = json.loads((REPO / "benchmark" / "traffic" / "paced14.json").read_text())
    mine = json.loads((REPO / "benchmark" / "traffic" / "paced14_orca.json").read_text())
    shape = set(paced) - {"name", "why", "sources", "events_per_pulse"}
    assert {k: mine[k] for k in shape} == {k: paced[k] for k in shape}
    assert set(mine) - set(paced) == {"camera_frames_per_pulse", "camera_mean_counts"}
    assert set(mine["sources"]) == set(paced["sources"]) | {"camera_frames_per_pulse", "camera_mean_counts"}
    assert (cell.traffic.camera_frames_per_pulse, cell.traffic.camera_pulses_per_frame) == (1, 1)
    assert cell.traffic.camera_mean_counts == 3000.0 and cell.traffic.events_per_pulse % 32768 == 0
    assert 14 * 8 * cell.traffic.camera_mean_counts > np.iinfo(np.uint16).max  # the spot wraps a uint16 window
    assert reference.check_names(cell.config, cell.kinds) == list(cell.limits) == [
        "spectrum_bins_wrong", "image_bins_wrong", "frame_bins_wrong", "prefix_off_pulses"]
    assert cell.limits == {"spectrum_bins_wrong": 0, "image_bins_wrong": 0, "frame_bins_wrong": 0,
                           "prefix_off_pulses": 0.1}
    assert reference.controls(cell.config, cell.kinds)[-4:] == tuple(f"frames.{f}" for f in FRAME_FAULTS)
    for metric in doc["end_to_end"]:
        assert "workloads" not in metric or CELL in metric["workloads"]
    listed = {m["name"] for m in cell.per_layer}
    nmx = {m["name"] for m in manifest.load_cell(REPO, "nmx_panels.paced14").per_layer}
    # what NMX's cell reports, and what the chip's traced line of this cell read besides: the job pool's
    # phases (two jobs a window), the pool's copies (the frames), the stage cache's lookups, the two new
    assert listed == nmx | set(NEW_METRICS) | {
        "accumulate_ms.paced", "accumulate_wait_ms.paced", "pool_queue_ms.paced", "staging_kept_share.paced",
        "stage_once_hit_share.paced"}


def test_the_configuration_mirrors_the_package(cell):
    import esslivedata_tpu.config.instruments  # noqa: F401 - registers
    from esslivedata_tpu.config.instrument import instrument_registry
    from esslivedata_tpu.config.instruments.odin import specs
    from esslivedata_tpu.config.streams import get_stream_mapping
    from esslivedata_tpu.workflows.area_detector_view import AreaDetectorParams
    from esslivedata_tpu.workflows.detector_view.workflow import DetectorViewParams
    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    odin = instrument_registry["odin"]
    timepix, camera = cell.config["streams"]
    declared = odin.detectors[timepix["name"]]
    assert declared.source_name == timepix["wire_source"] and declared.projection == "logical"
    ids = np.asarray(declared.detector_number)
    assert ids.shape == specs.TIMEPIX_SHAPE == tuple(cell.config["jobs"][0]["view"]["shape"])
    np.testing.assert_array_equal(ids.reshape(-1), np.arange(1, timepix["n_pixels"] + 1))
    assert odin.cameras[camera["name"]] == specs.CameraConfig(name="orca_camera", source_name="odin_orca")
    mapping = get_stream_mapping(odin, False)
    detectors = {stream: (key.topic, key.source_name) for key, stream in mapping.detectors.items()}
    cameras = {stream: (key.topic, key.source_name) for key, stream in mapping.area_detectors.items()}
    assert detectors[timepix["name"]] == (cell.config["detector_topic"], timepix["wire_source"])
    assert cameras[camera["name"]] == (camera["topic"], camera["wire_source"])
    view_spec = workflow_registry[specs.DETECTOR_XY_HANDLE.workflow_id]
    camera_spec = workflow_registry[specs.CAMERA_HANDLE.workflow_id]
    for spec, job, model in ((view_spec, cell.config["jobs"][0], DetectorViewParams),
                             (camera_spec, cell.config["jobs"][1], AreaDetectorParams)):
        assert [spec.namespace, spec.name] == job["workflow"] and spec.source_names == [job["job_source"]]
        assert spec.params_model is model and set(job["params"]) <= set(model.model_fields)
    assert DetectorViewParams().toa_bins == cell.config["toa_bins"] == cell.traffic.toa_bins
    assert set(cell.config["outputs"]["spectra"] + cell.config["outputs"]["images"]) <= set(view_spec.outputs)
    assert set(cell.config["jobs"][1]["outputs"]["arrays"]) == set(camera_spec.outputs)
    assert AreaDetectorParams() == AreaDetectorParams(transpose=False, flip_y=False, flip_x=False)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_new_metrics_are_data_only_and_the_program_has_their_instruments(metric):
    layer, unit, better, source, terms, per, scale = NEW_METRICS[metric]
    entry = next(m for m in manifest.load_manifest(REPO)["per_layer"] if m["name"] == metric)
    assert CELL in entry["workloads"]  # a later cell may join: no equality here
    assert (entry["layer"], entry["unit"], entry["better"], entry["moves"], entry["source"]) == (
        layer, unit, better, "freshness_p50_ms", source)
    doc = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    assert {k: doc[k] for k in ("layer", "unit", "better", "source", "moves")} == {
        k: entry[k] for k in ("layer", "unit", "better", "source", "moves")}
    reader = doc["reader"]
    assert reader["kind"] == "prometheus" and "absent_is_zero" not in reader  # a program without it: no value
    assert (reader["terms"], reader["per"], reader["scale"]) == (terms, per, scale)
    from esslivedata_tpu.telemetry.instruments import REGISTRY

    for term in (*terms, per):
        family = term["family"] if term["family"].endswith("_seconds") else f"{term['family']}_total"
        assert REGISTRY.get(family) is not None


def test_a_program_without_the_instruments_reads_nothing_and_one_with_them_reads_both():
    """A scrape with no ``frame`` span and no frame counter, as a
    program without them gives: both readers return None and the line
    leaves the metrics out."""

    def scrape(windows, frames=None):
        span = "livedata_tick_span_seconds"
        samples = [(f"{span}_count", {"span": "decode"}, float(windows)),
                   (f"{span}_sum", {"span": "decode"}, 0.03 * windows)]
        if frames is not None:
            samples += [(f"{span}_count", {"span": "frame"}, 14.0 * windows),
                        (f"{span}_sum", {"span": "frame"}, 14 * 0.002 * windows)]
            samples += [("livedata_camera_frames_total", {"path": path}, n * windows)
                        for path, n in frames.items()]
        return samples

    specs = [{**json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text()), "name": name}
             for name in NEW_METRICS]
    without = {"scrape_start": scrape(2), "scrape_end": scrape(53), "window_s": 51.0}
    assert metrics.evaluate(specs, without) == {}
    for frames, share in (({"device": 14.0, "host": 0.0}, 100.0), ({"device": 0.0, "host": 14.0}, 0.0)):
        ctx = {"scrape_start": scrape(2, frames), "scrape_end": scrape(53, frames), "window_s": 51.0}
        got = metrics.evaluate(specs, ctx)
        assert got["frame_ms.paced"]["value"] == pytest.approx(28.0)
        assert got["frames_device_share.paced"]["value"] == share


def _control():
    spec = importlib.util.spec_from_file_location("bench_control", REPO / "benchmark" / "control.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_cell_at_toy_size_is_correct_and_each_frames_fault_is_caught(cell):
    """The configuration as it is, but for the camera's frame (64 x 64)
    and the Timepix3's rate (4096 events a pulse), through the harness
    on the CPU: the uint16 frames at the cell's count level wrap a
    window's uint16 sum in the beam spot, and the program's sums read
    correct; the reference with one frame dropped, counted twice,
    transposed or truncated to uint8 does not."""
    streams = [dict(s, frame_shape=[64, 64]) if s.get("kind") == "camera" else s for s in cell.config["streams"]]
    toy = dataclasses.replace(cell, config={**cell.config, "streams": streams},
                              traffic=dataclasses.replace(cell.traffic, events_per_pulse=4096, pool_pulses=5))
    controls = tuple(c for c in _control().controls_of(toy) if c.startswith("frames."))
    assert controls == tuple(f"frames.{f}" for f in FRAME_FAULTS)
    line, report = bench.run_cell(toy, 2**31 + 42, 3.0, False, REPO, time.monotonic(), allow_cpu=True,
                                  controls=controls)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 2 * 3  # two jobs, a publish each a base window
    checks = line["checks"]
    assert list(checks)[:4] == list(cell.limits)
    for name in ("spectrum_bins_wrong", "image_bins_wrong", "frame_bins_wrong"):
        assert checks[name] == {"value": 0, "limit": 0}
    assert checks["largest_bin"]["value"] > 14 * 8 * 3000  # a spot pixel of a cumulative, past uint16
    assert checks["compared"]["arrays"] == 2 * line["pulses"]["publishes"]["orca"]
    assert checks["compared"]["spectra"] >= 2 * line["pulses"]["publishes"]["timepix3_xy"]
    assert any(text.startswith("check frame_bins_wrong: ") for text in report)
    for fault, reading in line["controls"].items():
        assert reading["correct"] is False and reading["frame_bins_wrong"] > 0, fault
        assert reading["spectrum_bins_wrong"] == reading["image_bins_wrong"] == 0, fault

"""The manifest and the data files it names: the rules that can be
checked without a run, and that a later PR extends the benchmark with
files and manifest entries only."""

from __future__ import annotations

import json
import re

import pytest
from bench_support import FIXTURE, KINDS, PARTS, PLUGS, REPO, edit_json, overlay_fixture
from harness import manifest, metrics, reference
from harness.traffic import Traffic

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
BENCH = REPO / "benchmark"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
FILE_NAME = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def test_the_committed_manifest_breaks_no_rule():
    assert manifest.check(REPO) == []
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert MANIFEST["paths"][:2] == ["benchmark", "tests/benchmark_harness"]
    assert 1 <= MANIFEST["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_loads_from_files_of_its_own(cell):
    loaded = manifest.load_cell(REPO, cell)
    assert (BENCH / "workloads" / f"{cell}.json").is_file()
    assert (BENCH / "limits" / f"{cell}.json").is_file()
    assert (BENCH / "traffic" / f"{loaded.traffic_name}.json").is_file()
    assert (BENCH / "configs" / f"{loaded.config_name}.json").is_file()
    reported = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded.per_layer, "a cell reports at least one per-layer metric"
    for metric in loaded.per_layer:
        assert metric["moves"] in reported
        assert metric["reader"]["kind"] in metrics.READERS


@pytest.mark.parametrize("cell", ["nmx_panels.paced14", "dream_banks.paced14"])
def test_the_first_cells_take_one_chip_and_start_the_service_as_documented(cell):
    loaded = manifest.load_cell(REPO, cell)
    assert loaded.chips == 1
    assert loaded.config["service"] == "detector_data"
    assert loaded.config["service_flags"] == []  # no path-selecting flag


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_every_configuration_states_what_the_issue_asks(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    doc = json.loads((REPO / entry["file"]).read_text())
    for key in ("source", "reduced", "assumed", "guarantees", "state_bytes", "outputs"):
        assert key in doc
    assert doc["reduced"] == {}, "both instruments run whole"
    assert doc["source"] == entry["source"]
    assert sorted(doc["reduced"]) == sorted(entry["reduced"])
    assert len(doc["guarantees"]) >= 4 and doc["state_bytes"] > 0
    assert len(entry["reduced"]) <= 16


def test_every_file_under_paths_is_named_from_a_names_characters():
    for top in MANIFEST["paths"]:
        for path in (REPO / top).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert FILE_NAME.match(str(path.relative_to(REPO))), path


@pytest.mark.parametrize("part", PARTS)
def test_data_files_carry_names_the_manifest_could_use(part):
    for path in sorted((BENCH / part).glob("*.json")):
        assert NAME.match(path.stem), path
        doc = json.loads(path.read_text())
        assert doc["name"] == path.stem
        if part == "traffic":
            Traffic.from_dict(doc)
            # every parameter names where it comes from
            named = {key.strip() for keys in doc["sources"] for key in keys.split(",")}
            assert named >= set(doc) - {"name", "why", "sources", "toa_bins"}
        if part in ("workloads", "limits"):
            assert doc["name"] in {w["name"] for w in MANIFEST["workloads"]}
        if part == "metrics":
            assert manifest.UNIT.match(doc["unit"])
            assert doc["source"] in manifest.SOURCES
            # no file waits for an entry; the file repeats its entry, but for the cells
            entry = next(m for m in MANIFEST["per_layer"] if m["name"] == doc["name"])
            entry = {k: v for k, v in entry.items() if k != "workloads"}  # the manifest's alone
            assert {k: doc[k] for k in entry} == entry


#: Each edits the manifest of a root that holds the benchmark's data and
#: the fixture's; the fixture's entries come after the benchmark's.
BREACHES = {
    "a unit with a space": lambda d: d["end_to_end"][0].update(unit="ms per tick"),
    "a name with a slash": lambda d: d["per_layer"][0].update(name="a/b"),
    "a name too long": lambda d: d["workloads"][0].update(name="x" * 65),
    "a why over 200": lambda d: d["workloads"][0].update(why="y" * 201),
    "a bound over a quarter": lambda d: d["end_to_end"][0].update(bound=0.3),
    "an extra key on a metric": lambda d: d["per_layer"][0].update(why="no"),
    "a cell of four chips and a half": lambda d: d["workloads"][0].update(chips=2),
    "a cell without its configuration": lambda d: d["workloads"][0].update(config="nope"),
    "no setup_s": lambda d: d.update(
        end_to_end=[m for m in d["end_to_end"] if m["name"] != "setup_s"]
    ),
    "a layer metric in a cell that lacks what it moves": lambda d: next(
        m for m in d["per_layer"] if m["name"] == "decode_ms.paced"
    )["workloads"].append("nmx_panels.unlisted"),
    "a source outside the vocabulary": lambda d: d["per_layer"][0].update(source="guess"),
    "an end-to-end metric read from the program": lambda d: d["end_to_end"][0].update(
        source="program_counter"
    ),
    "the same pair twice": lambda d: d["workloads"].append(
        {**d["workloads"][0], "name": "again"}
    ),
}


@pytest.mark.parametrize("breach", [*sorted(BREACHES), *sorted(PLUGS)])
def test_check_names_each_breach(tmp_path, breach):
    overlay_fixture(tmp_path)
    assert manifest.check(tmp_path) == []
    if breach in BREACHES:
        edit_json(tmp_path / "BENCHMARK.json", BREACHES[breach])
        assert manifest.check(tmp_path), breach
        return
    edit, word = PLUGS[breach]
    edit(tmp_path / "benchmark")
    sentences = manifest.check(tmp_path)
    assert len(sentences) == 1 and sentences[0].startswith("cell toy_loki.toy_iq: "), sentences
    assert word in sentences[0]
    with pytest.raises(manifest.ManifestError, match=re.escape(word)):
        manifest.load_cell(tmp_path, "toy_loki.toy_iq")
    manifest.load_cell(tmp_path, "toy_panel.toy_paced")  # the other cells are as they were


def test_fixture_adds_files_and_entries_and_edits_nothing(toy_root):
    """Three configurations, four cells with their limits, four traffic
    mixes, a prometheus metric and a reference kind arrive as new files
    and new manifest entries; the harness finds them by name with no
    edit to any file that was there."""
    assert manifest.check(toy_root) == []
    for part in (*PARTS, KINDS):
        for path in (BENCH / part).glob("*.*"):
            assert (toy_root / "benchmark" / part / path.name).read_bytes() == path.read_bytes()
        assert list((FIXTURE / part).iterdir())
    paced = manifest.load_cell(toy_root, "toy_panel.toy_paced")
    assert paced.traffic.pixel_dist == "hotspot" and paced.traffic.messages_per_pulse == 4
    assert "toy_messages" in {m["name"] for m in paced.per_layer}
    blob = manifest.load_cell(toy_root, "toy_panel.toy_blob")
    assert blob.traffic.pixel_dist == "blob" and blob.traffic.messages_per_pulse == 1
    assert {m["name"] for m in blob.end_to_end} == {
        "freshness_p50_ms", "freshness_p95_ms", "setup_s"
    }
    assert blob.limits == paced.limits
    assert paced.kinds == blob.kinds == {}  # a detector view needs no file
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell(toy_root, "toy_panel.nowhere")


def test_fixture_plugs_a_kind_a_stream_and_outputs_of_its_own(toy_root):
    """The toy LOKI: ``sans/iq`` on the data-reduction service, its
    reference found by the view's kind, a monitor stream on its own
    topic at an eighth of the rate bound as an aux source, outputs and
    a prefix total that the job lists itself."""
    cell = manifest.load_cell(toy_root, "toy_loki.toy_iq")
    assert cell.config["service"] == "data_reduction"
    assert list(cell.kinds) == ["sans_iq"]
    kind = cell.kinds["sans_iq"]
    assert all(callable(getattr(kind, name)) for name in reference.KIND_FUNCTIONS)
    assert set(kind.faults()) == {"monitor_twice", "toa_bin_off_by_one"}
    detector, monitor = cell.config["streams"]
    assert "topic" not in detector and "kind" not in detector and "rate_share" not in detector
    assert (monitor["kind"], monitor["topic"], monitor["rate_share"]) == ("monitor", "loki_monitor", 0.125)
    job = cell.config["jobs"][0]
    assert job["aux_source_names"] == {"monitor": monitor["name"]}
    assert job["prefix_total"] == "counts_q_current" and "arrays" in job["outputs"]
    assert reference.check_names(cell.config, cell.kinds) == [
        "iq_bins_off", "q_counts_wrong", "monitor_counts_wrong", "prefix_off_pulses"]
    assert set(cell.limits) == set(reference.check_names(cell.config, cell.kinds))
    for accepted in ("nmx_panels.paced14", "dream_banks.paced14"):
        loaded = manifest.load_cell(toy_root, accepted)
        assert loaded.kinds == {}
        assert reference.check_names(loaded.config, {}) == list(loaded.limits) == [
            "spectrum_bins_wrong", "image_bins_wrong", "prefix_off_pulses"]


def test_fixture_plugs_a_camera_stream_and_the_frames_kind(toy_root):
    """The toy ODIN: the package's ``camera_view`` on the detector
    service, a camera stream of 64 x 64 uint16 ad00 frames on its own
    topic, answered by the kind ``frames`` (``references/frames.py``,
    the benchmark's own, not the fixture's), its checks the kind's own."""
    cell = manifest.load_cell(toy_root, "toy_odin.toy_camera")
    assert cell.config["service"] == "detector_data" and cell.config["instrument"] == "odin"
    (camera,) = cell.config["streams"]
    assert (camera["kind"], camera["topic"], camera["wire_source"]) == ("camera", "odin_camera", "odin_orca")
    assert camera["frame_shape"] == [64, 64] and camera["dtype"] == "uint16"
    assert not {"first_id", "n_pixels", "rate_share"} & set(camera)
    (job,) = cell.config["jobs"]
    assert job["workflow"] == ["detector_view", "camera_view"] and job["view"] == {"kind": "frames"}
    assert list(cell.kinds) == ["frames"] and not (FIXTURE / KINDS / "frames.py").exists()
    assert reference.check_names(cell.config, cell.kinds) == list(cell.limits) == [
        "frame_bins_wrong", "prefix_off_pulses"]
    assert reference.controls(cell.config, cell.kinds) == tuple(
        f"frames.{name}" for name in ("frame_dropped", "frame_twice", "frame_transposed", "frame_uint8"))
    assert (cell.traffic.camera_frames_per_pulse, cell.traffic.camera_pulses_per_frame) == (1, 1)


def test_the_package_declares_the_fixtures_camera_as_the_configuration_states():
    import esslivedata_tpu.config.instruments  # noqa: F401 - registers
    from esslivedata_tpu.config.instrument import instrument_registry
    from esslivedata_tpu.config.streams import get_stream_mapping
    from esslivedata_tpu.workflows.area_detector_view import AreaDetectorParams

    config = json.loads((FIXTURE / "configs" / "toy_odin.json").read_text())
    mapping = get_stream_mapping(instrument_registry["odin"], False)
    wire = {stream: (key.topic, key.source_name) for key, stream in mapping.area_detectors.items()}
    for stream in config["streams"]:
        assert wire[stream["name"]] == (stream["topic"], stream["wire_source"])
    for job in config["jobs"]:
        assert set(job["params"]) <= set(AreaDetectorParams.model_fields)


def test_unknown_traffic_keys_and_modes_are_refused():
    good = json.loads((BENCH / "traffic" / "paced14.json").read_text())
    for bad in ({"mode": "catchup"}, {"pixel_dist": "ring"}, {"burst": 3},
                {"pixel_dist": "hotspot"}, {"messages_per_pulse": 5},
                {"out_of_range_probes": good["events_per_pulse"]}):
        with pytest.raises(ValueError):
            Traffic.from_dict({**good, **bad})

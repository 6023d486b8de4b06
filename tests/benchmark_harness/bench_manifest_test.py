"""The manifest and the data files it names: the rules that can be
checked without a run, and that a later PR extends the benchmark with
files and manifest entries only."""

from __future__ import annotations

import copy
import json
import re

import pytest
from bench_support import FIXTURE, PARTS, REPO
from harness import manifest, metrics
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


def _broken(edit):
    doc = copy.deepcopy(MANIFEST)
    edit(doc)
    return doc


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


@pytest.mark.parametrize("breach", sorted(BREACHES))
def test_check_names_each_breach(tmp_path, breach):
    root = tmp_path
    (root / "benchmark").symlink_to(BENCH)
    (root / "BENCHMARK.json").write_text(json.dumps(_broken(BREACHES[breach])))
    assert manifest.check(root), breach


def test_fixture_adds_files_and_entries_and_edits_nothing(toy_root):
    """A configuration, two cells with their limits, two traffic mixes
    and a prometheus metric arrive as new files and new manifest
    entries; the harness finds them by name with no edit to any file
    that was there."""
    assert manifest.check(toy_root) == []
    for part in PARTS:
        for path in (BENCH / part).iterdir():
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
    with pytest.raises(manifest.ManifestError):
        manifest.load_cell(toy_root, "toy_panel.nowhere")


def test_unknown_traffic_keys_and_modes_are_refused():
    good = json.loads((BENCH / "traffic" / "paced14.json").read_text())
    for bad in ({"mode": "catchup"}, {"pixel_dist": "ring"}, {"burst": 3},
                {"pixel_dist": "hotspot"}, {"messages_per_pulse": 5},
                {"out_of_range_probes": good["events_per_pulse"]}):
        with pytest.raises(ValueError):
            Traffic.from_dict({**good, **bad})

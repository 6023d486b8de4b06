"""``BENCHMARK.json`` and the data files it names: loading and checks.

Everything of one configuration, one traffic mix, one cell or one
per-layer metric is a file of its own, found by the name the manifest
gives: ``configs/<config>.json`` (the manifest's ``file``),
``traffic/<traffic>.json``, ``workloads/<cell>.json`` with the limits
of its comparison in ``limits/<cell>.json``,
``metrics/<metric>.json``, and ``references/<kind>.py`` for what the
jobs of a view kind should give (``harness/reference.py``), under
``benchmark/``. Adding one is adding files and manifest entries; no
code knows a name.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path

from . import reference
from .traffic import AD00_DTYPES, STREAM_KINDS, Traffic, stream_events

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
CONFIG_KEYS = (
    "source", "instrument", "service", "service_flags", "detector_topic", "streams",
    "jobs", "outputs", "state_bytes", "guarantees", "reduced", "assumed",
)


class ManifestError(ValueError):
    pass


def _load(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as err:
        raise ManifestError(f"{path}: {err}") from err


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: Traffic
    end_to_end: list[dict]  # the manifest's entries this cell reports
    per_layer: list[dict]  # the metric files' contents (with ``reader``)
    limits: dict[str, float]
    kinds: dict = field(default_factory=dict)  # view kind -> its module of references/


def load_manifest(root: Path) -> dict:
    return _load(root / "BENCHMARK.json")


def bench_dir(root: Path, manifest: dict) -> Path:
    return root / manifest["paths"][0]


def load_cell(root: Path, workload: str) -> Cell:
    manifest = load_manifest(root)
    bench = bench_dir(root, manifest)
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise ManifestError(f"BENCHMARK.json has no workload {workload!r}")
    config_entry = next(
        (c for c in manifest["configs"] if c["name"] == entry["config"]), None
    )
    if config_entry is None:
        raise ManifestError(f"workload {workload}: no configuration {entry['config']!r}")
    config = _load(root / config_entry["file"])
    missing = [k for k in CONFIG_KEYS if k not in config]
    if missing:
        raise ManifestError(f"{config_entry['file']}: missing keys {missing}")
    traffic_doc = _load(bench / "traffic" / f"{entry['traffic']}.json")
    cell_doc = _load(bench / "workloads" / f"{workload}.json")
    for key in ("config", "traffic", "chips"):
        if cell_doc.get(key) != entry[key]:
            raise ManifestError(f"workloads/{workload}.json disagrees on {key!r}")

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    per_layer = []
    for metric in manifest["per_layer"]:
        if reports(metric):
            doc = _load(bench / "metrics" / f"{metric['name']}.json")
            for key in ("unit", "layer", "source", "moves"):
                if doc.get(key) != metric[key]:
                    raise ManifestError(f"metrics/{metric['name']}.json disagrees on {key!r}")
            per_layer.append({**doc, "name": metric["name"]})
    traffic = Traffic.from_dict(traffic_doc)
    limits = _load(bench / "limits" / f"{workload}.json")["limits"]
    kinds, broken = plugs(bench, config, traffic, limits)
    if broken:
        raise ManifestError("; ".join(broken))
    return Cell(
        name=workload,
        chips=entry["chips"],
        config_name=entry["config"],
        config=config,
        traffic_name=entry["traffic"],
        traffic=traffic,
        end_to_end=[m for m in manifest["end_to_end"] if reports(m)],
        per_layer=per_layer,
        limits=limits,
        kinds=kinds,
    )


def plugs(bench: Path, config: dict, traffic: Traffic, limits: dict) -> tuple[dict, list[str]]:
    """What a configuration plugs into the harness by name, seen before
    a run: (view kind -> its module, every broken plug as a sentence)."""
    broken = []
    streams = {s["name"] for s in config["streams"]}
    for stream in config["streams"]:
        if stream.get("kind", "detector") not in STREAM_KINDS:
            broken.append(f"stream {stream['name']}: kind {stream['kind']!r}")
        elif stream.get("kind") == "camera":
            broken += camera_faults(stream)
            continue
        try:
            stream_events(stream, traffic)
        except ValueError as err:
            broken.append(str(err))
    kinds = {}
    stream_kind = {s["name"]: s.get("kind", "detector") for s in config["streams"]}
    for job in config["jobs"]:
        if job["stream"] not in streams:
            broken.append(f"job {job['name']}: no stream {job['stream']!r}")
        elif (job["view"]["kind"] == "frames") != (stream_kind[job["stream"]] == "camera"):
            broken.append(f"job {job['name']}: a view of kind {job['view']['kind']!r} on the "
                          f"{stream_kind[job['stream']]} stream {job['stream']!r}")
        for role, name in job.get("aux_source_names", {}).items():
            if name not in streams:
                broken.append(f"job {job['name']}: aux {role!r} names no stream {name!r}")
        kind = job["view"]["kind"]
        if kind in reference.VIEW_KINDS or kind in kinds:
            continue
        if not isinstance(kind, str) or not NAME.match(kind):
            broken.append(f"job {job['name']}: view kind {kind!r} is not a name")
            continue
        try:
            kinds[kind] = reference.load_kind(bench, kind)
        except ValueError as err:
            broken.append(str(err))
    if not broken:
        for name in reference.check_names(config, kinds):
            if name not in limits:
                broken.append(f"check {name} has no limit")
    return kinds, broken


#: What a camera stream states, and what it does not: it sends frames, not events.
CAMERA_KEYS = ("name", "kind", "wire_source", "topic", "frame_shape", "dtype")
EVENT_KEYS = ("first_id", "n_pixels", "rate_share")


def camera_faults(stream: dict) -> list[str]:
    """A ``camera`` stream's breaches, as sentences."""
    what = f"camera stream {stream['name']}"
    faults = [f"{what} lacks {key!r}" for key in CAMERA_KEYS if key not in stream]
    faults += [f"{what} states {key!r}, which is an event stream's" for key in EVENT_KEYS
               if key in stream]
    if "dtype" in stream and stream["dtype"] not in AD00_DTYPES:
        faults.append(f"{what}: dtype {stream['dtype']!r} is no ad00 type")
    shape = stream.get("frame_shape")
    if shape is not None and not (
        isinstance(shape, list) and len(shape) == 2
        and all(isinstance(n, int) and n > 0 for n in shape)
    ):
        faults.append(f"{what}: frame_shape {shape!r} is not [ny, nx]")
    return faults


def check(root: Path) -> list[str]:
    """Every breach of the manifest's rules that can be seen without a
    run, as sentences; empty when there is none."""
    manifest = load_manifest(root)
    faults = []

    def name_ok(what: str, value) -> None:
        if not isinstance(value, str) or not NAME.match(value):
            faults.append(f"{what}: {value!r} is not a name")

    def line_ok(what: str, value) -> None:
        if not isinstance(value, str) or not 1 <= len(value) <= 200 or "\n" in value or "\t" in value:
            faults.append(f"{what}: not one line of 1 to 200 characters")

    expected = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    if set(manifest) != expected:
        faults.append(f"manifest keys {sorted(manifest)} != {sorted(expected)}")
    paths = manifest["paths"]
    for word in manifest["command"]:
        line_ok("command", word)
    config_names = set()
    for config in manifest["configs"]:
        if set(config) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {config.get('name')}: keys {sorted(config)}")
        name_ok("config", config["name"])
        line_ok(f"config {config['name']} source", config["source"])
        line_ok(f"config {config['name']} why", config["why"])
        for key in config["reduced"]:
            name_ok(f"config {config['name']} reduced", key)
        if not any(config["file"].startswith(p + "/") for p in paths):
            faults.append(f"config {config['name']}: file outside paths")
        if not (root / config["file"]).is_file():
            faults.append(f"config {config['name']}: no file {config['file']}")
        else:
            doc = _load(root / config["file"])
            if sorted(doc.get("reduced", {})) != sorted(config["reduced"]):
                faults.append(f"config {config['name']}: reduced differs from its file")
            if doc.get("source") != config["source"]:
                faults.append(f"config {config['name']}: source differs from its file")
        config_names.add(config["name"])
    e2e = {}
    for metric in manifest["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        if not {"name", "unit", "better", "bound", "source"} <= set(metric) <= allowed:
            faults.append(f"end_to_end {metric.get('name')}: keys {sorted(metric)}")
        name_ok("end_to_end", metric["name"])
        if not UNIT.match(metric["unit"]):
            faults.append(f"end_to_end {metric['name']}: unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher"):
            faults.append(f"end_to_end {metric['name']}: better")
        if metric["source"] not in ("host_clock", "device_trace"):
            faults.append(f"end_to_end {metric['name']}: source {metric['source']!r}")
        if not 0.01 <= metric["bound"] <= 0.25:
            faults.append(f"end_to_end {metric['name']}: bound {metric['bound']}")
        e2e[metric["name"]] = metric
    if "setup_s" not in e2e:
        faults.append("end_to_end lacks setup_s")
    cells = {}
    for cell in manifest["workloads"]:
        if set(cell) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {cell.get('name')}: keys {sorted(cell)}")
        for key in ("name", "config", "traffic"):
            name_ok(f"workload {key}", cell[key])
        line_ok(f"workload {cell['name']} why", cell["why"])
        if cell["config"] not in config_names:
            faults.append(f"workload {cell['name']}: no configuration {cell['config']!r}")
        if cell["chips"] not in (1, 4):
            faults.append(f"workload {cell['name']}: chips {cell['chips']}")
        cells[cell["name"]] = cell
    if len({(c["config"], c["traffic"]) for c in cells.values()}) != len(manifest["workloads"]):
        faults.append("a pair of configuration and traffic appears twice")
    for name in config_names - {c["config"] for c in cells.values()}:
        faults.append(f"configuration {name} is used by no cell")

    def cells_of(metric: dict) -> set[str]:
        return set(metric.get("workloads", cells))

    for metric in e2e.values():
        for cell in cells_of(metric) - set(cells):
            faults.append(f"end_to_end {metric['name']}: no cell {cell!r}")
    layered = set()
    for metric in manifest["per_layer"]:
        allowed = {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        if not allowed - {"workloads"} <= set(metric) <= allowed:
            faults.append(f"per_layer {metric.get('name')}: keys {sorted(metric)}")
        name_ok("per_layer", metric["name"])
        line_ok(f"per_layer {metric['name']} layer", metric["layer"])
        if not UNIT.match(metric["unit"]):
            faults.append(f"per_layer {metric['name']}: unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher"):
            faults.append(f"per_layer {metric['name']}: better")
        if metric["source"] not in SOURCES:
            faults.append(f"per_layer {metric['name']}: source {metric['source']!r}")
        moved = e2e.get(metric["moves"])
        if moved is None:
            faults.append(f"per_layer {metric['name']}: moves {metric['moves']!r}")
            continue
        for cell in cells_of(metric) - cells_of(moved):
            faults.append(
                f"per_layer {metric['name']}: cell {cell} does not report {metric['moves']}"
            )
        layered |= cells_of(metric)
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    if len(names) != len(set(names)):
        faults.append("two metrics share a name")
    for cell in cells:
        reported = [m for m in e2e.values() if cell in cells_of(m)]
        if len(reported) < 2:
            faults.append(f"cell {cell}: reports no end-to-end metric beside setup_s")
        if cell not in layered:
            faults.append(f"cell {cell}: reports no per-layer metric")
        try:
            load_cell(root, cell)
        except (ManifestError, ValueError, KeyError) as err:
            faults.append(f"cell {cell}: {err}")
    return faults

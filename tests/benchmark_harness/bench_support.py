"""What the benchmark's tests share: the harness on the path, and a
temporary root holding the real manifest and data files with the toy
configuration, cells and metric of ``fixture/`` laid over them as files
and manifest entries only. A module of its own name: the repository
has other ``conftest`` modules, and ``import conftest`` means whichever
pytest loaded last."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixture"
sys.path.insert(0, str(REPO / "benchmark"))

#: The directories of data files, one file to a name.
PARTS = ("configs", "traffic", "workloads", "limits", "metrics")
#: The directory of reference kinds, one module to a view kind; the
#: benchmark has it once a cell's job is of another kind than a detector view.
KINDS = "references"


def overlay_fixture(root: Path) -> None:
    """Copy the benchmark's data (not its code) under ``root`` and add
    the fixture: new files, and new entries in the manifest's lists."""
    bench = root / "benchmark"
    for part in (*PARTS, KINDS):
        if (REPO / "benchmark" / part).is_dir():
            shutil.copytree(REPO / "benchmark" / part, bench / part,
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            (bench / part).mkdir(parents=True)
        for path in (FIXTURE / part).glob("*.*"):
            assert not (bench / part / path.name).exists(), "the fixture edits no file"
            shutil.copy(path, bench / part / path.name)
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    entries = json.loads((FIXTURE / "entries.json").read_text())
    manifest["configs"] += entries["configs"]
    manifest["workloads"] += entries["workloads"]
    manifest["per_layer"] += entries["per_layer"]
    manifest["end_to_end"] += entries["end_to_end"]
    for key, listing in (("end_to_end", "end_to_end_cells"), ("per_layer", "per_layer_cells")):
        for metric in manifest[key]:
            if "workloads" in metric:
                metric["workloads"] = metric["workloads"] + entries[listing].get(metric["name"], [])
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))


def edit_json(path, edit):
    doc = json.loads(path.read_text())
    edit(doc)
    path.write_text(json.dumps(doc))


def _edit_toy_loki(edit):
    return lambda bench: edit_json(bench / "configs" / "toy_loki.json", edit)


def _append_to_kind(text):
    def edit(bench):
        with open(bench / KINDS / "sans_iq.py", "a") as module:
            module.write(text)
    return edit


#: A broken plug: an edit to a file of the fixture's toy LOKI under the
#: root's ``benchmark/``, and a word of the sentence that names it.
PLUGS = {
    "a kind with no file": (lambda bench: (bench / KINDS / "sans_iq.py").unlink(), "no file"),
    **{
        f"a kind's module that lacks {name}": (_append_to_kind(f"\ndel {name}\n"), f"lacks {name}")
        for name in ("build", "tolerance", "work_bytes", "faults")
    },
    "a kind that names no fault": (_append_to_kind("\nfaults = dict\n"), "no fault"),
    "a check with no limit": (
        lambda bench: edit_json(bench / "limits" / "toy_loki.toy_iq.json",
                                 lambda d: d["limits"].pop("iq_bins_off")),
        "check iq_bins_off has no limit",
    ),
    "a rate_share that does not divide": (
        _edit_toy_loki(lambda d: d["streams"][1].update(rate_share=0.3)), "rate_share 0.3"),
    "a rate_share that messages_per_pulse does not divide": (
        _edit_toy_loki(lambda d: d["streams"][1].update(rate_share=1 / 4096)), "rate_share"),
    "an aux binding to a stream the configuration lacks": (
        _edit_toy_loki(lambda d: d["jobs"][0]["aux_source_names"].update(monitor="monitor_9")),
        "names no stream 'monitor_9'",
    ),
    "a job on a stream the configuration lacks": (
        _edit_toy_loki(lambda d: d["jobs"][0].update(stream="rear")), "no stream 'rear'"),
    "a stream of an unknown kind": (
        _edit_toy_loki(lambda d: d["streams"][1].update(kind="hologram")), "kind 'hologram'"),
    "a camera stream that lacks a key": (
        _edit_toy_loki(lambda d: d["streams"].append(
            {"name": "orca", "kind": "camera", "wire_source": "loki_orca", "topic": "loki_camera",
             "frame_shape": [8, 8]})),
        "camera stream orca lacks 'dtype'",
    ),
    "a camera stream of a type ad00 has not": (
        _edit_toy_loki(lambda d: d["streams"].append(
            {"name": "orca", "kind": "camera", "wire_source": "loki_orca", "topic": "loki_camera",
             "frame_shape": [8, 8], "dtype": "float16"})),
        "dtype 'float16' is no ad00 type",
    ),
    "a camera view of an event stream": (
        _edit_toy_loki(lambda d: d["jobs"][0]["view"].update(kind="frames")),
        "a view of kind 'frames' on the detector stream",
    ),
    "a view kind that is no name": (
        _edit_toy_loki(lambda d: d["jobs"][0]["view"].update(kind="../sans_iq")), "is not a name"),
}

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


def overlay_fixture(root: Path) -> None:
    """Copy the benchmark's data (not its code) under ``root`` and add
    the fixture: new files, and new entries in the manifest's lists."""
    bench = root / "benchmark"
    for part in PARTS:
        shutil.copytree(REPO / "benchmark" / part, bench / part)
        for path in (FIXTURE / part).iterdir():
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

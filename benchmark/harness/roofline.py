"""The least time the chip could take for a cell's ticks, from shapes.

Histogramming counts needs no FLOP, so the bound is HBM bytes over the
chip's peak bytes/s. The bytes are what the work must move whatever
implements it (an MXU one-hot kernel does not change the count):

- per event staged for a job: its flat index in (4 B), one bin read and
  one bin written (4 B each);
- per publish of a job: the fold reads window and cumulative and writes
  cumulative and the cleared window (4 x bins x 4 B), and the outputs
  are fetched (two images, two spectra, four scalars, float32).
"""

from __future__ import annotations

import json
from pathlib import Path

EVENT_BYTES = 12
FOLD_PASSES = 4


def peak(device_kind: str, table_path: Path | None = None) -> dict:
    path = table_path or Path(__file__).resolve().parent / "peaks.json"
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}")
    return table[device_kind]


def screen_bins(view: dict) -> int:
    if view["kind"] == "grid":
        return view["shape"][0] * view["shape"][1]
    sizes = view["sizes"]
    total = 1
    for dim in (*view["y"], *view.get("x", [])):
        total *= sizes[dim]
    return total


def job_bytes(job: dict, toa_bins: int, events: int, publishes: int) -> int:
    """Bytes one job must move for ``events`` staged and ``publishes``."""
    screen = screen_bins(job["view"])
    bins = screen * toa_bins
    fetched = 4 * (2 * screen + 2 * toa_bins + 4)
    return events * EVENT_BYTES + publishes * (FOLD_PASSES * bins * 4 + fetched)


def least_seconds(config: dict, events_per_job: dict, publishes_per_job: dict, device_kind: str,
                  kinds: dict | None = None) -> float:
    """The bytes of every job over the chip's peak bytes/s. A job of a
    kind that ``job_bytes`` does not know is reckoned by its kind's own
    ``work_bytes`` (``benchmark/references/<kind>.py``)."""
    total = 0
    for job in config["jobs"]:
        events, publishes = events_per_job[job["name"]], publishes_per_job[job["name"]]
        kind = (kinds or {}).get(job["view"]["kind"])
        if kind is None:
            total += job_bytes(job, config["toa_bins"], events, publishes)
        else:
            total += kind.work_bytes(job, config, events, publishes)
    return total / peak(device_kind)["hbm_bytes_per_s"]

"""From the profiler's trace and the tick spans to per-layer numbers.

``load`` turns the ``.xplane.pb`` the service wrote (``--profile``) and
its tick spans (``--trace-dump``) into plain lists; ``reduce`` cuts them
to the measured window and reduces. The profiler starts with the
service, so the trace holds set-up too: everything is cut by time.

Clocks: trace events are ns since the session's ``profile_start_time``
(epoch ns, in the "Task Environment" plane); tick spans and the window
are on CLOCK_MONOTONIC; ``load`` is given the epoch-minus-monotonic
offset and puts everything on epoch ns.
"""

from __future__ import annotations

import json
import os
import re
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(profile_dir: Path, ticks_path: Path, epoch_minus_mono_ns: int) -> dict | None:
    """{"ops": {device: [[name, start, dur]]}, "modules": {...},
    "spans": [[name, start, dur, tick id]]}, all in epoch ns; None without a trace."""
    found = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        return None
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # the chip's owner has exited
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(found[-1]))
    start = None
    ops, modules = {}, {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    if start is None:
        return None
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            target = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
            if target is None:
                continue
            target[plane.name] = [
                [event.name, int(start + event.start_ns), int(event.duration_ns)]
                for event in line.events
            ]
    spans = []
    try:
        doc = json.loads(Path(ticks_path).read_text())
    except (OSError, json.JSONDecodeError):
        doc = {"traceEvents": []}
    for event in doc["traceEvents"]:
        spans.append([
            event["name"],
            int(event["ts"] * 1e3) + epoch_minus_mono_ns,
            int(event["dur"] * 1e3),
            event.get("args", {}).get("trace_id", 0),
        ])
    return {"ops": ops, "modules": modules, "spans": spans}


_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTRUCTION = re.compile(r"^(%[\w.\-]+) = (.*?) ([\w\-]+)\(")


def short_name(text: str) -> str:
    """'opcode %name result-type' of an HLO instruction's text, layouts
    and operands dropped; other names as they are, cut to 120."""
    match = _INSTRUCTION.match(_LAYOUT.sub("", text))
    if match:
        name, result, opcode = match.groups()
        text = f"{opcode} {name} {result}"
    return text[:120]


def clip(intervals, t0: int, t1: int):
    """[(start, end)] cut to [t0, t1), empty ones dropped."""
    out = []
    for start, end in intervals:
        start, end = max(start, t0), min(end, t1)
        if end > start:
            out.append((start, end))
    return out


def union(intervals) -> list[tuple[int, int]]:
    """The intervals merged where they touch or overlap, in order."""
    merged: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def busy_ns(intervals) -> int:
    return sum(end - start for start, end in union(intervals))


def gaps(merged, t0: int, t1: int) -> list[tuple[int, int]]:
    """The idle stretches of [t0, t1) between merged busy intervals."""
    out, at = [], t0
    for start, end in merged:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if t1 > at:
        out.append((at, t1))
    return out


def tick_envelopes(spans) -> list[tuple[int, int]]:
    """(start, end) of each tick: its first span's start to its last
    span's end. ``spans`` rows are [name, start, dur, tick id]."""
    by_tick: dict[int, list[int]] = {}
    for _name, start, dur, tick in spans:
        bounds = by_tick.setdefault(tick, [start, start + dur])
        bounds[0], bounds[1] = min(bounds[0], start), max(bounds[1], start + dur)
    return sorted((lo, hi) for lo, hi in by_tick.values())


def name_gap(gap, spans, envelopes) -> dict[str, int]:
    """An idle stretch of the device, in ns by what the host was doing:
    under each tick span its name; inside a tick but under no span
    ``stage`` (the serial loop has no span for flatten and H2D); outside
    every tick ``between_ticks`` (the loop waits for a window to close).
    Spans of one loop do not overlap, so the parts add up to the gap."""
    parts: dict[str, int] = {}
    spanned = 0
    for name, start, dur, _tick in spans:
        cover = min(gap[1], start + dur) - max(gap[0], start)
        if cover > 0:
            parts[name] = parts.get(name, 0) + cover
            spanned += cover
    in_tick = sum(e - s for s, e in clip(envelopes, gap[0], gap[1]))
    if in_tick > spanned:
        parts["stage"] = in_tick - spanned
    if gap[1] - gap[0] > max(in_tick, spanned):
        parts["between_ticks"] = gap[1] - gap[0] - max(in_tick, spanned)
    return parts


def reduce(events: dict, t0: int, t1: int, batches: int | None = None) -> dict:
    """Busy and idle seconds averaged over the devices used, device ms per
    batch, and the breakdown, for the window [t0, t1) in epoch ns."""
    per_device = []
    op_seconds: dict[str, float] = {}
    gap_seconds: dict[str, float] = {}
    lines = events["ops"] or events["modules"]
    for device, rows in lines.items():
        cut = clip(((s, s + d) for _n, s, d in rows), t0, t1)
        if not cut:
            continue
        merged = union(cut)
        per_device.append(sum(e - s for s, e in merged))
        for name, start, dur in rows:
            inside = min(start + dur, t1) - max(start, t0)
            if inside > 0:
                name = short_name(name)
                op_seconds[name] = op_seconds.get(name, 0.0) + inside / 1e9
        spans = [s for s in events["spans"] if s[1] < t1 and s[1] + s[2] > t0]
        envelopes = tick_envelopes(spans)
        for gap in gaps(merged, t0, t1):
            for name, ns in name_gap(gap, spans, envelopes).items():
                gap_seconds[name] = gap_seconds.get(name, 0.0) + ns / 1e9
    if not per_device:
        return {}
    window_s = (t1 - t0) / 1e9
    busy_s = sum(per_device) / len(per_device) / 1e9
    if batches is None:
        batches = sum(1 for name, start, *_ in events["spans"] if name == "decode" and t0 <= start < t1)
    top = lambda table: [  # noqa: E731
        [name, seconds] for name, seconds in sorted(table.items(), key=lambda kv: -kv[1])[:10]
    ]
    out = {
        "busy_s": busy_s,
        "window_s": window_s,
        "device_idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "breakdown": {"device_ops": top(op_seconds), "idle_gaps": top(gap_seconds)},
    }
    if batches:
        out["tick_device_ms"] = 1e3 * busy_s / batches
        out["batches"] = batches
    return out

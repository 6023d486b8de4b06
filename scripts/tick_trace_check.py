#!/usr/bin/env python3
"""Read a profiler trace of the service beside its span dump:

    python scripts/tick_trace_check.py XPLANE [DUMP]

``XPLANE`` is a ``.xplane.pb`` or a directory that holds one (what
``--profile DIR`` or ``POST /profile`` wrote; the newest is taken), ``DUMP``
the ``--trace-dump`` file of the same process. It runs nothing: any trace
of any service will do. The report, JSON on stdout, is what PERF.md
section 6 quotes:

- **scopes**: device time by ``jax.named_scope`` (``scatter`` / ``fold`` /
  ``publish_reduce`` / ``pack``; ``scatter/replica_gather``, the LUT
  gather of a view that projects on the device, listed apart from the
  rest of its ``scatter``, and ``scatter/count_sort``, the key sort in
  front of the MXU count, ADR 0131; ``qmap_gather`` / ``q_bincount`` of
  a Q step) and by jitted program, with the heaviest
  ops of each scope (cut to the dump's steady ticks where there is a dump);
- **twins**: the ``TraceAnnotation`` twins of the tick spans in the host
  planes, counted by name;
- with a dump, the **clock check**: every ring span, shifted by the dump's
  own ``epoch_minus_clock_ns``, against its twin; the largest difference
  over every tick. And ``hold_us`` of the ``decode`` spans, tick by tick;
- with a dump, the **pool phase** by thread (``pool_phase``): the ``h2d``
  and ``q_step`` spans that a tick's job threads recorded (every thread
  but the one of the tick's ``decode``), tick by tick: each thread's
  seconds of either, its first start and last end, which thread finished
  last, and the median over ticks of (last end - first start) beside the
  median of the ``accumulate_wait`` twin, the loop thread's wait for them.

What it does not find it leaves out.
"""

from __future__ import annotations

import json
import re
import statistics
import sys
import time
from pathlib import Path

SCOPES = (
    "scatter",
    "replica_gather",
    "count_sort",
    "fold",
    "publish_reduce",
    "pack",
    "qmap_gather",
    "qmap_sort",
    "q_bincount",
)
# The profiler's own names for a TPU's plane and its two lines.
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_PROGRAM = re.compile(r"jit\(([\w.\-]+)\)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_CALL = re.compile(r"^(.*?\s[\w\-]+)\(")


def scope_of(texts) -> tuple[str, str]:
    """(scope path, program) from an op's string stats: the op_name
    metadata reads ``jit(tick_detector_view)/.../scatter/...``."""
    for text in texts:
        parts = [p for p in text.split("/") if p in SCOPES]
        program = _PROGRAM.search(text)
        if parts or program:
            return "/".join(parts) or "(no scope)", program.group(1) if program else "?"
    return "(no metadata)", "?"


def short_op(text: str) -> str:
    """An HLO instruction's text without layouts and operands."""
    match = _CALL.match(_LAYOUT.sub("", text))
    return (match.group(1) if match else text)[:120]


def _varint(buf, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for the rest."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        else:
            size = 8 if kind == 1 else 4
            value, at = buf[at:at + size], at + size
        yield key >> 3, value


def op_metadata(xplane: Path, plane_prefix: str) -> dict[str, dict[str, str]]:
    """HLO instruction text -> {stat name: string value} from the event
    metadata of the planes named ``plane_prefix``. ``jax.profiler.
    ProfileData`` gives an event's own stats (offsets, durations) and not
    its metadata's, which is where the compiler's ``op_name`` (the named
    scopes) lives; so the ``XSpace`` is read here as plain protobuf wire:
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5 (maps: key 1, value 2); XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7."""
    out: dict[str, dict[str, str]] = {}
    space = memoryview(Path(xplane).read_bytes())
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stats = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                events.append(value)
            elif field == 5:
                entry = dict(_fields(value))
                meta = dict(_fields(entry.get(2, b"")))
                stats[entry.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith(plane_prefix):
            continue
        for entry in events:
            op_name, found = "", {}
            for field, value in _fields(dict(_fields(entry)).get(2, b"")):
                if field == 2:
                    op_name = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    if 5 in stat:
                        text = bytes(stat[5]).decode(errors="replace")
                    elif 7 in stat:
                        text = stats.get(stat[7], "")
                    else:
                        continue
                    found[stats.get(stat.get(1, 0), "?")] = text
            if op_name:
                out[op_name] = found
    return out


def find_xplane(path: Path) -> Path | None:
    path = Path(path)
    if path.is_file():
        return path
    found = sorted(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def clock_check(ring, twins, start_ns: int, offset_ns: int) -> dict:
    """Ring span starts (perf_counter ns, by (name, trace id)) shifted
    onto the epoch by ``offset_ns``, against the twins' (ns after the
    session's ``start_ns``), pairing the k-th with the k-th."""
    worst = total = 0.0
    paired = unpaired = 0
    by_name: dict[str, float] = {}
    for key, starts in ring.items():
        theirs = sorted(twins.get(key, []))
        if len(theirs) != len(starts):
            unpaired += len(starts)
            continue
        for mine, twin in zip(sorted(starts), theirs):
            diff = mine + offset_ns - (start_ns + twin)
            paired += 1
            total += diff
            worst = max(worst, abs(diff))
            by_name[key[0]] = max(by_name.get(key[0], 0.0), abs(diff))
    return {
        "paired": paired,
        "unpaired_ring_spans": unpaired,
        "ticks": len({tick for _name, tick in ring}),
        "largest_abs_diff_us": worst / 1e3,
        "mean_diff_us": total / paired / 1e3 if paired else None,
        "largest_abs_diff_us_by_span": {
            name: value / 1e3 for name, value in sorted(by_name.items())
        },
    }


POOL_SPANS = ("h2d", "q_step")


def pool_phase(events, waits_ns: dict[int, int] | None = None) -> dict | None:
    """The accumulate phase by job thread, from a dump's events (Chrome
    ``X`` events: ``ts`` / ``dur`` in us, ``tid`` the thread's name).
    ``waits_ns``: the ``accumulate_wait`` twin's duration by trace id,
    where a profiler trace gave one."""
    loop_thread = {e["args"]["trace_id"]: e["tid"] for e in events if e["name"] == "decode"}
    ticks: dict[int, dict[str, dict]] = {}
    for event in events:
        tick = event["args"]["trace_id"]
        if event["name"] not in POOL_SPANS or event["tid"] == loop_thread.get(tick):
            continue
        row = ticks.setdefault(tick, {}).setdefault(
            event["tid"], {"h2d_ms": 0.0, "q_step_ms": 0.0, "first": event["ts"], "last": 0.0}
        )
        row[event["name"] + "_ms"] += event["dur"] / 1e3
        row["first"] = min(row["first"], event["ts"])
        row["last"] = max(row["last"], event["ts"] + event["dur"])
    if not ticks:
        return None
    by_tick, finished_last = [], {}
    for tick, threads in sorted(ticks.items()):
        first = min(row["first"] for row in threads.values())
        last_thread = max(threads, key=lambda tid: threads[tid]["last"])
        finished_last[last_thread] = finished_last.get(last_thread, 0) + 1
        entry = {
            "trace_id": tick,
            "wall_ms": (threads[last_thread]["last"] - first) / 1e3,
            "finished_last": last_thread,
            "threads": {
                tid: {
                    "h2d_ms": row["h2d_ms"],
                    "q_step_ms": row["q_step_ms"],
                    "first_start_ms": (row["first"] - first) / 1e3,
                    "last_end_ms": (row["last"] - first) / 1e3,
                }
                for tid, row in sorted(threads.items())
            },
        }
        if waits_ns and tick in waits_ns:
            entry["accumulate_wait_ms"] = waits_ns[tick] / 1e6
        by_tick.append(entry)
    out = {
        "ticks": len(by_tick),
        "wall_ms_median": statistics.median(e["wall_ms"] for e in by_tick),
        "finished_last": dict(sorted(finished_last.items())),
    }
    waited = [e["accumulate_wait_ms"] for e in by_tick if "accumulate_wait_ms" in e]
    if waited:
        out["accumulate_wait_ms_median"] = statistics.median(waited)
    out["by_tick"] = by_tick
    return out


def analyse(xplane: Path, dump: Path | None = None) -> dict:
    from jax.profiler import ProfileData

    report: dict = {}
    events = []
    if dump is not None:
        doc = json.loads(Path(dump).read_text())
        events = doc["traceEvents"]
        report["dump_clock"] = doc.get("clock")
        report["dump_offset_ns"] = doc.get("epoch_minus_clock_ns")
        report["ring_spans"] = len(events)
    found = find_xplane(xplane)
    if found is None:
        report["error"] = "no xplane"
        if events and (pool := pool_phase(events)):
            report["pool_phase"] = pool
        return report
    data = ProfileData.from_file(str(found))
    start = None
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
    report["profile_start_time"] = start

    # -- the annotation twins: host events that carry a trace id -------------
    twins: dict[tuple[str, int], list[int]] = {}
    twin_names: dict[str, int] = {}
    waits_ns: dict[int, int] = {}  # the accumulate_wait twin, one a tick
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for event in line.events:
                stats = dict(event.stats)
                if "trace_id" not in stats:
                    continue
                key = (event.name, int(stats["trace_id"]))
                twins.setdefault(key, []).append(int(event.start_ns))
                twin_names[event.name] = twin_names.get(event.name, 0) + 1
                if event.name == "accumulate_wait":
                    waits_ns[key[1]] = int(event.duration_ns)
    report["twins_by_name"] = dict(sorted(twin_names.items()))
    if events and (pool := pool_phase(events, waits_ns)):
        report["pool_phase"] = pool

    offset = report.get("dump_offset_ns")
    lo, hi = float("-inf"), float("inf")
    if events:
        ring: dict[tuple[str, int], list[float]] = {}
        for event in events:
            key = (event["name"], event["args"]["trace_id"])
            ring.setdefault(key, []).append(event["ts"] * 1e3)
        if start is not None and offset is not None and twins:
            report["clock_check"] = clock_check(ring, twins, start, offset)
            # What a reader in another process would shift by (the
            # benchmark's harness does, after the service's exit): only
            # on the machine and boot that recorded the dump.
            report["this_process_offset_minus_dump_us"] = (
                time.time_ns() - time.monotonic_ns() - offset
            ) / 1e3
        decodes = sorted(
            (e["ts"], e["args"].get("hold_us")) for e in events if e["name"] == "decode"
        )
        report["hold_us_by_tick"] = [hold for _ts, hold in decodes]
        # the steady part: from the first tick with a ``fetch`` span
        # (compile rounds record none) to the last span
        fetches = [e["ts"] * 1e3 for e in events if e["name"] == "fetch"]
        if start is not None and offset is not None and fetches:
            lo = min(fetches) + offset - start - 1e9
            hi = max((e["ts"] + e["dur"]) * 1e3 for e in events) + offset - start

    # -- device time by scope and program --------------------------------------
    metadata = op_metadata(found, DEVICE_PLANE)
    scopes: dict[str, float] = {}
    programs: dict[str, float] = {}
    ops: dict[str, dict[str, float]] = {}
    modules: dict[str, int] = {}
    for plane in data.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        for line in plane.lines:
            for event in line.events:
                if not lo <= event.start_ns <= hi:
                    continue
                if line.name == MODULES_LINE:
                    modules[event.name[:80]] = modules.get(event.name[:80], 0) + 1
                if line.name != OPS_LINE:
                    continue
                scope, program = scope_of(metadata.get(event.name, {}).values())
                seconds = event.duration_ns / 1e9
                scopes[scope] = scopes.get(scope, 0.0) + seconds
                programs[program] = programs.get(program, 0.0) + seconds
                table = ops.setdefault(scope, {})
                short = short_op(event.name)
                table[short] = table.get(short, 0.0) + seconds
    if scopes:
        report["device_s_by_scope"] = _by_time(scopes)
        report["device_s_by_program"] = _by_time(programs)
        report["top_ops_by_scope"] = {
            scope: list(_by_time(table).items())[:6] for scope, table in ops.items()
        }
        report["modules"] = modules
    return report


def _by_time(table: dict[str, float]) -> dict[str, float]:
    return dict(sorted(table.items(), key=lambda kv: -kv[1]))


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else list(argv)
    if not 1 <= len(args) <= 2 or args[0].startswith("-"):
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    report = analyse(Path(args[0]), Path(args[1]) if len(args) == 2 else None)
    print(json.dumps(report, indent=1))
    return 1 if "error" in report else 0


if __name__ == "__main__":
    raise SystemExit(main())

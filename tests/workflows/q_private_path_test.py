"""The private step path of the Q family (a window that carries monitor
events beside the detector's), as the manager counts it and the tracer
sees it: ``livedata_job_windows_total{path}``, the ``q_step`` span and
the table instruments."""

from __future__ import annotations

import gc

import numpy as np
import pytest
from q_tick_test import make_manager, make_sans, staged, windows

from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.telemetry.instruments import JOB_WINDOWS, TABLE_BUILD_SECONDS, TABLE_BYTES
from esslivedata_tpu.telemetry.trace import TRACER

T = Timestamp.from_ns


def paths() -> dict[str, float]:
    return {path: JOB_WINDOWS.value(path=path) for path in ("tick", "fused", "private")}


def delta(before: dict[str, float]) -> dict[str, float]:
    return {path: value - before[path] for path, value in paths().items()}


@pytest.mark.parametrize(
    "with_monitor, tick_program, expected",
    [
        (True, True, {"tick": 0, "fused": 0, "private": 2}),  # monitor events: not tick-eligible
        (False, True, {"tick": 2, "fused": 0, "private": 0}),  # the detector alone: one tick program each
        (False, False, {"tick": 0, "fused": 0, "private": 2}),  # no tick combiner: singletons step privately
    ],
)
def test_job_windows_are_counted_by_the_path_that_stepped_them(with_monitor, tick_program, expected):
    manager = make_manager([lambda: make_sans("mon0")] * 2, tick=tick_program, aux="mon0")
    try:
        for w, (pid, toa) in enumerate(windows(5, 3)):
            data = {"det0": staged(pid, toa)}
            if with_monitor:
                data["mon0"] = staged(np.zeros(40, np.int64), np.linspace(1, 1e6, 40))
            before = paths()
            results = manager.process_jobs(data, start=T(0), end=T(w + 1))
            assert len(results) == 2
            assert delta(before) == expected
            monitor = float(results[0].outputs["monitor_counts_current"].values)
            assert monitor == (40.0 if with_monitor else 0.0)
        before = paths()
        manager.process_jobs({}, start=T(0), end=T(9))  # a window with no data steps nothing
        assert delta(before) == {"tick": 0, "fused": 0, "private": 0}
    finally:
        manager.shutdown()


@pytest.mark.parametrize("job_threads", [1, 2])
def test_q_step_is_a_ring_span_of_the_window_that_encloses_no_other(job_threads):
    """On the loop thread and on the pool's threads alike: the step's
    dispatch comes after its staging, so no ``h2d`` lies inside it."""
    manager = make_manager([lambda: make_sans("mon0")] * job_threads, aux="mon0")
    trace_id = TRACER.new_trace()
    try:
        pid, toa = windows(6, 1)[0]
        data = {"det0": staged(pid, toa), "mon0": staged(np.zeros(8, np.int64), np.ones(8))}
        with TRACER.bind(trace_id):
            manager.process_jobs(data, start=T(0), end=T(1))
    finally:
        manager.shutdown()
    spans = TRACER.spans(trace_id)
    steps = [s for s in spans if s.name == "q_step"]
    assert len(steps) == job_threads and "h2d" in {s.name for s in spans}
    for step in steps:
        inside = [
            s for s in spans
            if s is not step and s.thread == step.thread
            and s.start_s < step.start_s + step.duration_s and step.start_s < s.start_s + s.duration_s
        ]
        assert inside == [], f"{[s.name for s in inside]} overlap q_step"
    staging = min(s.start_s for s in spans if s.name == "h2d")
    assert staging < min(s.start_s for s in steps)


def test_a_tick_stepped_window_records_no_q_step():
    manager = make_manager([make_sans])
    trace_id = TRACER.new_trace()
    try:
        pid, toa = windows(7, 1)[0]
        with TRACER.bind(trace_id):
            manager.process_jobs({"det0": staged(pid, toa)}, start=T(0), end=T(1))
    finally:
        manager.shutdown()
    assert "q_step" not in {s.name for s in TRACER.spans(trace_id)}


def test_the_tables_bytes_and_build_seconds_are_on_the_scrape():
    gc.collect()
    bytes_before = TABLE_BYTES.value(family="sans_iq")
    built_before = TABLE_BUILD_SECONDS.value(family="sans_iq")
    workflow = make_sans()
    table = workflow._hist._qmap
    assert TABLE_BYTES.value(family="sans_iq") - bytes_before == table.size * table.dtype.itemsize
    built = TABLE_BUILD_SECONDS.value(family="sans_iq")
    assert built > built_before
    workflow._hist.swap_table(np.asarray(table))  # a hand-made table of the same shape: a transfer, no bytes
    assert TABLE_BUILD_SECONDS.value(family="sans_iq") > built
    assert TABLE_BYTES.value(family="sans_iq") - bytes_before == table.size * table.dtype.itemsize
    del workflow, table
    gc.collect()
    assert TABLE_BYTES.value(family="sans_iq") == bytes_before  # the kernel went, and its table with it

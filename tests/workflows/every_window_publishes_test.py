"""A window that carried data for a due job publishes, always, on each
of the manager's three ways to step a job (``JobManager.process_jobs``),
and ``livedata_job_windows_total{path}`` names the way taken.

The census at the bottom is the default service's: for every family of
``harness/tick_contract.py``, which path two same-layout jobs on one
stream of a default ``JobManager`` take. ``fused`` is none of them: a
default service reaches the fused ``step_many`` + ``PublishCombiner``
path only where ``_split_tick_groups`` refuses a group (ROADMAP D4)."""

from __future__ import annotations

import numpy as np
import pytest
from q_private_path_test import delta, paths
from q_tick_test import make_manager as make_q_manager
from q_tick_test import make_sans, staged

from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.job_manager import JobFactory, JobManager
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.harness.tick_contract import REGISTRY
from esslivedata_tpu.workflows import WorkflowFactory
from esslivedata_tpu.workflows.detector_view import (
    DetectorViewWorkflow,
    project_logical,
)

T = Timestamp.from_ns
N_WINDOWS = 3


def events(seed: int, n_pixel: int, n: int = 500):
    rng = np.random.default_rng(seed)
    return staged(
        rng.integers(0, n_pixel, n).astype(np.int64),
        rng.uniform(0, 7e7, n).astype(np.float32),
    )


def two_jobs_on(stream: str, make, **manager_options) -> JobManager:
    """Two jobs of one workflow on ``stream``, everything else the
    manager's default."""
    reg = WorkflowFactory()
    spec = WorkflowSpec(instrument="ewp", name="wf", source_names=[stream])
    reg.register_spec(spec).attach_factory(
        lambda *, source_name, params: make()
    )
    mgr = JobManager(job_factory=JobFactory(reg), **manager_options)
    for _ in range(2):
        mgr.schedule_job(
            WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name=stream)
            )
        )
    return mgr


def detector_views(**manager_options) -> JobManager:
    det = np.arange(144).reshape(12, 12)
    return two_jobs_on(
        "det0",
        lambda: DetectorViewWorkflow(projection=project_logical(det)),
        **manager_options,
    )


def q_jobs_with_a_monitor() -> JobManager:
    return make_q_manager([lambda: make_sans("mon0")] * 2, aux="mon0")


#: path -> (the jobs, one window of them, the output that holds the
#: window's own counts).
STEP_PATHS = {
    # Two same-layout jobs on one stream: one tick program steps and
    # publishes both.
    "tick": (
        detector_views,
        lambda w: {"det0": events(w, 144)},
        "counts_current",
    ),
    # The same two without the tick program: one fused step, then one
    # combined publish.
    "fused": (
        lambda: detector_views(tick_program=False),
        lambda w: {"det0": events(w, 144)},
        "counts_current",
    ),
    # A window that holds monitor events beside the detector's is not
    # the one primary stream a tick takes: the workflow's own step.
    "private": (
        q_jobs_with_a_monitor,
        lambda w: {"det0": events(w, 64), "mon0": events(100 + w, 1, n=40)},
        "counts_q_current",
    ),
}


@pytest.mark.parametrize("path", list(STEP_PATHS))
def test_every_data_window_publishes(path):
    make_jobs, window, window_counts = STEP_PATHS[path]
    manager = make_jobs()
    try:
        for w in range(N_WINDOWS):
            before = paths()
            results = manager.process_jobs(
                window(w), start=T(0), end=T(w + 1)
            )
            assert len(results) == 2, f"window {w} did not publish"
            for result in results:
                current = result.outputs[window_counts].values.sum()
                assert current > 0, f"window {w} published no new counts"
            assert delta(before) == {
                p: (2 if p == path else 0) for p in STEP_PATHS
            }
    finally:
        manager.shutdown()


#: family -> (stream of its two jobs, one window of it).
CENSUS = {
    "detector_view": ("det0", lambda w: events(w, 144)),
    "monitor": ("mon0", lambda w: events(w, 1)),
    "q_sans": ("det0", lambda w: events(w, 64)),
    "powder_focus": ("det0", lambda w: events(w, 48)),
    "imaging": ("det0", lambda w: events(w, 64)),
    "correlation": ("a", lambda w: float(w + 1)),
}


def test_the_census_covers_every_registered_family():
    assert set(CENSUS) == set(REGISTRY)


@pytest.mark.parametrize("family", list(CENSUS))
def test_a_default_service_never_takes_the_fused_path(family):
    stream, window = CENSUS[family]
    spec = REGISTRY[family]
    manager = two_jobs_on(stream, lambda: spec.make_workflow("base"))
    offers_ingest = (
        spec.make_workflow("base").event_ingest(stream, events(0, 1))
        is not None
    )
    before = paths()
    try:
        for w in range(N_WINDOWS):
            results = manager.process_jobs(
                {stream: window(w)}, start=T(0), end=T(w + 1)
            )
            assert len(results) == 2, f"window {w} did not publish"
    finally:
        manager.shutdown()
    taken = "tick" if offers_ingest else "private"
    assert delta(before) == {
        p: (2 * N_WINDOWS if p == taken else 0) for p in STEP_PATHS
    }
    assert offers_ingest == (family != "correlation")

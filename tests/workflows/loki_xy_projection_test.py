"""LOKI's deployed 2-D view, ``detector_view/xy_projection``: the spec
on each of the nine banks at full size through the manager's tick
program, as the manager and the histogrammer count it
(``livedata_job_windows_total{path}``, ``livedata_view_wires_total``,
``livedata_scatter_updates_total``, the ``projection`` family of the
table instruments), and the replicas' weights."""

from __future__ import annotations

import gc

import numpy as np
import pytest

from esslivedata_tpu.config import JobId, WorkflowConfig
from esslivedata_tpu.core.job_manager import JobFactory, JobManager
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.ops import EventBatch
from esslivedata_tpu.ops.histogram import EventHistogrammer
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.telemetry.instruments import (
    JOB_WINDOWS,
    SCATTER_UPDATES,
    STAGED_EVENTS,
    TABLE_BUILD_SECONDS,
    TABLE_BYTES,
    VIEW_WIRES,
)
from esslivedata_tpu.workflows.detector_view.projectors import project_geometric, project_logical

BANKS = [f"loki_detector_{i}" for i in range(9)]
T = Timestamp.from_ns


def staged(pid, toa) -> StagedEvents:
    return StagedEvents(
        batch=EventBatch.from_arrays(np.asarray(pid), np.asarray(toa, np.float32)),
        first_timestamp=None, last_timestamp=None, n_chunks=1,
    )


def counters() -> dict[str, float]:
    return {
        "tick": JOB_WINDOWS.value(path="tick"),
        "private": JOB_WINDOWS.value(path="private"),
        "fused": JOB_WINDOWS.value(path="fused"),
        "raw": VIEW_WIRES.value(staging="raw"),
        "flat": VIEW_WIRES.value(staging="flat"),
        "updates": SCATTER_UPDATES.value(),
        "slots": STAGED_EVENTS.value(kind="staged"),
        "table_bytes": TABLE_BYTES.value(family="projection"),
        "table_s": TABLE_BUILD_SECONDS.value(family="projection"),
    }


@pytest.fixture(scope="module")
def loki(tmp_path_factory):
    """The instrument with its factories, its geometry synthesized into
    a directory of this module's, and no projection kept afterwards."""
    from esslivedata_tpu.config.instruments.loki import factories, specs
    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("LIVEDATA_DATA_DIR", str(tmp_path_factory.mktemp("loki_geometry")))
        specs.INSTRUMENT.load_factories()
        yield specs, workflow_registry
        factories._projection_for.cache_clear()


@pytest.mark.parametrize("bank", BANKS)
def test_the_spec_starts_on_a_bank_and_its_window_takes_the_tick_program_on_a_raw_wire(loki, bank):
    """Started with its defaults, as a dashboard starts it: the LUT is
    built at job start (four replicas over the bank's id space), one
    window of events on the bank's own ids is stepped and published by
    one tick program, staged as the raw pair, four updates a slot."""
    specs, registry = loki
    identifier = registry[specs.XY_PROJECTION_HANDLE.workflow_id].identifier
    declared = specs.INSTRUMENT.detectors[bank]
    first = 1 + sum(specs.BANK_PIXELS[b] for b in BANKS[: BANKS.index(bank)])
    n = specs.BANK_PIXELS[bank]
    rng = np.random.default_rng(BANKS.index(bank))
    ids = np.concatenate([rng.integers(first, first + n, 5000), [0, first - 1, first + n]])  # three out of range
    toa = rng.uniform(0.0, 7.0e7, ids.size)
    gc.collect()
    before = counters()
    manager = JobManager(job_factory=JobFactory(registry), job_threads=2, combine_publish=True, tick_program=True)
    try:
        manager.schedule_job(WorkflowConfig(identifier=identifier, job_id=JobId(source_name=bank)))
        built = counters()
        (result,) = manager.process_jobs({bank: staged(ids, toa)}, start=T(0), end=T(1))
        after = counters()
    finally:
        manager.shutdown()
    assert (declared.noise_sigma, declared.n_replica, declared.projection) == (0.004, 4, "xy_plane")
    # job start: a LUT of four rows over every id up to the bank's last, counted as built
    assert built["table_bytes"] - before["table_bytes"] == 4 * 4 * (first + n)
    assert built["table_s"] > before["table_s"]
    assert all(built[k] == before[k] for k in ("tick", "raw", "flat", "updates", "slots"))
    stepped = {k: after[k] - built[k] for k in built}
    assert (stepped["tick"], stepped["private"], stepped["fused"]) == (1, 0, 0)
    assert (stepped["raw"], stepped["flat"]) == (1, 0)
    assert stepped["slots"] == 8192 and stepped["updates"] == 4 * 8192
    image = np.asarray(result.outputs["image_current"].values)
    assert image.shape == (256, 256) and result.outputs["spectrum_current"].values.shape == (100,)
    total = float(result.outputs["counts_current"].values)
    assert total == image.sum() and (4 * total).is_integer() and 0.97 * 5000 < total <= 5000


def test_the_spec_refuses_the_toy_plane_and_rear_view_stays_on_it(loki):
    specs, registry = loki
    spec = registry[specs.XY_PROJECTION_HANDLE.workflow_id]
    assert spec.source_names == BANKS
    with pytest.raises(ValueError, match="not valid"):
        registry.create(WorkflowConfig(identifier=spec.identifier, job_id=JobId(source_name="larmor_detector")))
    rear = registry[specs.DETECTOR_VIEW_HANDLE.workflow_id]
    assert rear.source_names == ["larmor_detector"]
    with pytest.raises(ValueError, match="not valid"):
        registry.create(WorkflowConfig(identifier=rear.identifier, job_id=JobId(source_name=BANKS[0])))
    toy = specs.INSTRUMENT.detectors["larmor_detector"]
    assert (toy.noise_sigma, toy.n_replica) == (0.002, 4)


@pytest.mark.parametrize("replicas", [1, 4])
def test_the_replicas_weights_of_an_on_screen_pixel_add_to_one(replicas):
    """A pixel in the middle of a 9 x 9 plane, 100 events: every replica
    lands on the screen and the picture's total is the events', 1/R a
    replica; the raw wire is staged only where there are replicas."""
    y, x = np.mgrid[0:9, 0:9]
    positions = np.stack([x.ravel() * 0.01, y.ravel() * 0.01, np.full(81, 5.0)], axis=1)
    table = project_geometric(
        positions, np.arange(1, 82), resolution=(18, 18), noise_sigma=0.004, n_replica=replicas)
    assert table.lut.shape == (replicas, 82) and np.all(table.lut[:, 41] >= 0)
    hist = EventHistogrammer(toa_edges=np.linspace(0.0, 7.0e7, 11), n_screen=table.n_screen, pixel_lut=table.lut)
    assert hist.supports_host_flatten == (replicas == 1)
    before = counters()
    batch = EventBatch.from_arrays(np.full(100, 41), np.full(100, 3.5e7, np.float32))
    state = hist.step_batch(hist.init_state(), batch)
    after = counters()
    _, window = hist.read(state)
    assert float(window.sum()) == 100.0
    bins = window.sum(axis=1)
    assert set(np.unique(bins[bins > 0])) <= {100.0 * k / replicas for k in range(1, replicas + 1)}
    staging = "flat" if replicas == 1 else "raw"
    assert after[staging] - before[staging] == 1 and after["updates"] - before["updates"] == replicas * 4096


def test_a_logical_projection_counts_its_table_too_and_gives_the_bytes_back():
    gc.collect()
    before = counters()
    table = project_logical(np.arange(1, 145).reshape(12, 12))
    built = counters()
    assert built["table_bytes"] - before["table_bytes"] == table.lut.nbytes == 4 * 145
    assert built["table_s"] > before["table_s"]
    del table
    gc.collect()
    assert counters()["table_bytes"] == before["table_bytes"]  # the table went, and its bytes with it

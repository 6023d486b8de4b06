"""LOKI's nine banks as 2-D views, as files of the benchmark: the
configuration, its cell and its reference kind ``detector_xy_replicas``,
and the package against that reference on seeded events: two small banks
(one with its straws along x, one along y) cut out of the package's own
plan, through ``JobManager`` and the tick program on the CPU (counts and
exactness only), the replica tables of two banks at full size, and two
whole banks through the harness's plumbing."""

from __future__ import annotations

import ast
import copy
import dataclasses
import json
import time

import numpy as np
import pytest
from bench_support import REPO
from harness import bench, manifest, reference, results
from harness.traffic import Traffic

CELL = "loki_banks.paced14"
KIND = "detector_xy_replicas"
TUBES = (56, 16, 12, 16, 12, 28, 32, 20, 32)  # upstream, per layer
BANKS = [f"loki_detector_{i}" for i in range(9)]
SMALL = {0: {"tubes": 3, "pixels_per_straw": 64}, 2: {"tubes": 2, "pixels_per_straw": 48}}  # bank -> its cut
SMALL_RESOLUTION = [32, 24]  # (ny, nx): bins of 3-5 mm on banks of ~0.1 m, as the full size's
TRAFFIC = Traffic(pulse_hz=14, events_per_pulse=4096, out_of_range_probes=6, pool_pulses=5, toa_bins=100)
WINDOWS = ((0, 3), (3, 7), (7, 10))  # the pulses of three publishes
FAULTS = ["replica_left_out", "weights_left_at_one", "unjittered_lut", "jitter_other_seed",
          "neighbour_bank_lut", "offscreen_clipped", "accumulator_bfloat16"]
NEW_METRICS = {
    "view_raw_staging_share.paced": (
        "stage + tick program, host side", "%",
        [{"family": "livedata_view_wires", "labels": {"staging": "raw"}}], {"family": "livedata_view_wires"}, 100),
    "scatter_updates_per_event.paced": (
        "kernels", "updates/slot",
        [{"family": "livedata_scatter_updates"}],
        {"family": "livedata_staged_events", "labels": {"kind": "staged"}}, 1),
}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(REPO, CELL)


@pytest.fixture(scope="module")
def kind(cell):
    return cell.kinds[KIND]


def test_the_cell_is_in_the_manifest_whole_and_as_files(cell):
    assert manifest.check(REPO) == []
    entry = next(c for c in manifest.load_manifest(REPO)["configs"] if c["name"] == "loki_banks")
    assert entry["reduced"] == [] and cell.config["reduced"] == {}  # every bank, every pixel, every replica
    assert len(entry["source"]) <= 200
    assert "loki/specs.py" in entry["source"] and "GeometricProjector" in entry["source"]
    iq = next(c for c in manifest.load_manifest(REPO)["configs"] if c["name"] == "loki_iq")
    assert entry["source"] != iq["source"] and entry["file"] != iq["file"]  # two deployments, two sources
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "loki_banks", "paced14_xy4")
    assert cell.config["service"] == "detector_data" and cell.config["service_flags"] == []
    assert cell.config["toa_bins"] == cell.traffic.toa_bins == 100 and cell.traffic.pool_pulses == 13
    assert cell.traffic.events_per_pulse % 8192 == 0
    paced = json.loads((REPO / "benchmark" / "traffic" / "paced14.json").read_text())
    mine = json.loads((REPO / "benchmark" / "traffic" / "paced14_xy4.json").read_text())
    shape = set(paced) - {"name", "why", "sources", "events_per_pulse"}
    assert {k: mine[k] for k in shape} == {k: paced[k] for k in shape}  # paced14's shape, its own rate
    assert set(mine["sources"]) == set(paced["sources"])
    streams = cell.config["streams"]
    assert [s["name"] for s in streams] == BANKS and all(s.get("kind", "detector") == "detector" for s in streams)
    first = 1
    for stream, job, tubes in zip(streams, cell.config["jobs"], TUBES, strict=True):
        view = job["view"]
        assert stream["first_id"] == view["bank"]["first_id"] == first
        assert stream["n_pixels"] == 4 * tubes * 7 * 512 and view["bank"]["tubes"] == tubes
        assert job["stream"] == job["job_source"] == stream["wire_source"] == stream["name"]
        assert job["workflow"] == ["detector_view", "xy_projection"] and job["params"] == {}
        assert (view["kind"], view["resolution"], view["noise_sigma_m"], view["replicas"], view["seed"],
                view["toa_bins"]) == (KIND, [256, 256], 0.004, 4, 0, 100)
        first += stream["n_pixels"]
    assert first - 1 == 3_211_264
    outputs = cell.config["outputs"]
    assert (outputs["spectra"], outputs["images"], outputs["prefix_total"]) == (
        ["spectrum_current", "spectrum_cumulative"], ["image_current", "image_cumulative"], "counts_cumulative")
    luts = sum(4 * 4 * (s["first_id"] + s["n_pixels"]) for s in streams)  # sized by the last id + 1
    assert cell.config["state_bytes"] == 9 * 2 * (256 * 256 * 100 + 1) * 4 + luts == 736_100_568
    assert str(luts) in cell.config["state_reckoning"] and str(3_211_264 * 16) in cell.config["state_reckoning"]
    assumed = " ".join(cell.config["assumed"])
    assert "sigma 4 mm" in assumed and "R = 4" in assumed and "not re-read" in assumed
    assert set(cell.limits) == set(reference.check_names(cell.config, cell.kinds))
    assert cell.limits == manifest.load_cell(REPO, "nmx_panels.paced14").limits  # the same exact limits
    why = json.loads((REPO / "benchmark" / "limits" / f"{CELL}.json").read_text())["why"]
    assert set(why) == set(cell.limits) == {"spectrum_bins_wrong", "image_bins_wrong", "prefix_off_pulses"}
    listed = {m["name"] for m in cell.per_layer}
    nmx = {m["name"] for m in manifest.load_cell(REPO, "nmx_panels.paced14").per_layer}
    # what NMX's cell reports but the span no raw wire records, and the pool's share, which its raw wires have
    assert listed == nmx - {"flatten_ms.paced"} | {"staging_kept_share.paced"}
    assert set(NEW_METRICS) <= listed
    for metric in manifest.load_manifest(REPO)["end_to_end"]:
        assert "workloads" not in metric or CELL in metric["workloads"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_the_new_metrics_are_data_only_and_the_program_has_their_counters(metric):
    layer, unit, terms, per, scale = NEW_METRICS[metric]
    entry = next(m for m in manifest.load_manifest(REPO)["per_layer"] if m["name"] == metric)
    assert {CELL, "nmx_panels.paced14", "dream_banks.paced14"} <= set(entry["workloads"])  # a later cell may join
    assert (entry["layer"], entry["unit"], entry["better"], entry["moves"], entry["source"]) == (
        layer, unit, "lower", "freshness_p50_ms", "program_counter")
    reader = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())["reader"]
    assert reader["kind"] == "prometheus" and "absent_is_zero" not in reader  # a program without the counter: no value
    assert (reader["terms"], reader["per"], reader["scale"]) == (terms, per, scale)
    from esslivedata_tpu.telemetry.instruments import REGISTRY

    for term in (*terms, per):
        counter = REGISTRY.get(f"{term['family']}_total")
        assert counter is not None and counter.collect().kind == "counter"


def test_a_program_without_the_counters_reads_nothing_and_one_with_them_reads_the_shares():
    """The parent's scrape has neither counter: both readers return None
    and the line leaves the metrics out."""
    from harness import metrics as layer_metrics

    def scrape(windows, wires=None, updates=None):
        samples = [("livedata_staged_events_total", {"kind": "staged"}, 4.0e6 * windows)]
        if wires is not None:
            samples += [("livedata_view_wires_total", {"staging": k}, float(v * windows)) for k, v in wires.items()]
        if updates is not None:
            samples.append(("livedata_scatter_updates_total", {}, updates * windows))
        return samples

    specs = [
        {**json.loads((REPO / "benchmark" / "metrics" / f"{name}.json").read_text()), "name": name}
        for name in NEW_METRICS
    ]
    parent = {"scrape_start": scrape(2), "scrape_end": scrape(53), "window_s": 51.0}
    assert layer_metrics.evaluate(specs, parent) == {}
    for wires, updates, expected in (({"flat": 0, "raw": 9}, 16.0e6, (100.0, 4.0)), ({"flat": 3, "raw": 0}, 4.0e6, (0.0, 1.0))):
        ctx = {"scrape_start": scrape(2, wires, updates), "scrape_end": scrape(53, wires, updates), "window_s": 51.0}
        got = layer_metrics.evaluate(specs, ctx)
        assert tuple(got[name]["value"] for name in NEW_METRICS) == expected


def test_the_kinds_module_imports_nothing_of_the_program():
    tree = ast.parse((REPO / "benchmark" / "references" / f"{KIND}.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "numpy", "harness"}


def test_the_kind_states_its_protocol(kind, cell):
    assert list(kind.faults()) == FAULTS
    outputs = cell.config["outputs"]
    assert set(outputs["spectra"] + outputs["images"]) == set(kind.CHECKS)
    for output in outputs["spectra"]:
        assert kind.check(output) == "spectrum_bins_wrong"
    for output in outputs["images"]:
        assert kind.check(output) == "image_bins_wrong"
    for output in kind.CHECKS:
        rel, abs_, reason = kind.tolerance(output)
        assert (rel, abs_) == (0.0, 0.0) and "2**22" in reason and "bfloat16" in reason  # exact, and why
    assert kind.EXACT_BELOW == 2**22 and float(np.float32(kind.EXACT_BELOW - 0.25)) == kind.EXACT_BELOW - 0.25
    assert float(np.float32(kind.EXACT_BELOW + 0.25)) != kind.EXACT_BELOW + 0.25
    job = cell.config["jobs"][0]
    assert kind.work_bytes(job, cell.config, 1000, 0) == 56 * 1000  # 8 B in, 4 x (4 B of LUT + 8 B of bin)
    from harness import roofline

    grid = {"view": {"kind": "grid", "shape": [256, 256]}}
    # per publish as a detector view of the same screen: tick_roofline stays one definition
    assert kind.work_bytes(job, cell.config, 0, 3) == roofline.job_bytes(grid, 100, 0, 3)
    odd = copy.deepcopy(job)
    odd["view"]["replicas"] = 3
    with pytest.raises(ValueError, match="no binary fraction"):
        kind.build(odd, cell.config, cell.traffic, [])


def test_the_configuration_mirrors_the_package(cell):
    from esslivedata_tpu.config.instruments.loki import specs
    from esslivedata_tpu.config.streams import get_stream_mapping
    from esslivedata_tpu.workflows.detector_view.workflow import DetectorViewParams
    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    spec = workflow_registry[specs.XY_PROJECTION_HANDLE.workflow_id]
    assert (spec.namespace, spec.name, spec.source_names) == ("detector_view", "xy_projection", BANKS)
    assert set(cell.config["outputs"]["spectra"] + cell.config["outputs"]["images"]) <= set(spec.outputs)
    params = DetectorViewParams()
    assert (params.toa_bins, params.histogram_method, params.pixel_weighting) == (100, "scatter", False)
    assert (params.toa_range.low, params.toa_range.high) == (0.0, 1e9 / 14)
    for stream, job in zip(cell.config["streams"], cell.config["jobs"], strict=True):
        declared = specs.INSTRUMENT.detectors[stream["name"]]
        view = job["view"]
        assert (declared.source_name, declared.projection) == (stream["wire_source"], "xy_plane")
        assert list(declared.resolution) == view["resolution"]
        assert (declared.noise_sigma, declared.n_replica) == (view["noise_sigma_m"], view["replicas"])
    assert cell.config["detector_topic"] in get_stream_mapping(specs.INSTRUMENT).detector_topics


@pytest.mark.parametrize("bank", [2, 7])
def test_the_references_replica_tables_are_the_packages_at_full_size(cell, kind, bank, monkeypatch, tmp_path):
    """One bank with straws along y and one along x, every pixel and
    every replica: positions from the package's synthesized geometry
    file, the LUT from ``project_geometric`` as the factory calls it."""
    from esslivedata_tpu.config.instruments.loki.geometry import bank_geometry
    from esslivedata_tpu.config.instruments.loki.specs import INSTRUMENT
    from esslivedata_tpu.workflows.detector_view.projectors import project_geometric

    monkeypatch.setenv("LIVEDATA_DATA_DIR", str(tmp_path))
    positions, ids = bank_geometry(BANKS[bank])
    view = cell.config["jobs"][bank]["view"]
    assert np.array_equal(kind.bank_positions(view["bank"]), positions)  # to the last bit: the bins follow
    declared = INSTRUMENT.detectors[BANKS[bank]]
    table = project_geometric(
        positions, ids, mode=declared.projection, resolution=declared.resolution,
        noise_sigma=declared.noise_sigma, n_replica=declared.n_replica,
    )
    mine = kind.replica_luts(view)
    assert table.lut.shape == (4, view["bank"]["first_id"] + len(ids)) and mine.shape == (4, len(ids))
    assert np.array_equal(table.lut[:, ids], mine)
    assert np.all(table.lut[:, : view["bank"]["first_id"]] == -1)  # the ids of the banks before it
    off = np.count_nonzero(mine < 0) / mine.size
    assert 0.003 < off < 0.012  # some replicas of the edge pixels leave the screen, and are dropped
    assert len({tuple(column) for column in mine[:, ::997].T}) > 0.9 * len(mine[0, ::997])  # the rows differ


def small_config(config: dict) -> dict:
    """The configuration with banks 0 and 2 alone, each cut to SMALL
    and seen at SMALL_RESOLUTION, ids consecutive."""
    doc = copy.deepcopy(config)
    doc["streams"] = [doc["streams"][i] for i in SMALL]
    doc["jobs"] = [doc["jobs"][i] for i in SMALL]
    first = 1
    for stream, job, cut in zip(doc["streams"], doc["jobs"], SMALL.values()):
        bank = job["view"]["bank"]
        bank.update(cut, first_id=first)
        job["view"]["resolution"] = SMALL_RESOLUTION
        n = bank["layers"] * bank["tubes"] * bank["straws"] * bank["pixels_per_straw"]
        stream.update(first_id=first, n_pixels=n)
        first += n
    return doc


def window_of(pools, index, lo, hi):
    pool, _ = pools[index]
    ids = np.concatenate([pool[k % len(pool)][0] for k in range(lo, hi)])
    toa = np.concatenate([pool[k % len(pool)][1] for k in range(lo, hi)])
    return ids, toa


def package_projection(job):
    """The package's table for a small bank: its plan's panel, its
    projector, the declared noise."""
    from esslivedata_tpu.config.instruments.loki.specs import INSTRUMENT
    from esslivedata_tpu.config.nexus_plans import plan_for
    from esslivedata_tpu.config.nexus_synthesis import straw_positions
    from esslivedata_tpu.workflows.detector_view.projectors import project_geometric

    bank = job["view"]["bank"]
    plan = next(b for b in plan_for("loki").banks if b.name == job["stream"])
    declared = INSTRUMENT.detectors[job["stream"]]
    shape = (bank["layers"], bank["tubes"], bank["straws"], bank["pixels_per_straw"])
    return project_geometric(
        straw_positions(shape, plan.panel),
        np.arange(bank["first_id"], bank["first_id"] + int(np.prod(shape))),
        mode=declared.projection, resolution=tuple(job["view"]["resolution"]),
        noise_sigma=declared.noise_sigma, n_replica=declared.n_replica,
    )


@pytest.fixture(scope="module")
def small(cell):
    """(config, pools, job -> the package's outputs of the three windows,
    what the run counted): two jobs in one ``JobManager``, each window
    carrying both banks' events."""
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.core import Timestamp
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.preprocessors import DetectorEvents, ToEventBatch
    from esslivedata_tpu.telemetry.instruments import (
        JOB_PUBLISHES, JOB_WINDOWS, SCATTER_UPDATES, STAGED_EVENTS, TICK_GROUPS, VIEW_WIRES)
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.detector_view import DetectorViewParams, DetectorViewWorkflow

    def staged(ids, toa):
        acc = ToEventBatch(min_bucket=16)
        acc.add(Timestamp.from_ns(0), DetectorEvents(pixel_id=ids, time_of_arrival=toa.astype(np.float32)))
        return acc.get()

    def counters():
        return {
            "tick": JOB_WINDOWS.value(path="tick"),
            "not_tick": JOB_WINDOWS.value(path="private") + JOB_WINDOWS.value(path="fused"),
            "raw": VIEW_WIRES.value(staging="raw"), "flat": VIEW_WIRES.value(staging="flat"),
            "updates": SCATTER_UPDATES.value(), "slots": STAGED_EVENTS.value(kind="staged"),
            "groups_ahead": TICK_GROUPS.value(dispatched="ahead"), "groups_alone": TICK_GROUPS.value(dispatched="alone"),
            "publishes_ahead": JOB_PUBLISHES.value(when="ahead"), "publishes_end": JOB_PUBLISHES.value(when="end"),
        }

    config = small_config(cell.config)
    pools = reference.make_pools(config, TRAFFIC, 2**31 + 37)
    registry = WorkflowFactory()
    manager = JobManager(job_factory=JobFactory(registry), job_threads=2, combine_publish=True, tick_program=True)
    try:
        for job in config["jobs"]:
            def make(*, source_name, params, _job=job):
                return DetectorViewWorkflow(projection=package_projection(_job), params=params)

            spec = WorkflowSpec(instrument="loki_small", namespace="detector_view", name=job["name"],
                                source_names=[job["stream"]], params_model=DetectorViewParams)
            registry.register_spec(spec).attach_factory(make)
            manager.schedule_job(WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name=job["job_source"]), params=job["params"]))
        outputs = {job["name"]: [] for job in config["jobs"]}
        by_source = {job["job_source"]: job["name"] for job in config["jobs"]}
        counted = []
        for number, (lo, hi) in enumerate(WINDOWS):
            data = {job["stream"]: staged(*window_of(pools, i, lo, hi)) for i, job in enumerate(config["jobs"])}
            before = counters()
            published = []  # what left ahead, through the window's publisher, and then the rest
            published += manager.process_jobs(
                data, start=Timestamp.from_ns(number), end=Timestamp.from_ns(number + 1), publish=published.extend)
            after = counters()
            counted.append({k: after[k] - before[k] for k in before})
            assert len(published) == len(by_source)
            for result in published:
                outputs[by_source[result.job_id.source_name]].append(
                    {k: np.asarray(v.values) for k, v in result.outputs.items()})
    finally:
        manager.shutdown()
    return config, pools, outputs, counted


def test_the_small_run_took_the_device_path_that_the_cell_exists_for(small):
    """Every window of both jobs: one tick program a job, a raw wire and
    no flat one, four updates a staged slot; the third window's first
    group leaves ahead of the second's collect (the first two compile
    the program's two variants, and a compile round is collected on the
    spot)."""
    config, _, _, counted = small
    jobs = len(config["jobs"])
    for window in counted:
        assert (window["tick"], window["not_tick"]) == (jobs, 0)
        assert (window["raw"], window["flat"]) == (jobs, 0)
        assert window["slots"] == jobs * 16384  # three or four pulses of 4096 in one bucket
        assert window["updates"] == 4 * window["slots"]
    for compiling in counted[:2]:
        assert (compiling["groups_ahead"], compiling["groups_alone"]) == (0, jobs)
    assert (counted[2]["groups_ahead"], counted[2]["groups_alone"]) == (jobs - 1, 1)  # (n - 1) / n: 8/9 at full size
    assert (counted[2]["publishes_ahead"], counted[2]["publishes_end"]) == (jobs - 1, 1)


def misses(kind, ref, published) -> dict[str, int]:
    """check -> bins of the three publishes that miss ``ref``, judged as
    ``results.compare`` judges them."""
    out: dict[str, int] = {}
    previous = 0
    for (_lo, hi), outputs in zip(WINDOWS, published, strict=True):
        for output in kind.CHECKS:
            got = outputs[output]
            lo = previous if output.endswith("_current") else 0
            want = np.asarray(ref.expected(output, lo, hi))
            assert got.shape == want.shape, output
            rel, abs_, _reason = kind.tolerance(output)
            miss, _share = results.bins_outside(got, want, rel, abs_)
            out[kind.check(output)] = out.get(kind.check(output), 0) + miss
        previous = hi
    return out


def test_the_package_publishes_what_the_reference_says_for_two_small_banks(kind, small):
    config, pools, outputs, _ = small
    for job in config["jobs"]:
        ref = kind.build(job, config, TRAFFIC, pools)
        assert ref.per_pulse.min() > 0, "every bank counts events in every pulse"
        assert misses(kind, ref, outputs[job["name"]]) == {"spectrum_bins_wrong": 0, "image_bins_wrong": 0}, job["name"]
        last = outputs[job["name"]][-1]
        assert last["image_cumulative"].shape == tuple(SMALL_RESOLUTION) and last["image_cumulative"].dtype == np.float32
        assert last["spectrum_current"].shape == (100,)
        # the prefix total is the weighted sum: quarters, a little under the events counted (replicas off the screen)
        lo, hi = WINDOWS[-1]
        in_range = (hi - lo) * (TRAFFIC.events_per_pulse - 2 * TRAFFIC.out_of_range_probes)
        assert float(last["counts_current"]) == ref.counts(lo, hi) == last["image_current"].sum()
        assert 0.9 * in_range < ref.counts(lo, hi) < in_range and (4 * ref.counts(lo, hi)).is_integer()
        assert float(last["counts_cumulative"]) == ref.counts(0, hi) == last["spectrum_cumulative"].sum()
        assert ref.prefix_of(float(last["counts_cumulative"]), 20) == (hi, 0.0)
        assert not np.all(last["image_current"] == np.round(last["image_current"]))  # quarters, not whole events


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_kind_is_caught(kind, small, fault):
    config, pools, outputs, _ = small
    caught = 0
    for job in config["jobs"]:
        broken = kind.faults()[fault](job, config, TRAFFIC, pools)
        caught += sum(misses(kind, broken, outputs[job["name"]]).values()) > 0
    assert caught == len(config["jobs"]), f"{fault}: seen in {caught} of two jobs"


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_fault_of_the_streams_is_caught(kind, small, fault):
    config, pools, outputs, _ = small
    broken = reference.break_guarantee(pools, fault)
    for job in config["jobs"]:
        ref = kind.build(job, config, TRAFFIC, broken)
        assert sum(misses(kind, ref, outputs[job["name"]]).values()) > 0, job["name"]


def test_a_state_kept_in_bfloat16_fails_the_comparison(kind, small):
    """The package's own histogrammer with its weights and its bins in
    the precision below the one the configuration states. A weight of
    1/4 is a power of two and bfloat16 holds it; the sums it cannot hold
    past 2**6, so the spectra miss."""
    import jax.numpy as jnp

    from esslivedata_tpu.ops import EventBatch
    from esslivedata_tpu.ops.histogram import EventHistogrammer

    config, pools, _, _ = small
    job = config["jobs"][0]
    table = package_projection(job)
    ref = kind.build(job, config, TRAFFIC, pools)
    readings = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        hist = EventHistogrammer(
            toa_edges=np.linspace(0.0, 1e9 / 14, 101), n_screen=table.n_screen, pixel_lut=table.lut, dtype=dtype)
        ids, toa = window_of(pools, 0, *WINDOWS[0])
        state = hist.step(hist.init_state(), EventBatch.from_arrays(ids, toa.astype(np.float32)))
        _, window = hist.read(state)
        spectrum = np.asarray(window.sum(axis=0), np.float64)
        readings[dtype] = results.bins_outside(spectrum, ref.expected("spectrum_current", *WINDOWS[0]), 0.0, 0.0)[0]
    assert readings[jnp.float32] == 0 and readings[jnp.bfloat16] > 50  # of 100 TOA bins


def test_a_bank_that_does_not_cover_its_stream_is_refused(kind, small):
    config, pools, _, _ = small
    job = copy.deepcopy(config["jobs"][1])
    job["view"]["bank"]["first_id"] += 1  # a wrong id base shows before any comparison
    with pytest.raises(ValueError, match="does not cover"):
        kind.build(job, config, TRAFFIC, pools)


def toy(cell: manifest.Cell) -> manifest.Cell:
    """The cell with its two smallest banks alone (172 032 pixels each,
    whole: the service builds their tables and states at full width),
    at a fiftieth of the rate."""
    keep = ("loki_detector_2", "loki_detector_4")
    config = {
        **cell.config,
        "streams": [s for s in cell.config["streams"] if s["name"] in keep],
        "jobs": [j for j in cell.config["jobs"] if j["stream"] in keep],
    }
    return dataclasses.replace(
        cell, config=config, traffic=dataclasses.replace(cell.traffic, events_per_pulse=2048))


def test_the_harness_runs_a_toy_banks_cell_on_the_package_and_finds_it_correct(cell):
    """``run.py``'s plumbing on the CPU: the loki detector service
    started with no flag, ``detector_view/xy_projection`` on two whole
    banks, the traced run's counters read by the new metric files."""
    line, report = bench.run_cell(toy(cell), 2**31 + 37, 3.0, True, REPO, time.monotonic(), allow_cpu=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] in (4, 6)  # two jobs x base windows offered
    checks = line["checks"]
    assert [checks[name]["value"] for name in cell.limits] == [0, 0, 0.0]
    assert checks["image_bins_wrong"]["tolerance"] == {"rel": 0.0, "abs": 0.0}
    assert checks["spectrum_bins_wrong"]["worst_share"] == 0.0
    assert checks["compared"]["images"] >= 2 * 2 and checks["compared"]["spectra"] >= 2 * 2 * 3
    metrics = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert metrics["view_raw_staging_share.paced"] == 100.0
    assert metrics["scatter_updates_per_event.paced"] == 4.0
    assert metrics["compiles_in_window.paced"] == 0.0
    assert metrics["groups_ahead_share.paced"] == metrics["publishes_ahead_share.paced"] == 50.0  # (n - 1) / n
    assert "flatten_ms.paced" not in metrics and metrics["h2d_ms.paced"] > 0.0
    assert any(text.startswith("check spectrum_bins_wrong: ") and "2**22" in text for text in report)

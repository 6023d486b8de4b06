"""DREAM's powder reduction whole, as files of the benchmark: the
configuration, its cell and its reference kind ``powder_dspacing_banks``,
and the package against that reference on seeded events: five small
banks cut out of the package's own geometry (every strip kept, so the
two-theta bands are the full size's), through ``JobManager`` on the CPU
(counts and exactness only), and one bank at full size through the
harness's plumbing."""

from __future__ import annotations

import ast
import copy
import dataclasses
import json
import time
import types

import numpy as np
import pytest
from bench_support import REPO
from harness import bench, manifest, reference, results
from harness.traffic import Traffic

CELL = "dream_powder.paced14"
KIND = "powder_dspacing_banks"
VOXELS = {
    "mantle_detector": 491_520,
    "endcap_backward_detector": 157_696,
    "endcap_forward_detector": 71_680,
    "high_resolution_detector": 61_440,
    "sans_detector": 30_720,
}
SMALL = {"wire": 2, "module": 1}  # of every small bank, and a fifth of its segments; strips and counters whole
TRAFFIC = Traffic(pulse_hz=14, events_per_pulse=2048, out_of_range_probes=6, pool_pulses=5, toa_bins=500)
WINDOWS = ((0, 3), (3, 7))  # the pulses of two publishes
FAULTS = ["monitor_twice", "toa_bin_off_by_one", "bank_off_by_one", "composite_transposed",
          "d_clipped", "quotient_bfloat16"]


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(REPO, CELL)


@pytest.fixture(scope="module")
def kind(cell):
    return cell.kinds[KIND]


def test_the_cell_is_in_the_manifest_whole_and_as_files(cell):
    assert manifest.check(REPO) == []
    entry = next(c for c in manifest.load_manifest(REPO)["configs"] if c["name"] == "dream_powder")
    assert entry["reduced"] == [] and cell.config["reduced"] == {}  # every bank, every voxel
    assert len(entry["source"]) <= 200 and "dream/specs.py" in entry["source"] and "powder" in entry["source"]
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "dream_powder", "paced14_toa500")
    assert cell.config["service"] == "data_reduction" and cell.config["service_flags"] == []
    assert cell.config["toa_bins"] == cell.traffic.toa_bins == 500 and cell.traffic.pool_pulses == 13
    assert cell.traffic.events_per_pulse % 32768 == 0
    detectors = [s for s in cell.config["streams"] if s.get("kind", "detector") == "detector"]
    assert {s["name"]: s["n_pixels"] for s in detectors} == VOXELS
    assert sum(s["n_pixels"] for s in detectors) == 813_056
    (monitor,) = [s for s in cell.config["streams"] if s.get("kind") == "monitor"]
    assert (monitor["name"], monitor["topic"], monitor["rate_share"]) == ("monitor_cave", "dream_monitor", 0.125)
    first = 1
    for stream, job in zip(detectors, cell.config["jobs"], strict=True):
        view = job["view"]
        assert stream["first_id"] == view["bank"]["first_id"] == first
        assert int(np.prod(list(view["bank"]["sizes"].values()))) == stream["n_pixels"]
        assert job["stream"] == job["job_source"] == stream["name"]
        assert job["workflow"] == ["powder", "dspacing"]
        assert job["aux_source_names"] == {"monitor": "monitor_cave"}
        assert job["params"] == {"d_bins": 2000, "d_min": 0.4, "d_max": 3.5, "two_theta_bins": 17}
        assert (view["d"], view["two_theta_bands"], view["toa_bins"]) == (
            {"bins": 2000, "min": 0.4, "max": 3.5}, 17, 500)
        first += stream["n_pixels"]
    assert cell.config["state_bytes"] == 813_056 * 500 * 4 + 5 * (2 * 34_000 + 2) * 4
    assert set(cell.limits) == set(reference.check_names(cell.config, cell.kinds))
    assert cell.limits == manifest.load_cell(REPO, "nmx_panels.paced14").limits  # the same exact limits
    why = json.loads((REPO / "benchmark" / "limits" / f"{CELL}.json").read_text())["why"]
    assert set(why) == set(cell.limits)
    listed = {m["name"] for m in cell.per_layer}
    loki = {m["name"] for m in manifest.load_cell(REPO, "loki_iq.paced14").per_layer}
    # what LOKI's cell reports, but the share whose label never gets a sample here and the two metrics
    # that bench_loki_iq_test.py holds to LOKI's cell alone (a file PR 31 may not edit), and this cell's two
    assert listed >= loki - {
        "q_lookup_windowed_share.paced", "private_windows_share.paced", "q_step_ms.paced",
    } | {"q_lookup_gather_share.paced", "q_bincount_scatter_share.paced"}
    assert "q_lookup_windowed_share.paced" not in listed and "flatten_ms.paced" not in listed


@pytest.mark.parametrize(
    "metric, family, label",
    [
        ("q_lookup_gather_share.paced", "livedata_q_lookup_steps", {"lookup": "gather"}),
        ("q_bincount_scatter_share.paced", "livedata_q_bincount_steps", {"method": "scatter"}),
    ],
)
def test_the_new_metrics_are_this_cells_and_data_only(metric, family, label):
    entry = next(m for m in manifest.load_manifest(REPO)["per_layer"] if m["name"] == metric)
    assert CELL in entry["workloads"] and entry["layer"] == "kernels"  # a later cell may join: no equality here
    assert (entry["better"], entry["moves"], entry["unit"]) == ("lower", "freshness_p50_ms", "%")
    doc = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    reader = doc["reader"]
    assert reader["kind"] == "prometheus" and "absent_is_zero" not in reader  # a program without the counter: no value
    assert reader["terms"] == [{"family": family, "labels": label}]
    assert reader["per"] == {"family": family} and reader["scale"] == 100
    from esslivedata_tpu.telemetry.instruments import REGISTRY

    counter = REGISTRY.get(f"{family}_total")
    assert counter is not None and counter.collect().kind == "counter"


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
    outputs = cell.config["outputs"]["arrays"]
    assert set(outputs) == set(kind.CHECKS) and cell.config["outputs"]["prefix_total"] == "counts_current"
    for output in outputs:
        if output == "dspacing_normalized":
            continue
        assert kind.tolerance(output) is None and kind.check(output) == "spectrum_bins_wrong"
    assert kind.check("dspacing_normalized") == "image_bins_wrong"
    rel, abs_, reason = kind.tolerance("dspacing_normalized")
    assert 2.0**-24 < rel < 2.0**-12 and abs_ == 0.0 and "bfloat16" in reason  # between the two precisions
    job = cell.config["jobs"][0]
    assert kind.work_bytes(job, cell.config, 1000, 0) == 20 * 1000  # 8 B in, 4 B of table, 8 B of bin
    assert kind.work_bytes(job, cell.config, 0, 1) == 4 * 34_000 * 4 + 4 * (2 * 34_000 + 2)


def test_the_configuration_mirrors_the_package(cell):
    from esslivedata_tpu.config.instruments.dream import specs
    from esslivedata_tpu.config.streams import get_stream_mapping
    from esslivedata_tpu.workflows.powder import PowderDiffractionParams

    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    spec = workflow_registry[specs.POWDER_HANDLE.workflow_id]
    detectors = [s for s in cell.config["streams"] if s.get("kind", "detector") == "detector"]
    assert [s["name"] for s in detectors] == list(specs.BANK_SIZES)
    for stream, job in zip(detectors, cell.config["jobs"], strict=True):
        declared = specs.INSTRUMENT.detectors[stream["name"]]
        assert job["view"]["bank"]["sizes"] == specs.BANK_SIZES[stream["name"]]  # axis order too
        assert declared.source_name == stream["wire_source"]
        ids = declared.detector_number.reshape(-1)
        assert (int(ids[0]), int(ids[-1]), ids.size) == (
            stream["first_id"], stream["first_id"] + stream["n_pixels"] - 1, stream["n_pixels"])
        params = PowderDiffractionParams(**job["params"])
        assert (params.toa_bins, params.toa_offset_ns) == (cell.config["toa_bins"], 0.0)
        assert params.d_bins * params.two_theta_bins == 34_000 > np.iinfo(np.int16).max
    assert (spec.namespace, spec.name) == ("powder", "dspacing")
    assert spec.source_names == list(VOXELS) and spec.service == "data_reduction"
    assert "monitor_cave" in spec.aux_source_names["monitor"]
    assert set(spec.outputs) == set(cell.config["outputs"]["arrays"])
    mapping = get_stream_mapping(specs.INSTRUMENT)
    (monitor,) = [s for s in cell.config["streams"] if s.get("kind") == "monitor"]
    assert specs.INSTRUMENT.monitors[monitor["name"]].source_name == monitor["wire_source"]
    assert cell.config["detector_topic"] in mapping.detector_topics
    assert monitor["topic"] in mapping.monitor_topics


@pytest.mark.parametrize("bank", list(VOXELS))
def test_the_references_geometry_is_the_packages_at_full_size(cell, kind, bank):
    """Every voxel's scattering angle and flight path, from the
    package's placeholder; no table is built."""
    from esslivedata_tpu.config.instruments.dream.specs import powder_geometry

    theirs = powder_geometry(bank)
    view = next(j for j in cell.config["jobs"] if j["stream"] == bank)["view"]["bank"]
    two_theta, l_total = kind.voxel_geometry(view)
    assert np.array_equal(two_theta, theirs["two_theta"]) and np.array_equal(l_total, theirs["l_total"])
    ids = theirs["pixel_ids"]
    assert ids[0] == view["first_id"] and np.array_equal(np.diff(ids), np.ones(ids.size - 1, ids.dtype))
    band = kind.bands(two_theta, 17)
    assert band.min() == 0 and band.max() == 16 and np.all(np.diff(band[np.argsort(two_theta, kind="stable")]) >= 0)


def small_config(config: dict) -> dict:
    """The configuration with every bank cut to SMALL along wire and
    module and to a fifth of its segments (so that no two banks come
    out alike; strips and counters whole), ids consecutive."""
    doc = copy.deepcopy(config)
    first = 1
    for stream, job in zip(doc["streams"], doc["jobs"]):
        bank = job["view"]["bank"]
        bank["sizes"].update(SMALL, segment=bank["sizes"]["segment"] // 5)
        bank["first_id"] = first
        n = int(np.prod(list(bank["sizes"].values())))
        stream.update(first_id=first, n_pixels=n)
        first += n
    return doc


def cut_out(full: np.ndarray, sizes: dict, small: dict) -> np.ndarray:
    """The voxels of the full bank that make up the small one, in the
    small one's C order."""
    block = full.reshape(tuple(sizes.values()))
    return block[tuple(slice(0, small[axis]) for axis in sizes)].reshape(-1)


def window_of(pools, index, name, lo, hi):
    pool, _ = pools[index[name]]
    ids = np.concatenate([pool[k % len(pool)][0] for k in range(lo, hi)])
    toa = np.concatenate([pool[k % len(pool)][1] for k in range(lo, hi)])
    return ids, toa


@pytest.fixture(scope="module")
def small(cell):
    """(config, pools, job -> the package's outputs of the two windows,
    what the run pinned): five jobs in one ``JobManager``, each window
    carrying the five banks' events and the monitor's."""
    from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
    from esslivedata_tpu.config.instruments.dream.specs import powder_geometry
    from esslivedata_tpu.core import Timestamp
    from esslivedata_tpu.core.job_manager import JobFactory, JobManager
    from esslivedata_tpu.preprocessors import DetectorEvents, MonitorEvents, ToEventBatch
    from esslivedata_tpu.telemetry.instruments import (
        JOB_WINDOWS, Q_BINCOUNT_STEPS, Q_LOOKUP_STEPS, TABLE_BUILD_SECONDS, TABLE_BYTES)
    from esslivedata_tpu.workflows import WorkflowFactory
    from esslivedata_tpu.workflows.powder import PowderDiffractionParams, PowderDiffractionWorkflow

    def staged(ids, toa):
        acc = ToEventBatch(min_bucket=16)
        toa = toa.astype(np.float32)
        acc.add(Timestamp.from_ns(0), DetectorEvents(pixel_id=ids, time_of_arrival=toa)
                if ids.size else MonitorEvents(time_of_arrival=toa))
        return acc.get()

    def counters():
        return {
            "private": JOB_WINDOWS.value(path="private"),
            "not_private": JOB_WINDOWS.value(path="tick") + JOB_WINDOWS.value(path="fused"),
            "gather": Q_LOOKUP_STEPS.value(lookup="gather"),
            "windowed": Q_LOOKUP_STEPS.value(lookup="windowed"),
            "scatter": Q_BINCOUNT_STEPS.value(method="scatter"),
            "onehot": Q_BINCOUNT_STEPS.value(method="onehot"),
            "dspacing_s": TABLE_BUILD_SECONDS.value(family="dspacing"),
            "dspacing_bytes": TABLE_BYTES.value(family="dspacing"),
            "q_bytes": TABLE_BYTES.value(family="q"),
        }

    config = small_config(cell.config)
    pools = reference.make_pools(config, TRAFFIC, 2**31 + 31)
    index = {s["name"]: i for i, s in enumerate(config["streams"])}
    before = counters()
    registry, workflows = WorkflowFactory(), {}
    manager = JobManager(job_factory=JobFactory(registry), job_threads=2, combine_publish=True, tick_program=True)
    try:
        for job, full in zip(config["jobs"], cell.config["jobs"], strict=True):
            theirs = powder_geometry(job["stream"])  # the package's geometry, cut to the small bank
            sizes, bank = full["view"]["bank"]["sizes"], job["view"]["bank"]
            n = int(np.prod(list(bank["sizes"].values())))

            def make(*, source_name, params, aux_source_names=None, _theirs=theirs, _sizes=sizes, _bank=bank, _n=n):
                workflows[source_name] = PowderDiffractionWorkflow(
                    two_theta=cut_out(_theirs["two_theta"], _sizes, _bank["sizes"]),
                    l_total=cut_out(_theirs["l_total"], _sizes, _bank["sizes"]),
                    pixel_ids=np.arange(_bank["first_id"], _bank["first_id"] + _n),
                    params=params,
                    primary_stream=source_name,
                    monitor_streams={aux_source_names["monitor"]},
                )
                return workflows[source_name]

            spec = WorkflowSpec(
                instrument="dream_small", namespace="powder", name=job["name"],
                source_names=[job["stream"]], aux_source_names={"monitor": ["monitor_cave"]},
                params_model=PowderDiffractionParams,
            )
            registry.register_spec(spec).attach_factory(make)
            manager.schedule_job(WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name=job["job_source"]),
                params=job["params"], aux_source_names=job["aux_source_names"],
            ))
        built = counters()
        outputs = {job["name"]: [] for job in config["jobs"]}
        by_source = {job["job_source"]: job["name"] for job in config["jobs"]}
        for number, (lo, hi) in enumerate(WINDOWS):
            data = {
                name: staged(*window_of(pools, index, name, lo, hi))
                for name in [*by_source, "monitor_cave"]
            }
            published = manager.process_jobs(
                data, start=Timestamp.from_ns(number), end=Timestamp.from_ns(number + 1))
            assert len(published) == len(by_source)
            for result in published:
                outputs[by_source[result.job_id.source_name]].append(
                    {k: np.asarray(v.values) for k, v in result.outputs.items()})
        stepped = counters()
        tables = {source: np.asarray(w._hist._qmap) for source, w in workflows.items()}
    finally:
        manager.shutdown()
    pinned = {
        "build": {k: built[k] - before[k] for k in before},
        "steps": {k: stepped[k] - built[k] for k in before},
        "tables": tables,
    }
    return config, pools, outputs, pinned


def test_the_small_run_took_the_side_of_both_choices_that_the_cell_exists_for(small):
    """Private windows, an int32 composite table under the builder's
    family, XLA's gather and the scatter method, counted per step."""
    config, _, _, pinned = small
    jobs, windows = len(config["jobs"]), len(WINDOWS)
    steps = pinned["steps"]
    assert (steps["private"], steps["not_private"]) == (jobs * windows, 0)
    assert (steps["gather"], steps["windowed"]) == (jobs * windows, 0)
    assert (steps["scatter"], steps["onehot"]) == (jobs * windows, 0)
    for stream, job in zip(config["streams"], config["jobs"]):
        table = pinned["tables"][job["job_source"]]
        assert table.dtype == np.int32 and table.shape == (stream["n_pixels"], 500)
        assert 8192 < table.max() < 34_000 and table.min() == -1  # past the one-hot kernel's bin space
    assert pinned["tables"]["mantle_detector"].max() > np.iinfo(np.int16).max  # and past int16
    build = pinned["build"]
    assert build["dspacing_bytes"] == sum(t.nbytes for t in pinned["tables"].values())
    assert build["q_bytes"] == 0, "the composite table keeps its builder's family"
    assert build["dspacing_s"] > 0 and (build["private"], build["gather"], build["scatter"]) == (0, 0, 0)


def test_the_composite_pass_counts_into_the_tables_build_seconds(monkeypatch):
    from esslivedata_tpu.ops import qhistogram
    from esslivedata_tpu.telemetry.instruments import TABLE_BUILD_SECONDS
    from esslivedata_tpu.workflows import powder

    ticks = iter(range(0, 10_000, 100))  # every clock read of powder.py is 100 s after its last
    monkeypatch.setattr(powder, "time", types.SimpleNamespace(perf_counter=lambda: float(next(ticks))))
    before = TABLE_BUILD_SECONDS.value(family="dspacing")
    workflow = powder.PowderDiffractionWorkflow(
        two_theta=np.linspace(0.5, 2.5, 8), l_total=np.full(8, 77.65), pixel_ids=np.arange(8),
        params=powder.PowderDiffractionParams(d_bins=2000, d_max=3.5, two_theta_bins=17),
    )
    # powder's own two reads (the composite pass) are 100 s apart; the builder's and the placement's are real time
    assert 100.0 <= TABLE_BUILD_SECONDS.value(family="dspacing") - before < 110.0
    assert workflow._hist._family == "dspacing" and workflow._build_table().family == "dspacing"
    assert isinstance(workflow._build_table(), qhistogram.PixelBinMap)


def misses(kind, ref, published) -> dict[str, int]:
    """check -> bins of the two publishes that miss ``ref``, judged as
    ``results.compare`` judges them."""
    out: dict[str, int] = {}
    previous = 0
    for (_lo, hi), outputs in zip(WINDOWS, published, strict=True):
        for output, got in outputs.items():
            lo = previous if output.endswith("_current") else 0
            want = np.asarray(ref.expected(output, lo, hi))
            assert got.shape == want.shape, output
            tolerance = kind.tolerance(output)
            if tolerance is None:
                miss = results.bins_off(got, want)
            else:
                miss, _share = results.bins_outside(got, want, tolerance[0], tolerance[1])
            out[kind.check(output)] = out.get(kind.check(output), 0) + miss
        previous = hi
    return out


def test_the_package_publishes_what_the_reference_says_for_five_small_banks(kind, small):
    config, pools, outputs, _ = small
    for job in config["jobs"]:
        ref = kind.build(job, config, TRAFFIC, pools)
        assert ref.per_pulse.min() > 0, "every bank counts events in every pulse"
        assert set(outputs[job["name"]][-1]) == set(kind.CHECKS)
        got = misses(kind, ref, outputs[job["name"]])
        assert got == {"spectrum_bins_wrong": 0, "image_bins_wrong": 0}, job["name"]
        last = outputs[job["name"]][-1]
        assert last["dspacing_two_theta"].shape == (2000, 17) and last["dspacing_two_theta"].dtype == np.float32
        assert float(last["counts_current"]) == ref.counts(*WINDOWS[-1]) == last["dspacing_current"].sum()
        assert float(last["monitor_counts_current"]) == (WINDOWS[-1][1] - WINDOWS[-1][0]) * 2048 // 8
        # a bank of 16 strips fills 16 of its 17 bands: one band holds no voxel
        strips = job["view"]["bank"]["sizes"]["strip"]
        assert np.count_nonzero(last["dspacing_two_theta"].sum(axis=0)) == min(17, strips)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_the_kind_is_outside_the_tolerance(kind, small, fault):
    config, pools, outputs, _ = small
    caught = 0
    for job in config["jobs"]:
        broken = kind.faults()[fault](job, config, TRAFFIC, pools)
        caught += sum(misses(kind, broken, outputs[job["name"]]).values()) > 0
    assert caught == len(config["jobs"]), f"{fault}: seen in {caught} of five jobs"


def test_a_bank_that_does_not_cover_its_stream_is_refused(kind, small):
    config, pools, _, _ = small
    job = copy.deepcopy(config["jobs"][3])
    job["view"]["bank"]["first_id"] += 1  # a wrong id base shows before any comparison
    with pytest.raises(ValueError, match="does not cover"):
        kind.build(job, config, TRAFFIC, pools)


def toy(cell: manifest.Cell) -> manifest.Cell:
    """The cell with its smallest bank alone (30 720 voxels, whole: the
    service builds its table at full width) and the monitor, at a
    hundredth of the rate."""
    keep = ("sans_detector", "monitor_cave")
    config = {
        **cell.config,
        "streams": [s for s in cell.config["streams"] if s["name"] in keep],
        "jobs": [j for j in cell.config["jobs"] if j["stream"] in keep],
    }
    return dataclasses.replace(
        cell, config=config, traffic=dataclasses.replace(cell.traffic, events_per_pulse=2048))


def test_the_harness_runs_a_toy_powder_cell_on_the_package_and_finds_it_correct(cell):
    """``run.py``'s plumbing on the CPU: the dream data-reduction
    service started with no flag, one ``powder/dspacing`` job on the
    SANS bank with ``monitor_cave`` bound on its own topic, the traced
    run's counters read by the new metric files."""
    line, report = bench.run_cell(toy(cell), 2**31 + 31, 3.0, True, REPO, time.monotonic(), allow_cpu=True)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 3
    checks = line["checks"]
    assert [checks[name]["value"] for name in cell.limits] == [0, 0, 0.0]
    normalized = checks["image_bins_wrong"]
    assert normalized["tolerance"] == {"rel": 2.0**-18, "abs": 0.0} and normalized["worst_share"] <= 2.0**-6
    assert checks["compared"]["arrays"] >= 6 * line["pulses"]["publishes"]["powder_sans"]
    metrics = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert metrics["q_lookup_gather_share.paced"] == 100.0
    assert metrics["q_bincount_scatter_share.paced"] == 100.0
    assert metrics["compiles_in_window.paced"] == 0.0 and metrics["publishes_ahead_share.paced"] == 0.0
    assert any(text.startswith("check image_bins_wrong: ") and "bfloat16" in text for text in report)

"""LOKI whole, as files of the benchmark: the configuration, its cell
and its reference kind ``sans_iq_banks``, and the package against that
reference on seeded events: nine small banks of the same plan, on the
CPU (counts and exactness only)."""

from __future__ import annotations

import ast
import copy
import json

import numpy as np
import pytest
from bench_support import REPO
from harness import manifest, reference, results
from harness.traffic import Traffic

CELL = "loki_iq.paced14"
TUBES = (56, 16, 12, 16, 12, 28, 32, 20, 32)
SMALL = {"tubes": 3, "pixels_per_straw": 16}  # of every small bank
TRAFFIC = Traffic(pulse_hz=14, events_per_pulse=2048, out_of_range_probes=6, pool_pulses=5, toa_bins=200)
WINDOWS = ((0, 3), (3, 7))  # the pulses of two publishes


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(REPO, CELL)


@pytest.fixture(scope="module")
def kind(cell):
    return cell.kinds["sans_iq_banks"]


def test_the_cell_is_in_the_manifest_whole_and_as_files(cell):
    assert manifest.check(REPO) == []
    entry = next(c for c in manifest.load_manifest(REPO)["configs"] if c["name"] == "loki_iq")
    assert entry["reduced"] == [] and cell.config["reduced"] == {}  # every bank, every pixel
    assert (cell.chips, cell.config_name, cell.traffic_name) == (1, "loki_iq", "paced14_toa200")
    assert cell.config["service"] == "data_reduction" and cell.config["service_flags"] == []
    assert cell.traffic.toa_bins == 200 and cell.traffic.pool_pulses == 13
    assert cell.traffic.events_per_pulse % 32768 == 0
    detectors = [s for s in cell.config["streams"] if s.get("kind", "detector") == "detector"]
    assert [s["n_pixels"] for s in detectors] == [4 * t * 7 * 512 for t in TUBES]
    assert sum(s["n_pixels"] for s in detectors) == 3_211_264
    first = 1
    for stream, job in zip(detectors, cell.config["jobs"], strict=True):
        assert stream["first_id"] == job["view"]["bank"]["first_id"] == first
        assert job["stream"] == job["job_source"] == stream["name"]
        assert job["aux_source_names"] == {"monitor": "monitor_1", "transmission_monitor": "monitor_2"}
        assert job["params"] == {}  # default SansIQParams
        first += stream["n_pixels"]
    assert [s["name"] for s in detectors] == [f"loki_detector_{i}" for i in range(9)]
    assert set(cell.limits) == set(reference.check_names(cell.config, cell.kinds))
    accepted = manifest.load_cell(REPO, "nmx_panels.paced14").limits
    assert cell.limits == accepted  # the same exact limits in every cell
    why = json.loads((REPO / "benchmark" / "limits" / f"{CELL}.json").read_text())["why"]
    assert set(why) == set(cell.limits)
    listed = {m["name"] for m in cell.per_layer}
    assert {"private_windows_share.paced", "q_step_ms.paced", "tick_roofline.paced"} <= listed
    assert not {"flatten_ms.paced", "groups_ahead_share.paced"} & listed  # nothing to read there


@pytest.mark.parametrize("metric", ["private_windows_share.paced", "q_step_ms.paced"])
def test_the_new_metrics_are_the_new_cells_alone(metric):
    entry = next(m for m in manifest.load_manifest(REPO)["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [CELL] and entry["layer"] == "stage + tick program, host side"
    doc = json.loads((REPO / "benchmark" / "metrics" / f"{metric}.json").read_text())
    assert doc["reader"]["kind"] == "prometheus" and "absent_is_zero" not in doc["reader"]


def test_the_kinds_module_imports_nothing_of_the_program():
    tree = ast.parse((REPO / "benchmark" / "references" / "sans_iq_banks.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {alias.name.split(".")[0] for alias in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported == {"__future__", "numpy", "harness"}


def test_the_kind_states_its_protocol(kind):
    assert set(kind.faults()) >= {
        "monitor_twice", "toa_bin_off_by_one", "transmission_unbound", "bank_off_by_one"}
    for exact in ("counts_q_current", "monitor_counts_current", "transmission_current"):
        assert kind.tolerance(exact) is None and kind.check(exact) == "spectrum_bins_wrong"
    assert kind.check("iq_current") == kind.check("iq_cumulative") == "image_bins_wrong"
    rel, abs_, reason = kind.tolerance("iq_cumulative")
    assert 2.0**-24 < rel < 2.0**-12 and abs_ == 0.0 and "bfloat16" in reason  # between the two precisions
    job = {"view": {"q": {"bins": 100}}}
    assert kind.work_bytes(job, {}, 1000, 0) == 18 * 1000  # 8 B in, 2 B gathered, 8 B of Q bin
    assert kind.work_bytes(job, {}, 0, 1) == 4 * 100 * 4 + 4 * 202


@pytest.mark.parametrize("bank", [1, 2])
def test_the_references_positions_are_the_artifacts_at_full_size(cell, kind, bank, monkeypatch, tmp_path):
    """One bank with straws along x and one along y, every pixel, from
    the package's synthesized geometry file; no table is built."""
    from esslivedata_tpu.config.instruments.loki.geometry import bank_geometry

    monkeypatch.setenv("LIVEDATA_DATA_DIR", str(tmp_path))
    positions, ids = bank_geometry(f"loki_detector_{bank}")
    view = cell.config["jobs"][bank]["view"]["bank"]
    mine = kind.bank_positions(view)
    assert mine.shape == positions.shape == (4 * TUBES[bank] * 7 * 512, 3)
    assert np.abs(mine - positions).max() <= 1e-12
    assert ids[0] == view["first_id"] and ids[-1] == view["first_id"] + len(ids) - 1


def small_config(config: dict) -> dict:
    """The configuration with every bank cut to SMALL, ids consecutive,
    and the transmission monitor at half the incident one's rate: at the
    cell's equal rates T is 1 and a job that ignores it does not show."""
    doc = copy.deepcopy(config)
    next(s for s in doc["streams"] if s["name"] == "monitor_2")["rate_share"] = 0.0625
    first = 1
    for stream, job in zip(doc["streams"], doc["jobs"]):
        bank = job["view"]["bank"]
        bank.update(SMALL, first_id=first)
        n = bank["layers"] * bank["tubes"] * bank["straws"] * bank["pixels_per_straw"]
        stream.update(first_id=first, n_pixels=n)
        first += n
    return doc


@pytest.fixture(scope="module")
def small(cell):
    """(config, pools, job -> the package's outputs of the two windows)."""
    from esslivedata_tpu.config.nexus_plans import plan_for
    from esslivedata_tpu.config.nexus_synthesis import straw_positions
    from esslivedata_tpu.core import Timestamp
    from esslivedata_tpu.preprocessors import DetectorEvents, MonitorEvents, ToEventBatch
    from esslivedata_tpu.workflows.sans import SansIQWorkflow

    def staged(ids, toa):
        acc = ToEventBatch(min_bucket=16)
        toa = toa.astype(np.float32)
        acc.add(Timestamp.from_ns(0), DetectorEvents(pixel_id=ids, time_of_arrival=toa)
                if ids.size else MonitorEvents(time_of_arrival=toa))
        return acc.get()

    config = small_config(cell.config)
    pools = reference.make_pools(config, TRAFFIC, 2**31 + 27)
    index = {s["name"]: i for i, s in enumerate(config["streams"])}
    plans = {b.name: b for b in plan_for("loki").banks}
    outputs = {}
    for job in config["jobs"]:
        bank, plan = job["view"]["bank"], plans[job["stream"]]
        shape = (bank["layers"], bank["tubes"], bank["straws"], bank["pixels_per_straw"])
        n = int(np.prod(shape))
        workflow = SansIQWorkflow(
            positions=straw_positions(shape, plan.panel),  # the package's plan for this bank
            pixel_ids=np.arange(bank["first_id"], bank["first_id"] + n),
            primary_stream=job["stream"],
            monitor_streams={"monitor_1"},
            transmission_streams={"monitor_2"},
        )
        published = []
        for lo, hi in WINDOWS:
            window = {}
            for name in (job["stream"], "monitor_1", "monitor_2"):
                pool, _ = pools[index[name]]
                ids = np.concatenate([pool[k % len(pool)][0] for k in range(lo, hi)])
                toa = np.concatenate([pool[k % len(pool)][1] for k in range(lo, hi)])
                window[name] = staged(ids, toa)
            workflow.accumulate(window)
            published.append({k: np.asarray(v.values) for k, v in workflow.finalize().items()})
        outputs[job["name"]] = published
    return config, pools, outputs


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


def test_the_package_publishes_what_the_reference_says_for_nine_small_banks(kind, small):
    config, pools, outputs = small
    for job in config["jobs"]:
        ref = kind.build(job, config, TRAFFIC, pools)
        assert ref.per_pulse.min() > 0, "every bank counts events in every pulse"
        got = misses(kind, ref, outputs[job["name"]])
        assert got == {"spectrum_bins_wrong": 0, "image_bins_wrong": 0}, job["name"]
        last = outputs[job["name"]][-1]
        assert float(last["transmission_current"]) == 0.5  # monitor_2 at half monitor_1's rate
        assert last["counts_q_current"].sum() == ref.counts(*WINDOWS[-1])


@pytest.mark.parametrize("fault", ["monitor_twice", "toa_bin_off_by_one", "transmission_unbound",
                                   "bank_off_by_one", "quotient_bfloat16"])
def test_each_fault_of_the_kind_is_outside_the_tolerance(kind, small, fault):
    config, pools, outputs = small
    caught = 0
    for job in config["jobs"]:
        broken = kind.faults()[fault](job, config, TRAFFIC, pools)
        caught += sum(misses(kind, broken, outputs[job["name"]]).values()) > 0
    assert caught == len(config["jobs"]), f"{fault}: seen in {caught} of nine jobs"


def test_at_the_cells_own_rates_the_transmission_fraction_is_one(cell, kind, small):
    """ISSUE 27 fixed both monitors at an eighth of a bank's rate: T is
    exactly 1 in every window, which is what the cell compares, and the
    fault ``transmission_unbound`` says the same there (stated in the
    configuration's ``assumed`` and in the limits' ``why``)."""
    from harness.traffic import stream_events

    monitors = [s for s in cell.config["streams"] if s.get("kind") == "monitor"]
    assert [s["rate_share"] for s in monitors] == [0.125, 0.125]
    assert {stream_events(s, cell.traffic) for s in monitors} == {cell.traffic.events_per_pulse // 8}
    config, _, _ = small
    equal = copy.deepcopy(config)
    next(s for s in equal["streams"] if s["name"] == "monitor_2")["rate_share"] = 0.125
    pools = reference.make_pools(equal, TRAFFIC, 2**31 + 27)
    job = equal["jobs"][0]
    sound = kind.build(job, equal, TRAFFIC, pools)
    unbound = kind.faults()["transmission_unbound"](job, equal, TRAFFIC, pools)
    for lo, hi in WINDOWS:
        assert float(sound.expected("transmission_current", lo, hi)) == 1.0
        for output in ("transmission_current", "iq_current", "monitor_counts_current"):
            assert np.array_equal(sound.expected(output, lo, hi), unbound.expected(output, lo, hi))


def test_a_bank_that_does_not_cover_its_stream_is_refused(kind, small):
    config, pools, _ = small
    job = copy.deepcopy(config["jobs"][3])
    job["view"]["bank"]["first_id"] += 1  # a wrong id base shows before any comparison
    with pytest.raises(ValueError, match="does not cover"):
        kind.build(job, config, TRAFFIC, pools)

"""The harness end to end on the package's dummy instrument, through the
fixture's configuration and cells (hotspot pixels and four messages a
pulse in the paced one), and on its toy LOKI (``sans/iq`` on the
data-reduction service: a reference kind, a monitor stream and float
outputs that came as files), on the CPU: what a run measures and
compares is driven as on the chip, only the look for a chip is skipped.
The command itself keeps that look, and a test pins it."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from bench_support import FIXTURE, PLUGS, REPO, overlay_fixture
from harness import bench, manifest, reference, service

SECONDS = 3.0


def run(toy_root, cell_name, seed, *, trace=False, cell_edit=None, **how):
    cell = manifest.load_cell(toy_root, cell_name)
    if cell_edit:
        cell = cell_edit(cell)
    return bench.run_cell(
        cell, seed, SECONDS, trace, REPO, time.monotonic(), allow_cpu=True, **how
    )


def shape_of_a_result(line, cell, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"  # the numbers compared come last
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert "memory_peak_bytes" in line["device"]
    wanted = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in wanted}
    for name, entry in line["metrics"].items():
        unit = next(m["unit"] for m in wanted if m["name"] == name)
        assert entry["unit"] == unit and isinstance(entry["value"], float)
    assert list(line["checks"])[: len(cell.limits)] == list(cell.limits)
    for name in ("spectrum_bins_wrong", "image_bins_wrong", "prefix_off_pulses"):
        if name in cell.limits:
            assert set(line["checks"][name]) == {"value", "limit"}


def test_paced_cell_reports_freshness_of_every_pair_and_is_correct(toy_root):
    line, report = run(toy_root, "toy_panel.toy_paced", 2**31 + 5)
    cell = manifest.load_cell(toy_root, "toy_panel.toy_paced")
    shape_of_a_result(line, cell, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == int(SECONDS * 14) // 14  # one job, one publish a window
    assert set(line["metrics"]) == {"freshness_p50_ms", "freshness_p95_ms", "setup_s"}
    p50, p95 = (line["metrics"][k]["value"] for k in ("freshness_p50_ms", "freshness_p95_ms"))
    # a window closes on the next pulse (71 ms) and the poll; never before
    assert 71.0 < p50 <= p95 < 2000.0
    assert line["checks"]["compared"]["spectra"] >= 2 * line["attempted"]
    assert line["checks"]["compared"]["images"] >= 2
    assert any(text.startswith("check failed_publishes: 0 of") for text in report)
    assert report[0].startswith("check spectrum_bins_wrong: ")


@pytest.mark.parametrize("cell_name, seed", [("toy_panel.toy_paced", 11), ("toy_loki.toy_iq", 2**31 + 11)])
def test_traced_run_prints_the_per_layer_metrics_that_found_something(toy_root, cell_name, seed):
    line, _ = run(toy_root, cell_name, seed, trace=True)
    cell = manifest.load_cell(toy_root, cell_name)
    shape_of_a_result(line, cell, trace=True)
    assert line["correct"] is True
    # prometheus and generator readers found their counters; the CPU has
    # no device plane in its trace, so the trace readers return nothing
    # and their metrics are left out (never 0 for a share of a peak).
    assert {"generator_late_p95_ms", "decode_ms.paced", "compiles_in_window.paced"} <= set(line["metrics"])
    assert line["metrics"]["compiles_in_window.paced"]["value"] == 0.0
    if not cell.kinds:
        assert line["metrics"]["toy_messages"]["value"] >= 4 * 14 * (SECONDS - 1)
    assert "freshness_p50_ms" not in line["metrics"]
    assert "breakdown" not in line and "busy_s" not in line["device"]


def test_every_pair_due_in_the_window_is_in_the_line_with_its_due_time(toy_root):
    line, _ = run(toy_root, "toy_panel.toy_blob", 12)
    assert line["correct"] is True and line["failed"] == 0
    pairs = line["pulses"]["pairs"]
    # one job: one pair for each base window whose last pulse was due in the window
    assert len(pairs) in (int(SECONDS) - 1, int(SECONDS), int(SECONDS) + 1)
    assert all(0.0 <= at < line["pulses"]["window_s"] for at, _ in pairs)
    assert [at for at, _ in pairs] == sorted(at for at, _ in pairs)
    assert max(ms for _, ms in pairs) == pytest.approx(line["pulses"]["freshness_max_ms"], abs=1e-3)
    assert line["metrics"]["freshness_p95_ms"]["value"] <= line["pulses"]["freshness_max_ms"]


def _control():
    spec = importlib.util.spec_from_file_location("bench_control", REPO / "benchmark" / "control.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("cell_name, seed", [("toy_panel.toy_paced", 14), ("toy_loki.toy_iq", 2**31 + 14)])
def test_controls_read_beside_a_sound_run_and_each_comes_out_not_correct(toy_root, cell_name, seed):
    """The reference with one guarantee broken, put in the program's
    place, as ``benchmark/control.py`` lists them for the cell (the
    pools' faults, then the faults of each reference kind of its own):
    the sound run stays correct, every control does not."""
    cell = manifest.load_cell(toy_root, cell_name)
    controls = _control().controls_of(cell)
    own = tuple(f"sans_iq.{name}" for name in cell.kinds["sans_iq"].faults()) if cell.kinds else ()
    assert controls == (*reference.FAULTS, *own) and len(own) == 2 * len(cell.kinds)
    line, _ = run(toy_root, cell_name, seed, controls=controls)
    assert line["correct"] is True
    assert list(line)[-2:] == ["controls", "checks"]
    assert list(line["controls"]) == list(controls)
    for fault, reading in line["controls"].items():
        assert reading["correct"] is False and reading["failed"] > 0
        assert set(reading) == {"correct", "failed", *cell.limits}
        if not cell.kinds:
            assert reading["spectrum_bins_wrong"] >= 1
        elif fault != "sans_iq.monitor_twice":
            assert reading["q_counts_wrong"] >= 1
    assert line["controls"]["half_pulse"]["prefix_off_pulses"] > 0.3
    if cell.kinds:
        twice = line["controls"]["sans_iq.monitor_twice"]
        assert twice["q_counts_wrong"] == 0 and twice["prefix_off_pulses"] < 0.1
        assert twice["iq_bins_off"] > 0 and twice["monitor_counts_wrong"] > 0


def test_a_kind_a_monitor_stream_and_float_outputs_run_as_files(toy_root):
    """The toy LOKI cell, with no edit to any file under ``benchmark/``:
    correct, every pair's freshness found from the sum of the job's own
    ``counts_q_current`` over its publishes, the I(Q) judged by the
    tolerance its kind states (printed with the reason), the monitor on
    its own topic at an eighth of the rate (its counts exact: one topic
    or one event off and they are not)."""
    line, report = run(toy_root, "toy_loki.toy_iq", 2**31 + 27)
    cell = manifest.load_cell(toy_root, "toy_loki.toy_iq")
    shape_of_a_result(line, cell, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == int(SECONDS * 14) // 14
    assert set(line["metrics"]) == {"freshness_p50_ms", "freshness_p95_ms", "setup_s"}
    assert 71.0 < line["metrics"]["freshness_p50_ms"]["value"] < 2000.0
    assert len(line["pulses"]["pairs"]) in (int(SECONDS) - 1, int(SECONDS), int(SECONDS) + 1)
    checks = line["checks"]
    assert list(checks)[:4] == ["prefix_off_pulses", "iq_bins_off", "q_counts_wrong", "monitor_counts_wrong"]
    assert checks["prefix_off_pulses"]["value"] == 0.0  # exact sums of exact windows
    assert checks["compared"] == {"spectra": 0, "images": 0, "arrays": 4 * line["pulses"]["publishes"]["iq"]}
    iq = checks["iq_bins_off"]
    assert iq["value"] == 0 and iq["tolerance"] == {"rel": 2.0**-22, "abs": 0.0}
    assert 0 < iq["worst_share"] <= 0.25 and "float32 quotient" in iq["reason"]
    assert set(checks["q_counts_wrong"]) == set(checks["monitor_counts_wrong"]) == {"value", "limit"}
    assert any(text.startswith("check iq_bins_off: ") and "float32 quotient" in text for text in report)
    # the generator sent a pulse as four messages: two of the detector's, two of the monitor's
    assert line["pulses"]["sent"] > 14 * SECONDS


def test_a_camera_cell_runs_as_files_and_each_frames_fault_is_caught(toy_root):
    """The toy ODIN cell, with no edit to any file under ``benchmark/``:
    the package's ``camera_view`` on the detector service fed 64 x 64
    uint16 ad00 frames, correct, every pair's freshness found from the
    sum of the job's cumulative image; and ``control.py``'s four faults
    of the kind ``frames`` (a frame dropped, counted twice, transposed,
    truncated to uint8), each put in the program's place, not correct."""
    cell = manifest.load_cell(toy_root, "toy_odin.toy_camera")
    controls = _control().controls_of(cell)
    assert controls == ("frames.frame_dropped", "frames.frame_twice", "frames.frame_transposed",
                        "frames.frame_uint8")
    line, report = run(toy_root, "toy_odin.toy_camera", 2**31 + 41, controls=controls)
    shape_of_a_result(line, cell, trace=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == int(SECONDS * 14) // 14
    assert 71.0 < line["metrics"]["freshness_p50_ms"]["value"] < 2000.0
    assert len(line["pulses"]["pairs"]) in (int(SECONDS) - 1, int(SECONDS), int(SECONDS) + 1)
    checks = line["checks"]
    assert list(checks)[:2] == ["frame_bins_wrong", "prefix_off_pulses"]
    assert checks["frame_bins_wrong"] == {"value": 0, "limit": 0}
    assert checks["prefix_off_pulses"]["value"] == 0.0  # float64 sums of whole counts
    assert checks["compared"] == {"spectra": 0, "images": 0, "arrays": 2 * line["pulses"]["publishes"]["camera"]}
    # a pulse is one ad00 frame of 64 x 64 x 2 B and its framing
    assert line["pulses"]["bytes"] > line["pulses"]["sent"] * 64 * 64 * 2
    assert any(text.startswith("check frame_bins_wrong: ") for text in report)
    assert list(line["controls"]) == list(controls)
    for fault, reading in line["controls"].items():
        assert reading["correct"] is False and reading["failed"] > 0, fault
        assert reading["frame_bins_wrong"] > 0, fault


def test_aux_source_names_go_on_the_wire_and_default_to_none():
    loki = json.loads((FIXTURE / "configs" / "toy_loki.json").read_text())["jobs"][0]
    sent = json.loads(service.start_command("loki", loki, "n-1"))
    assert sent["config"]["aux_source_names"] == {"monitor": "monitor_1"}
    assert sent["config"]["identifier"] == {
        "instrument": "loki", "namespace": "sans", "name": "iq", "version": 1}
    assert sent["config"]["job_id"] == {"source_name": "larmor_detector", "job_number": "n-1"}
    panel = json.loads((REPO / "benchmark" / "configs" / "nmx_panels.json").read_text())["jobs"][0]
    sent = json.loads(service.start_command("nmx", panel, "n-2"))
    assert sent["config"]["aux_source_names"] == {} and sent["kind"] == "start_job"
    assert list(sent["config"]) == ["identifier", "job_id", "params", "aux_source_names", "schedule"]


@pytest.mark.parametrize(
    "fault, cell_name",
    [
        ("altered_answer", "toy_panel.toy_paced"),
        ("half_batch", "toy_panel.toy_paced"),
        ("state_unchanged", "toy_panel.toy_paced"),
        ("half_batch", "toy_panel.toy_blob"),
        ("half_batch", "toy_loki.toy_iq"),
        ("altered_frame", "toy_odin.toy_camera"),
    ],
)
def test_a_fault_under_the_timed_path_makes_correct_false(toy_root, monkeypatch, fault, cell_name):
    """The rest of a run as it is, the service broken underneath: an
    answer altered where it is produced, half of every batch left out
    (of the monitor's too, where there is one), a step that returns its
    state unchanged, a camera frame altered where it is decoded."""
    monkeypatch.setenv("BENCH_TEST_FAULT", fault)
    monkeypatch.setenv("BENCH_TEST_SERVICE", manifest.load_cell(toy_root, cell_name).config["service"])
    monkeypatch.setenv("PYTHONPATH", str(FIXTURE.parent))

    def faulty(cell):
        return dataclasses.replace(
            cell, config={**cell.config, "service": "fixture.faulty_service"}
        )

    line, report = run(toy_root, cell_name, 15, cell_edit=faulty)
    assert line["correct"] is False
    assert line["failed"] > 0
    assert any(text.startswith("check failed_publishes: ") for text in report)


def make_checkout(root):
    overlay_fixture(root)
    shutil.copytree(REPO / "benchmark" / "harness", root / "benchmark" / "harness",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "benchmark" / "run.py", root / "benchmark" / "run.py")
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """What the driver's checkout holds of the benchmark, with the
    fixture's files laid over it and the program beside it."""
    return make_checkout(tmp_path_factory.mktemp("checkout"))


def command(root, *args):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), *args],
        capture_output=True, text=True, timeout=120, cwd=root, env={**env, "BENCH_RUN": "7"},
    )


def test_the_command_gives_no_result_without_the_program_beside_it(checkout):
    done = command(checkout, "--workload", "toy_panel.toy_paced", "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and done.stdout == ""
    assert "not beside" in done.stderr


def test_the_command_accepts_cells_added_as_files_and_refuses_a_cpu(checkout):
    (checkout / "src").symlink_to(REPO / "src")
    done = command(checkout, "--workload", "toy_panel.toy_paced", "--seed", "3000000019",
                   "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert done.stdout == "", "no result line without a TPU"
    assert "not a TPU" in done.stderr
    unknown = command(checkout, "--workload", "toy_panel.nowhere", "--seed", "1",
                      "--seconds", "1", "--trace", "0")
    assert unknown.returncode != 0 and unknown.stdout == ""
    assert "no workload" in unknown.stderr
    assert not list(checkout.glob("benchmark/**/broker")), "the broker lives outside the checkout"


@pytest.mark.parametrize("plug", sorted(PLUGS))
def test_the_command_names_a_broken_plug_before_it_starts_a_service(tmp_path, plug):
    root = make_checkout(tmp_path)
    (root / "src").symlink_to(REPO / "src")
    edit, word = PLUGS[plug]
    edit(root / "benchmark")
    began = time.monotonic()
    done = command(root, "--workload", "toy_loki.toy_iq", "--seed", "5", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and done.stdout == ""
    assert word in done.stderr and "no result" in done.stderr
    # no service was started: nothing looked for a chip, and no start-up was waited for
    assert "not a TPU" not in done.stderr and time.monotonic() - began < 5.0

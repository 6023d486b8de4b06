"""From a trace to per-layer numbers, and the least time from shapes:
interval arithmetic on hand-made cases, the reduction on a small trace
recorded on the chip (``fixture/trace_dream_ticks.json``: three ticks of
the first round's mantle-only DREAM cell, op names shortened), and the bytes per tick of
both configurations worked out by hand."""

from __future__ import annotations

import json

import pytest
from bench_support import FIXTURE, REPO
from harness import roofline, trace_reduce

BENCH = REPO / "benchmark"
NMX = json.loads((BENCH / "configs" / "nmx_panels.json").read_text())
DREAM = json.loads((BENCH / "configs" / "dream_banks.json").read_text())


@pytest.fixture(scope="module")
def recorded():
    return json.loads((FIXTURE / "trace_dream_ticks.json").read_text())


def test_union_clip_gaps_on_hand_made_intervals():
    spans = [(0, 10), (5, 15), (20, 30), (30, 31), (50, 60)]
    assert trace_reduce.union(spans) == [(0, 15), (20, 31), (50, 60)]
    assert trace_reduce.busy_ns(spans) == 15 + 11 + 10
    assert trace_reduce.clip(spans, 8, 25) == [(8, 10), (8, 15), (20, 25)]
    assert trace_reduce.clip(spans, 100, 200) == []
    merged = trace_reduce.union(trace_reduce.clip(spans, 8, 55))
    assert trace_reduce.gaps(merged, 8, 55) == [(15, 20), (31, 50)]
    assert trace_reduce.gaps([], 0, 7) == [(0, 7)]
    assert trace_reduce.gaps([(0, 7)], 0, 7) == []


def test_a_gap_is_named_by_what_the_host_was_doing():
    spans = [["decode", 100, 20, 1], ["fetch", 150, 30, 1], ["sink", 190, 10, 1],
             ["decode", 400, 10, 2]]
    envelopes = trace_reduce.tick_envelopes(spans)
    assert envelopes == [(100, 200), (400, 410)]
    # under decode 10, unspanned inside the tick 30, under fetch 5
    assert trace_reduce.name_gap((110, 155), spans, envelopes) == {
        "decode": 10, "fetch": 5, "stage": 30}
    assert trace_reduce.name_gap((200, 400), spans, envelopes) == {"between_ticks": 200}
    assert trace_reduce.name_gap((195, 405), spans, envelopes) == {
        "sink": 5, "decode": 5, "between_ticks": 200}


def test_reduce_cuts_to_the_window_and_averages_over_devices():
    events = {
        "ops": {
            "/device:TPU:0": [["a", 0, 100], ["b", 50, 100], ["a", 900, 200]],
            "/device:TPU:1": [["a", 400, 100]],
            "/device:TPU:2": [["c", 5000, 10]],  # not in the window: not a device used
        },
        "modules": {},
        "spans": [["decode", 390, 5, 1], ["fetch", 400, 100, 1]],
    }
    out = trace_reduce.reduce(events, 100, 1000)
    # device 0: [100,150) + [900,1000) = 150; device 1: 100; mean 125
    assert out["busy_s"] == pytest.approx(125e-9)
    assert out["window_s"] == pytest.approx(900e-9)
    assert out["device_idle_pct"] == pytest.approx(100 * (1 - 125 / 900))
    assert out["batches"] == 1 and out["tick_device_ms"] == pytest.approx(125e-6)
    assert dict(out["breakdown"]["device_ops"]) == pytest.approx(
        {"a": (100 + 100) * 1e-9, "b": 50e-9})
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx((750 + 800) * 1e-9)
    assert trace_reduce.reduce(events, 2000, 3000) == {}


def test_short_name_keeps_opcode_name_and_result():
    text = ("%fusion = f32[163840001]{0:T(1024)} fusion(f32[163840001]{0:T(1024)} %w, "
            "s32[4194304]{0:T(1024)S(1)} %i), kind=kCustom, calls=%fused_computation")
    assert trace_reduce.short_name(text) == "fusion %fusion f32[163840001]"
    assert trace_reduce.short_name("jit_tick(123)") == "jit_tick(123)"
    assert len(trace_reduce.short_name("x" * 500)) == 120


def test_no_trace_is_no_numbers(tmp_path):
    assert trace_reduce.load(tmp_path, tmp_path / "ticks.json", 0) is None


def test_recorded_trace_reduces_to_what_the_chip_run_read(recorded):
    t0, t1 = recorded["window"]
    out = trace_reduce.reduce(recorded["events"], t0, t1)
    want = recorded["expected"]
    assert out["batches"] == want["batches"] == 3
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["device_idle_pct"] == pytest.approx(want["device_idle_pct"], rel=1e-9)
    assert 80.0 < out["device_idle_pct"] < 99.0
    ops = dict(out["breakdown"]["device_ops"])
    top = max(ops, key=ops.get)
    assert top.startswith("fusion ")  # the scatter into the bin space
    # the ten ops kept are all but a thousandth of the busy time (ops of one device do not overlap)
    assert 0.999 * out["busy_s"] < sum(ops.values()) <= out["busy_s"]
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    assert max(gaps, key=gaps.get) == "between_ticks"  # paced: the loop waits for the beam
    # the same trace, cut to its first tick alone
    first = trace_reduce.reduce(recorded["events"], t0, t0 + (t1 - t0) // 3)
    assert first["batches"] == 1 and first["busy_s"] < out["busy_s"] / 2


def test_bytes_per_tick_of_both_configurations_by_hand():
    events = 14 * 262144  # one base window of one stream
    # NMX, one job: 3 670 016 events x 12 B; fold 4 passes x 163 840 000 bins x 4 B;
    # fetch 2 images + 2 spectra + 4 scalars in float32
    nmx_job = 3_670_016 * 12 + 4 * 163_840_000 * 4 + 4 * (2 * 1_638_400 + 200 + 4)
    assert nmx_job == 44_040_192 + 2_621_440_000 + 13_108_016
    assert roofline.job_bytes(NMX["jobs"][0], 100, events, 1) == nmx_job
    least = roofline.least_seconds(
        NMX, {j["name"]: events for j in NMX["jobs"]}, {j["name"]: 1 for j in NMX["jobs"]},
        "TPU v5 lite")
    assert least == pytest.approx(3 * nmx_job / 819e9)
    assert 9.0e-3 < least < 1.0e-2  # 9.8 ms a tick at the HBM peak
    # DREAM: the mantle's screens of 60 x 256, 32 x 60 and 256 x 1, then a screen of
    # every voxel for each other bank (strip against the rest)
    screens = [roofline.screen_bins(j["view"]) for j in DREAM["jobs"]]
    assert screens == [15_360, 1_920, 256, 157_696, 71_680, 61_440, 30_720]
    assert sum(screens) == 339_072
    dream = sum(events * 12 + 4 * s * 100 * 4 + 4 * (2 * s + 200 + 4) for s in screens)
    assert dream == 7 * 44_040_192 + 16 * 100 * 339_072 + 8 * 339_072 + 7 * 816
    assert roofline.least_seconds(
        DREAM, {j["name"]: events for j in DREAM["jobs"]}, {j["name"]: 1 for j in DREAM["jobs"]},
        "TPU v5 lite") == pytest.approx(dream / 819e9)
    # no publish, no fold: events alone
    assert roofline.job_bytes(DREAM["jobs"][2], 100, 1000, 0) == 12_000


def test_an_unknown_device_kind_is_an_error_not_a_default():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.least_seconds(DREAM, {j["name"]: 1 for j in DREAM["jobs"]},
                               {j["name"]: 1 for j in DREAM["jobs"]}, "cpu")

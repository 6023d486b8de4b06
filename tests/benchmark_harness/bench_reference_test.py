"""The plain reference: its views against the instrument packages it
mirrors, its arithmetic against a loop, and its control — the reference
with one guarantee broken, put in the program's place — coming out as
not correct."""

from __future__ import annotations

import json

import numpy as np
import pytest
from bench_support import REPO
from harness import reference, results
from harness.traffic import Traffic

BENCH = REPO / "benchmark"
LIMITS = json.loads((BENCH / "limits" / "nmx_panels.paced14.json").read_text())["limits"]
DREAM = json.loads((BENCH / "configs" / "dream_banks.json").read_text())
NMX = json.loads((BENCH / "configs" / "nmx_panels.json").read_text())
MIX = Traffic.from_dict(
    {**json.loads((BENCH / "traffic" / "paced14.json").read_text()), "events_per_pulse": 32768}
)
OUTPUTS = results.Outputs.from_config(NMX)


def test_every_cell_has_the_same_exact_limits():
    for path in (BENCH / "limits").glob("*.json"):
        assert json.loads(path.read_text())["limits"] == LIMITS


def test_limits_are_exact_where_the_outputs_are():
    assert LIMITS["spectrum_bins_wrong"] == 0 and LIMITS["image_bins_wrong"] == 0
    assert 0 < LIMITS["prefix_off_pulses"] < 0.5


@pytest.mark.parametrize("job", [j["name"] for j in DREAM["jobs"]])
def test_dream_views_mirror_the_package(job):
    from esslivedata_tpu.config.instruments.dream import factories

    spec = next(j for j in DREAM["jobs"] if j["name"] == job)
    stream = next(s for s in DREAM["streams"] if s["name"] == spec["stream"])
    if spec["workflow"][1] == "bank_view":
        table = factories._bank_projection(spec["job_source"])
    else:
        table = factories._mantle_projection(job)
    lut, shape = reference.screen_lut(spec["view"], stream["n_pixels"])
    assert shape == (table.ny, table.nx)
    first = stream["first_id"]
    assert table.lut.shape == (1, first + stream["n_pixels"])
    assert np.array_equal(table.lut[0, first:], lut)
    assert (table.lut[0, :first] == -1).all()


def test_streams_and_topics_mirror_the_packages():
    import esslivedata_tpu.config.instruments  # noqa: F401 - registers
    from esslivedata_tpu.config.instrument import instrument_registry
    from esslivedata_tpu.config.streams import get_stream_mapping

    for config in (DREAM, NMX):
        instrument = instrument_registry[config["instrument"]]
        mapping = get_stream_mapping(instrument, False)
        wire_names = {stream: (key.topic, key.source_name)
                      for key, stream in mapping.detectors.items()}
        for stream in config["streams"]:
            assert wire_names[stream["name"]] == (config["detector_topic"], stream["wire_source"])
            numbers = np.asarray(instrument.detectors[stream["name"]].detector_number).ravel()
            assert numbers.min() == stream["first_id"] and numbers.size == stream["n_pixels"]
            assert numbers.max() == stream["first_id"] + stream["n_pixels"] - 1
        reckoned = sum(
            2 * 4 * config["toa_bins"] * int(np.prod(reference.screen_lut(
                j["view"], next(s for s in config["streams"] if s["name"] == j["stream"])["n_pixels"]
            )[1]))
            for j in config["jobs"]
        )
        assert config["state_bytes"] == reckoned


def _refs(config, seed=3, mix=MIX):
    pools = reference.make_pools(config, mix, seed)
    return pools, reference.build(config, mix, pools)


def test_reference_arithmetic_equals_a_loop_over_pulses():
    pools, refs = _refs(DREAM)
    stream_index = {s["name"]: i for i, s in enumerate(DREAM["streams"])}
    for job in DREAM["jobs"]:
        pool, (first, n_pixels) = pools[stream_index[job["stream"]]]
        ref = refs[job["name"]]
        lut, shape = reference.screen_lut(job["view"], n_pixels)
        lo, hi = 9, 9 + 2 * MIX.pool_pulses + 5
        image = np.zeros(shape[0] * shape[1], np.int64)
        spectrum = np.zeros(100, np.int64)
        for pulse in range(lo, hi):
            ids, toa = pool[pulse % MIX.pool_pulses]
            pix = ids.astype(np.int64) - first
            ok = (pix >= 0) & (pix < n_pixels) & (toa >= 0) & (toa < 1e9 / 14)
            screen = lut[pix[ok]]
            keep = screen >= 0
            np.add.at(image, screen[keep], 1)
            np.add.at(spectrum, (toa[ok][keep] // (1e9 / 14 / 100)).astype(int), 1)
        assert np.array_equal(ref.image(lo, hi).ravel(), image)
        assert np.array_equal(ref.spectrum(lo, hi), spectrum)
        assert ref.counts(lo, hi) == image.sum() == spectrum.sum()
        assert ref.counts(0, 0) == 0 and not ref.image(5, 5).any()
    front, wires = refs["mantle_front_layer"], refs["mantle_wire_view"]
    assert 20 < wires.counts(0, 13) / front.counts(0, 13) < 50  # one wire of 32


def test_prefix_of_finds_the_pulse_prefix_through_float32_rounding():
    _pools, refs = _refs(NMX)
    ref = refs["panel_0"]
    for n in (1, 14, 700, 5000):
        exact = ref.counts(0, n)
        as_float32 = float(np.float32(exact))
        prefix, off = ref.prefix_of(as_float32, 6000)
        assert prefix == n and off < 1e-3
    prefix, off = ref.prefix_of(ref.counts(0, 70) + 0.4 * ref.per_pulse.mean(), 6000)
    assert prefix == 70 and 0.35 < off < 0.45


def test_bins_are_compared_exactly_as_long_as_float32_holds_them():
    want = np.array([5, 2**24 - 1, 2**24, 3 * 2**24], np.int64)
    assert results.bins_off(want.astype(np.float32), want) == 0
    # below 2**24 one count off is wrong; from there on 2**-20 of the value is the room
    assert results.bins_off(np.array([6.0, 2**24 - 2, 2**24 + 16, 3 * 2**24 - 48]), want) == 2
    assert results.bins_off(np.array([5.0, 2**24 - 1, 2**24 + 18, 3 * 2**24 + 50]), want) == 2
    assert results.ROUNDING == 2.0**-20 and results.EXACT_BELOW == 2**24


def _publishes_of(refs, prefixes, clock_ns=10**9):
    """What a program that computes as ``refs`` does would publish."""
    out = {}
    for job, ref in refs.items():
        items, previous = [], 0
        for ordinal, prefix in enumerate(prefixes):
            items.append(results.Publish(
                job, ordinal, received_ns=clock_ns * (ordinal + 1),
                scalars={"counts_cumulative": float(np.float32(ref.counts(0, prefix))),
                         "counts_current": float(ref.counts(previous, prefix))},
                spectra={"spectrum_current": ref.spectrum(previous, prefix).astype(np.float64),
                         "spectrum_cumulative": ref.spectrum(0, prefix).astype(np.float64)},
                images={"image_current": ref.image(previous, prefix).astype(np.float32),
                        "image_cumulative": ref.image(0, prefix).astype(np.float32)},
            ))
            previous = prefix
        out[job] = items
    return out


PREFIXES = [14 * k for k in range(1, 13)]
#: One panel of NMX: the same arithmetic at a third of the time, for all but the first seed.
NMX_ONE = {**NMX, "streams": NMX["streams"][:1], "jobs": NMX["jobs"][:1]}


def _lighter(config, seed):
    return NMX_ONE if config is NMX and seed != 1 else config


@pytest.mark.parametrize("config", [DREAM, NMX], ids=["dream_banks", "nmx_panels"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_reference_in_the_programs_place_is_correct(config, seed):
    config = _lighter(config, seed)
    _pools, refs = _refs(config, seed)
    publishes = _publishes_of(refs, PREFIXES)
    results.assign_prefixes(publishes, refs, PREFIXES[-1] + 14, OUTPUTS.prefix_total)
    numbers, wrong = results.compare(publishes, refs, LIMITS, OUTPUTS)
    assert wrong == 0
    assert [p.prefix for p in publishes[config["jobs"][0]["name"]]] == PREFIXES
    assert all(numbers[k]["value"] <= LIMITS[k] for k in LIMITS)
    assert numbers["compared"] == {"spectra": 24 * len(refs), "images": 24 * len(refs)}


@pytest.mark.parametrize("config", [DREAM, NMX], ids=["dream_banks", "nmx_panels"])
@pytest.mark.parametrize("fault", reference.FAULTS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_as_not_correct(config, fault, seed):
    """The reference with one guarantee broken stands in for the program;
    at least one number compared passes its limit, on every seed."""
    config = _lighter(config, seed)
    pools, refs = _refs(config, seed)
    broken = reference.build(config, MIX, reference.break_guarantee(pools, fault))
    publishes = _publishes_of(broken, PREFIXES)
    results.assign_prefixes(publishes, refs, PREFIXES[-1] + 14, OUTPUTS.prefix_total)
    numbers, wrong = results.compare(publishes, refs, LIMITS, OUTPUTS)
    over = [k for k in LIMITS if numbers[k]["value"] > LIMITS[k]]
    assert over and wrong > 0, numbers
    assert numbers["spectrum_bins_wrong"]["value"] >= 1
    if fault == "half_pulse":
        # half a pulse short: the total sits between two pulse prefixes
        assert numbers["prefix_off_pulses"]["value"] > 3 * LIMITS["prefix_off_pulses"]


def test_an_unknown_fault_is_refused():
    pools, _refs_ = _refs(DREAM)
    with pytest.raises(ValueError):
        reference.break_guarantee(pools, "round_down")


def _timed(prefixes_and_times):
    return [results.Publish("job", k, received_ns=at, prefix=prefix)
            for k, (prefix, at) in enumerate(prefixes_and_times)]


def test_freshness_takes_every_pair_due_in_the_window_whenever_it_arrived():
    """Pulse k is due at k * 10 ms; the window is [100 ms, 500 ms). A
    pair counts by its last pulse's due time: one that arrives after
    the window's end is in, one due before the window opened is out."""
    ms = 10**6
    due = np.arange(60) * 10 * ms
    publishes = {"job": _timed([(8, 95 * ms), (14, 160 * ms), (28, 300 * ms), (42, 720 * ms)])}
    pairs = results.freshness(publishes, due, 100 * ms, 500 * ms, 42, 14, 900 * ms)
    assert [round(fresh) for _, fresh in pairs] == [160 - 130, 300 - 270, 720 - 410]
    assert [round(at, 2) for at, _ in pairs] == [0.03, 0.17, 0.31]


def test_pulses_offered_and_never_published_count_with_their_age_at_the_drains_end():
    ms = 10**6
    due = np.arange(60) * 10 * ms
    publishes = {"job": _timed([(14, 160 * ms)]), "idle": []}
    pairs = results.freshness(publishes, due, 100 * ms, 500 * ms, 45, 14, 900 * ms)
    # job: windows ending at pulses 28, 42 and the rest (45) never came; idle: 14 too
    assert sorted(round(fresh) for _, fresh in pairs) == sorted(
        [160 - 130, 900 - 270, 900 - 410, 900 - 440, 900 - 130, 900 - 270, 900 - 410, 900 - 440]
    )

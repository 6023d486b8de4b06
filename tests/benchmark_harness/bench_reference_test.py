"""The plain reference: its views against the instrument packages it
mirrors, its arithmetic against a loop, and its control — the reference
with one guarantee broken, put in the program's place — coming out as
not correct. The fixture's toy LOKI goes through the same tests with a
reference kind of its own (``fixture/references/sans_iq.py``)."""

from __future__ import annotations

import ast
import hashlib
import json
import subprocess
import sys
import tempfile

import numpy as np
import pytest
from bench_support import FIXTURE, KINDS, REPO
from harness import generator, reference, results, roofline
from harness.traffic import Traffic, pulse_time_ns

BENCH = REPO / "benchmark"
LIMITS = json.loads((BENCH / "limits" / "nmx_panels.paced14.json").read_text())["limits"]
DREAM = json.loads((BENCH / "configs" / "dream_banks.json").read_text())
NMX = json.loads((BENCH / "configs" / "nmx_panels.json").read_text())
MIX = Traffic.from_dict(
    {**json.loads((BENCH / "traffic" / "paced14.json").read_text()), "events_per_pulse": 32768}
)
OUTPUTS = results.Outputs.from_config(NMX)
TOY_LOKI = json.loads((FIXTURE / "configs" / "toy_loki.json").read_text())
IQ_MIX = Traffic.from_dict(json.loads((FIXTURE / "traffic" / "toy_iq.json").read_text()))
IQ_LIMITS = json.loads((FIXTURE / "limits" / "toy_loki.toy_iq.json").read_text())["limits"]
SANS_IQ = reference.load_kind(FIXTURE, "sans_iq")
#: name -> (configuration, traffic mix, its reference kinds, the limits of its cell)
CASES = {
    "dream_banks": (DREAM, MIX, {}, LIMITS),
    "nmx_panels": (NMX, MIX, {}, LIMITS),
    "toy_loki": (TOY_LOKI, IQ_MIX, {"sans_iq": SANS_IQ}, IQ_LIMITS),
}


def _sha(*arrays) -> str:
    digest = hashlib.sha256()
    for array in arrays:
        array = np.ascontiguousarray(array)
        digest.update(str(array.dtype).encode() + str(array.shape).encode() + array.tobytes())
    return digest.hexdigest()


PINNED = json.loads((FIXTURE / "pins_parent.json").read_text())


@pytest.fixture(scope="module")
def pinned_tables():
    """Pools and references of both accepted configurations at the pins' reduced rate."""
    mix = Traffic.from_dict({**json.loads((BENCH / "traffic" / "paced14.json").read_text()),
                             "events_per_pulse": PINNED["events_per_pulse"]})
    out = {}
    for name in PINNED["pins"]:
        config = CASES[name][0]
        pools = reference.make_pools(config, mix, PINNED["seed"])
        out[name] = (config, mix, pools, reference.build(config, mix, pools))
    return out


@pytest.mark.parametrize("what", ["pools", "first_pulse", "spectra", "image", "least_seconds"])
@pytest.mark.parametrize("name", sorted(PINNED["pins"]))
def test_nothing_moved_in_the_accepted_configurations(pinned_tables, name, what):
    """Pools, the generator's first pulse of messages, the reference's
    tables and the roofline's least time, each equal to what commit
    7e4ce74 (the parent of the PR that made kinds, streams and outputs
    pluggable) gave: a stream with no new key yields the bytes it
    yielded, a detector view the numbers it had."""
    config, mix, pools, refs = pinned_tables[name]
    pins = PINNED["pins"][name]
    if what == "pools":
        assert {
            stream["name"]: _sha(*[a for pulse in pool for a in pulse], np.asarray(span))
            for stream, (pool, span) in zip(config["streams"], pools)
        } == pins["pools"]
    elif what == "first_pulse":
        with tempfile.TemporaryDirectory() as tmp:
            source = generator.Generator({
                "seed": PINNED["seed"], "traffic": mix.__dict__,
                "streams": [{"topic": config["detector_topic"], **s} for s in config["streams"]],
                "broker_dir": tmp, "log_path": f"{tmp}/log",
            })
            source.producer.close()
        assert {topic for topic, _ in source.templates[0]} == {config["detector_topic"]}
        digest = hashlib.sha256()
        for index, (_topic, template) in enumerate(source.templates[0]):
            digest.update(bytes(template.stamp(index, pulse_time_ns(1000))))
        assert digest.hexdigest() == pins["first_pulse"]
        assert source.messages_per_pulse == pins["messages_per_pulse"]
    elif what == "spectra":
        assert {
            job: _sha(ref.expected("spectrum_cumulative", 0, 27),
                      ref.expected("spectrum_current", 14, 28), ref.per_pulse)
            for job, ref in refs.items()
        } == pins["spectra"]
    elif what == "image":
        assert {job: _sha(refs[job].expected("image_current", 3, 30))
                for job in pins["image"]} == pins["image"]
    else:
        batches = PINNED["batches"]  # of fixture/trace_dream_ticks.json
        assert json.loads((FIXTURE / "trace_dream_ticks.json").read_text())["expected"]["batches"] == batches
        jobs = [j["name"] for j in config["jobs"]]
        least = roofline.least_seconds(config, dict.fromkeys(jobs, batches * 14 * 229376),
                                       dict.fromkeys(jobs, batches), "TPU v5 lite")
        assert repr(least) == pins["least_seconds"]


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


def _refs(config, seed=3, mix=MIX, kinds=None):
    pools = reference.make_pools(config, mix, seed)
    return pools, reference.build(config, mix, pools, kinds)


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


def _publishes_of(config, refs, prefixes, clock_ns=10**9, through=np.float32):
    """What a program that computes as ``refs`` does would publish: the
    totals, images and exact arrays in float32, an array that states a
    tolerance ``through`` the type the quotient is computed in."""
    out = {}
    for job in config["jobs"]:
        ref, outputs = refs[job["name"]], results.Outputs.from_config(config, job)
        items, previous = [], 0

        def held(name, prefix):
            return np.asarray(ref.expected(name, *ref.span(name, previous, prefix)))

        for ordinal, prefix in enumerate(prefixes):
            total = ref.counts(0, prefix)
            items.append(results.Publish(
                job["name"], ordinal, received_ns=clock_ns * (ordinal + 1),
                # a *_current prefix total is summed by the reader: exact sums of exact windows
                total=float(total if outputs.prefix_total.endswith("_current") else np.float32(total)),
                scalars={name: float(np.float32(ref.counts(*ref.span(name, previous, prefix))))
                         for name in outputs.scalars if name.startswith("counts_")},
                spectra={name: held(name, prefix).astype(np.float64) for name in outputs.spectra},
                images={name: held(name, prefix).astype(np.float32) for name in outputs.images},
                arrays={name: held(name, prefix)
                        .astype(through if ref.tolerance(name) else np.float32).astype(np.float32)
                        for name in outputs.arrays},
            ))
            previous = prefix
        out[job["name"]] = items
    return out


def _outputs(config):
    return {job["name"]: results.Outputs.from_config(config, job) for job in config["jobs"]}


PREFIXES = [14 * k for k in range(1, 13)]
#: One panel of NMX: the same arithmetic at a third of the time, for all but the first seed.
NMX_ONE = {**NMX, "streams": NMX["streams"][:1], "jobs": NMX["jobs"][:1]}


def _lighter(config, seed):
    return NMX_ONE if config is NMX and seed != 1 else config


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_reference_in_the_programs_place_is_correct(case, seed):
    config, mix, kinds, limits = CASES[case]
    config = _lighter(config, seed)
    _pools, refs = _refs(config, seed, mix, kinds)
    publishes = _publishes_of(config, refs, PREFIXES)
    results.assign_prefixes(publishes, refs, PREFIXES[-1] + 14)
    numbers, wrong = results.compare(publishes, refs, limits, _outputs(config))
    assert wrong == 0
    assert [p.prefix for p in publishes[config["jobs"][0]["name"]]] == PREFIXES
    assert list(numbers)[: len(limits)] == list(limits)  # the checks in the limits' order
    assert all(numbers[k]["value"] <= limits[k] for k in limits)
    if kinds:
        assert numbers["compared"] == {"spectra": 0, "images": 0, "arrays": 4 * 12}
        assert set(numbers["iq_bins_off"]) == {"value", "limit", "tolerance", "worst_share", "reason"}
        assert 0 < numbers["iq_bins_off"]["worst_share"] <= 0.25  # one float32 rounding of the room of four
        assert set(numbers["q_counts_wrong"]) == {"value", "limit"}  # exact: no tolerance stated
    else:
        assert numbers["compared"] == {"spectra": 24 * len(refs), "images": 24 * len(refs)}
        assert all(set(numbers[k]) == {"value", "limit"} for k in limits)


def _lower_precision():
    """The nearest precision below float32, for the control of a float output."""
    try:
        from ml_dtypes import bfloat16
    except ImportError:
        return np.float16
    return bfloat16


#: (case, fault): every case under the pools' faults; the toy LOKI under its kind's own and,
#: since its I(Q) is a float32 quotient, computed in the precision below.
CONTROLS = [
    *((case, fault) for case in sorted(CASES) for fault in reference.FAULTS),
    *(("toy_loki", f"sans_iq.{fault}") for fault in SANS_IQ.faults()),
    ("toy_loki", "lower_precision"),
]


@pytest.mark.parametrize("case, fault", CONTROLS, ids=[f"{c}-{f}" for c, f in CONTROLS])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_comes_out_as_not_correct(case, fault, seed):
    """The reference with one guarantee broken stands in for the program;
    at least one number compared passes its limit, on every seed."""
    config, mix, kinds, limits = CASES[case]
    config = _lighter(config, seed)
    pools, refs = _refs(config, seed, mix, kinds)
    through = np.float32
    if fault in reference.FAULTS:
        broken = reference.build(config, mix, reference.break_guarantee(pools, fault), kinds)
    elif fault == "lower_precision":
        broken, through = refs, _lower_precision()
    else:
        broken = reference.build(config, mix, pools, kinds, fault)
    publishes = _publishes_of(config, broken, PREFIXES, through=through)
    results.assign_prefixes(publishes, refs, PREFIXES[-1] + 14)
    numbers, wrong = results.compare(publishes, refs, limits, _outputs(config))
    over = [k for k in limits if numbers[k]["value"] > limits[k]]
    assert over and wrong > 0, numbers
    if not kinds:
        assert numbers["spectrum_bins_wrong"]["value"] >= 1
    elif fault == "lower_precision":
        assert over == ["iq_bins_off"] and numbers["iq_bins_off"]["worst_share"] > 2**9
    elif fault == "sans_iq.monitor_twice":
        assert over == ["iq_bins_off", "monitor_counts_wrong"]
        assert numbers["iq_bins_off"]["worst_share"] > 1e6  # half of the value, not a rounding
    else:
        assert numbers["q_counts_wrong"]["value"] >= 1 and numbers["iq_bins_off"]["value"] >= 1
    if fault == "half_pulse":
        # half a pulse short: the total sits between two pulse prefixes
        assert numbers["prefix_off_pulses"]["value"] > 3 * limits["prefix_off_pulses"]


def test_a_reference_kind_imports_nothing_of_the_program_and_the_harness_no_jax():
    """A kind's module stands on numpy and the harness alone (its
    imports are read, not run), and loading the harness, a kind and the
    command's own modules leaves jax and the program unimported."""
    modules = [*(FIXTURE / KINDS).glob("*.py"), *(BENCH / KINDS).glob("*.py")]
    assert modules
    for path in modules:
        for node in ast.walk(ast.parse(path.read_text())):
            names = [a.name for a in node.names] if isinstance(node, ast.Import) else (
                [node.module] if isinstance(node, ast.ImportFrom) else [])
            for name in names:
                assert name.split(".")[0] in ("__future__", "numpy", "harness"), (path, name)
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]);"
         "from pathlib import Path;"
         "from harness import bench, generator, manifest, metrics, reference, trace_reduce;"
         "reference.load_kind(Path(sys.argv[2]), 'sans_iq');"
         "print(sorted(m for m in sys.modules if m.split('.')[0] in ('jax', 'jaxlib', 'esslivedata_tpu')))",
         str(BENCH), str(FIXTURE)],
        capture_output=True, text=True, timeout=60,
    )
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "[]"


def test_a_kinds_least_bytes_join_the_one_roofline():
    """``roofline.least_seconds`` asks a kind's module for a job it does
    not know: the toy I(Q) by hand, 18 B an event and the fold and fetch
    of 100 Q bins a publish."""
    job = TOY_LOKI["jobs"][0]
    by_hand = 57_344 * 18 + 2 * (4 * 100 * 4 + 4 * (2 * 100 + 2))
    assert SANS_IQ.work_bytes(job, TOY_LOKI, 57_344, 2) == by_hand == 1_032_192 + 2 * 2_408
    assert roofline.least_seconds(TOY_LOKI, {"iq": 57_344}, {"iq": 2}, "TPU v5 lite",
                                  {"sans_iq": SANS_IQ}) == pytest.approx(by_hand / 819e9)
    with pytest.raises(KeyError):  # no module, no bytes: never a default
        roofline.least_seconds(TOY_LOKI, {"iq": 1}, {"iq": 1}, "TPU v5 lite")


def test_the_toy_iq_reference_equals_a_loop_over_events():
    """The kind's vectorised Q histogram against the formulae applied
    event by event, and its prefix total against its own counts."""
    pools, refs = _refs(TOY_LOKI, 5, IQ_MIX, {"sans_iq": SANS_IQ})
    ref, view = refs["iq"], TOY_LOKI["jobs"][0]["view"]
    (detector, (first, n_pixels)), (monitor, _) = pools
    period, q_lo, q_hi = 1e9 / 14, view["q"]["min"], view["q"]["max"]
    counts = np.zeros(view["q"]["bins"], np.int64)
    ids, toa = detector[2]
    for pixel_id, t in zip(ids[:600].tolist(), toa[:600].tolist()):
        pixel = pixel_id - first
        if not (0 <= pixel < n_pixels and 0 <= t < period):
            continue
        row, col = divmod(pixel, 256)
        x, y, z = -0.5 + col / 255, -0.5 + row / 255, 5.0
        theta = np.arctan2(np.hypot(x, y), z)
        centre_s = (int(t // (period / 200)) + 0.5) * (period / 200) * 1e-9
        q = 4 * np.pi * np.sin(theta / 2) / (3956.034 * centre_s / (23.0 + np.sqrt(x * x + y * y + z * z)))
        if q_lo <= q < q_hi:
            counts[int((q - q_lo) / (q_hi - q_lo) * 100)] += 1
    vectorised = SANS_IQ.q_histograms(
        TOY_LOKI["jobs"][0], TOY_LOKI, [([(ids[:600], toa[:600])], (first, n_pixels)), (monitor, (0, 0))])
    assert np.array_equal(vectorised[0], counts) and 400 < counts.sum() <= 600
    assert [ids.size for ids, _ in monitor] == [0] * IQ_MIX.pool_pulses  # TOA only
    assert ref.expected("monitor_counts_current", 0, 14) == 14 * 512  # an eighth of 4096, probes and all
    assert ref.counts(0, 23) == ref.expected("counts_q_current", 0, 23).sum()
    total = float(sum(ref.expected("counts_q_current", lo, lo + 14).sum() for lo in (0, 14, 28)))
    assert ref.prefix_of(total, 500) == (42, 0.0)
    assert np.allclose(ref.expected("iq_cumulative", 0, 42) * 42 * 512,
                       ref.expected("counts_q_current", 0, 42), rtol=1e-12)


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

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
from harness import bench, generator, manifest, reference, results, roofline
from harness.traffic import FramePool, Traffic, pulse_time_ns

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
TOY_ODIN = json.loads((FIXTURE / "configs" / "toy_odin.json").read_text())
FRAMES = reference.load_kind(BENCH, "frames")
CAMERA_KINDS = {"frames": FRAMES}
CAMERA_MIX = Traffic.from_dict(json.loads((FIXTURE / "traffic" / "toy_camera.json").read_text()))
CAMERA_LIMITS = json.loads((FIXTURE / "limits" / "toy_odin.toy_camera.json").read_text())["limits"]
#: name -> (configuration, traffic mix, its reference kinds, the limits of its cell)
CASES = {
    "dream_banks": (DREAM, MIX, {}, LIMITS),
    "nmx_panels": (NMX, MIX, {}, LIMITS),
    "toy_loki": (TOY_LOKI, IQ_MIX, {"sans_iq": SANS_IQ}, IQ_LIMITS),
    "toy_odin": (TOY_ODIN, CAMERA_MIX, CAMERA_KINDS, CAMERA_LIMITS),
}


def _sha(*arrays) -> str:
    digest = hashlib.sha256()
    for array in arrays:
        array = np.ascontiguousarray(array)
        digest.update(str(array.dtype).encode() + str(array.shape).encode() + array.tobytes())
    return digest.hexdigest()


PINNED = json.loads((FIXTURE / "pins_parent.json").read_text())
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
#: What a configuration's pins hold: its pools, the wire bytes of its first
#: pulse and of its whole pool, its reference's spectra, arrays and image,
#: and the roofline's least time; a configuration has those of its outputs.
PINNED_PARTS = ("pools", "first_pulse", "wire", "spectra", "arrays", "image", "least_seconds")


@pytest.fixture(scope="module")
def pinned_tables():
    """Pools, generators and references of every accepted configuration,
    each under its cell's traffic mix at the pins' reduced rate."""
    out = {}
    for name, pins in PINNED["pins"].items():
        entry = next(c for c in MANIFEST["configs"] if c["name"] == name)
        config = json.loads((REPO / entry["file"]).read_text())
        mix = Traffic.from_dict({**json.loads((BENCH / "traffic" / f"{pins['traffic']}.json").read_text()),
                                 "events_per_pulse": PINNED["events_per_pulse"]})
        kinds = {job["view"]["kind"]: reference.load_kind(BENCH, job["view"]["kind"])
                 for job in config["jobs"] if job["view"]["kind"] not in reference.VIEW_KINDS}
        pools = reference.make_pools(config, mix, PINNED["seed"])
        with tempfile.TemporaryDirectory() as tmp:
            source = generator.Generator({
                "seed": PINNED["seed"], "traffic": mix.__dict__,
                "streams": bench.streams_with_topics(config), "broker_dir": tmp, "log_path": f"{tmp}/log",
            })
            source.producer.close()
        out[name] = (config, source, kinds, reference.build(config, mix, pools, kinds), pools)
    return out


@pytest.mark.parametrize(
    "name, what",
    [(name, what) for name in sorted(PINNED["pins"]) for what in PINNED_PARTS if what in PINNED["pins"][name]],
    ids=lambda value: value,
)
def test_nothing_moved_in_the_accepted_configurations(pinned_tables, name, what):
    """Pools, the generator's first pulse and whole pool of messages, the
    reference's tables and the roofline's least time of every accepted
    configuration, each equal to what the harness gave before camera
    streams came (NMX and DREAM: since commit 7e4ce74, the parent of the
    PR that made kinds, streams and outputs pluggable): a stream with no
    new key yields the bytes it yielded, a view the numbers it had."""
    config, source, kinds, refs, pools = pinned_tables[name]
    pins = PINNED["pins"][name]
    outputs = {job["name"]: reference.compared_classes(config, job) for job in config["jobs"]}
    if what == "pools":
        assert {
            stream["name"]: _sha(*[a for pulse in pool for a in pulse], np.asarray(span))
            for stream, (pool, span) in zip(config["streams"], pools)
        } == pins["pools"]
    elif what == "first_pulse":
        assert {topic for topic, _ in source.templates[0]} == {
            s["topic"] for s in bench.streams_with_topics(config)}
        digest = hashlib.sha256()
        for index, (_topic, template) in enumerate(source.templates[0]):
            digest.update(bytes(template.stamp(index, pulse_time_ns(1000))))
        assert digest.hexdigest() == pins["first_pulse"]
        assert {len(messages) for messages in source.templates} == {pins["messages_per_pulse"]}
    elif what == "wire":
        digest = hashlib.sha256()
        for entry, messages in enumerate(source.templates):
            for index, (topic, template) in enumerate(messages):
                digest.update(topic.encode() + bytes(template.stamp(index, pulse_time_ns(1000 + entry))))
        assert digest.hexdigest() == pins["wire"]
    elif what == "spectra":
        assert {
            job: _sha(ref.expected("spectrum_cumulative", 0, 27),
                      ref.expected("spectrum_current", 14, 28), ref.per_pulse)
            for job, ref in refs.items() if "spectrum_cumulative" in outputs[job]
        } == pins["spectra"]
    elif what == "arrays":
        assert {
            job: _sha(*[ref.expected(o, *ref.span(o, 14, 28))
                        for o, c in outputs[job].items() if c == "arrays"], ref.per_pulse)
            for job, ref in refs.items() if "arrays" in outputs[job].values()
        } == pins["arrays"]
    elif what == "image":
        assert {job: _sha(refs[job].expected("image_current", 3, 30))
                for job in pins["image"]} == pins["image"]
    else:
        batches = PINNED["batches"]  # of fixture/trace_dream_ticks.json
        assert json.loads((FIXTURE / "trace_dream_ticks.json").read_text())["expected"]["batches"] == batches
        jobs = [j["name"] for j in config["jobs"]]
        least = roofline.least_seconds(config, dict.fromkeys(jobs, batches * 14 * 229376),
                                       dict.fromkeys(jobs, batches), "TPU v5 lite", kinds)
        assert repr(least) == pins["least_seconds"]


def test_the_pins_are_of_accepted_configurations_under_their_cells_traffic():
    """Six, taken on the parent of the PR that added camera streams; a
    configuration added later brings no pins and is not asked for any."""
    assert len(PINNED["pins"]) == 6
    for name, pins in PINNED["pins"].items():
        assert any(w["config"] == name and w["traffic"] == pins["traffic"] for w in MANIFEST["workloads"])


def test_every_cell_has_the_same_exact_limits():
    """Every cell's limits are its comparison's checks, each at NMX's
    limit where NMX has that check and exact (0) where it is another
    kind's count (a camera's ``frame_bins_wrong``), so that a cell that
    adds a camera brings its limits as a file."""
    for path in (BENCH / "limits").glob("*.json"):
        cell = manifest.load_cell(REPO, path.stem)
        checks = reference.check_names(cell.config, cell.kinds)
        assert json.loads(path.read_text())["limits"] == {name: LIMITS.get(name, 0) for name in checks}


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


def _refs(config, seed=3, mix=MIX, kinds=CAMERA_KINDS):
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
    if config is TOY_ODIN:  # two frame sums a publish, each bin exact
        assert numbers["compared"] == {"spectra": 0, "images": 0, "arrays": 2 * 12}
        assert set(numbers["frame_bins_wrong"]) == {"value", "limit"}
    elif kinds:
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


#: (case, fault): every case under the faults ``control.py`` runs for it (the pools' where it
#: has a detector stream, its kinds' own); the toy LOKI, since its I(Q) is a float32 quotient,
#: also computed in the precision below.
CONTROLS = [
    *((case, fault) for case in sorted(CASES) for fault in reference.controls(CASES[case][0], CASES[case][2])),
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
    if config is TOY_ODIN:
        assert numbers["frame_bins_wrong"]["value"] >= 1
    elif not kinds:
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


#: A camera stream beside the toy panel: what a configuration of an instrument that
#: has both (ODIN: the Timepix3 and the Orca) gives the harness.
TOY_PANEL = json.loads((FIXTURE / "configs" / "toy_panel.json").read_text())
CAMERA = {"name": "orca", "kind": "camera", "wire_source": "odin_orca", "topic": "odin_camera",
          "frame_shape": [6, 10], "dtype": "uint16"}
FRAMES_JOB = {"name": "camera", "workflow": ["detector_view", "camera_view"], "job_source": "orca",
              "stream": "orca", "params": {}, "outputs": {"arrays": ["current", "cumulative"]},
              "prefix_total": "cumulative", "view": {"kind": "frames"}}
BOTH = {**TOY_PANEL, "streams": [*TOY_PANEL["streams"], CAMERA], "jobs": [*TOY_PANEL["jobs"], FRAMES_JOB]}
PANEL_MIX = Traffic.from_dict(json.loads((FIXTURE / "traffic" / "toy_blob.json").read_text()))


@pytest.mark.parametrize("camera", [{}, {"camera_frames_per_pulse": 3}, {"camera_pulses_per_frame": 5}])
def test_a_camera_stream_leaves_an_event_streams_expected_outputs_untouched(camera):
    """The panel's pool, its reference's spectra and images and the pools'
    faults are what they are without the camera; the frames job reads
    the camera's frames alone, and the pools' faults leave them as they are."""
    mix = Traffic.from_dict({**PANEL_MIX.__dict__, **camera})
    alone_pools, alone = _refs(TOY_PANEL, 7, mix)
    pools, refs = _refs(BOTH, 7, mix)
    assert _sha(*[a for pulse in pools[0][0] for a in pulse]) == _sha(*[a for pulse in alone_pools[0][0] for a in pulse])
    for lo, hi in ((0, 14), (3, 30), (14, 28)):
        for output in ("spectrum_current", "image_current"):
            assert np.array_equal(refs["panel_view"].expected(output, lo, hi),
                                  alone["panel_view"].expected(output, lo, hi))
    frames = pools[1][0]
    assert isinstance(frames, FramePool) and pools[1][1] == (0, 0)
    window = sum(f.astype(np.float64) for pulse in range(14, 28) for f in frames[pulse % len(frames)])
    assert np.array_equal(refs["camera"].expected("current", 14, 28), window)
    for fault in reference.FAULTS:
        broken = reference.break_guarantee(pools, fault)
        assert broken[1][0] is frames
        assert not np.array_equal(
            reference.build(BOTH, mix, broken, CAMERA_KINDS)["panel_view"].expected("spectrum_current", 0, 14),
            refs["panel_view"].expected("spectrum_current", 0, 14))
    assert reference.controls(BOTH, CAMERA_KINDS) == (
        *reference.FAULTS, "frames.frame_dropped", "frames.frame_twice", "frames.frame_transposed",
        "frames.frame_uint8")
    assert reference.controls(TOY_ODIN, CAMERA_KINDS) == (
        reference.controls(BOTH, CAMERA_KINDS)[len(reference.FAULTS):])


@pytest.mark.parametrize("params", [{}, {"transpose": True}, {"flip_y": True, "flip_x": True},
                                    {"transpose": True, "flip_y": True, "flip_x": True}])
def test_the_frames_reference_equals_a_loop_over_frames(params):
    """``current`` sums the frames since the previous publish and
    ``cumulative`` all of them, frame by frame as the generator sends them,
    as the camera view shows them: transposed, then flipped in y, then in x."""
    mix = Traffic.from_dict({**CAMERA_MIX.__dict__, "camera_frames_per_pulse": 2})
    config = {**BOTH, "jobs": [{**FRAMES_JOB, "params": params}]}
    pools, refs = _refs(config, 9, mix)
    ref, frames = refs["camera"], pools[1][0]
    assert [len(frames[e]) for e in range(len(frames))] == [2] * mix.pool_pulses

    def loop(lo, hi):
        image = np.zeros((6, 10), np.int64)
        for pulse in range(lo, hi):
            for frame in frames[pulse % mix.pool_pulses]:
                image += frame
        if params.get("transpose"):
            image = image.T
        if params.get("flip_y"):
            image = image[::-1]
        if params.get("flip_x"):
            image = image[:, ::-1]
        return image

    for previous, prefix in ((0, 14), (14, 28), (28, 37)):
        assert ref.span("current", previous, prefix) == (previous, prefix)
        assert ref.span("cumulative", previous, prefix) == (0, prefix)
        assert np.array_equal(ref.expected("current", previous, prefix), loop(previous, prefix))
        assert np.array_equal(ref.expected("cumulative", 0, prefix), loop(0, prefix))
        assert ref.counts(0, prefix) == loop(0, prefix).sum()
    with pytest.raises(KeyError):
        ref.expected("image_current", 0, 14)


@pytest.mark.parametrize("camera", [{}, {"camera_frames_per_pulse": 2}, {"camera_pulses_per_frame": 5}])
def test_prefix_of_recovers_every_prefix_of_a_cycled_frame_pool(camera):
    """Every frame's total differs from every other's, so the sum of a
    publish's cumulative finds its pulse prefix over several turns of the
    pool; where a camera skips pulses, the prefix found holds the same
    frames as the one summed (it ends at the last frame's pulse)."""
    mix = Traffic.from_dict({**CAMERA_MIX.__dict__, **camera})
    pools, refs = _refs(TOY_ODIN, 11, mix)
    ref, frames = refs["camera"], pools[0][0]
    totals = [int(f.sum(dtype=np.int64)) for f in frames.frames]
    assert len(set(totals)) == len(totals) == len(frames.frames)
    for n in range(1, 4 * mix.pool_pulses + 3):
        total = float(ref.expected("cumulative", 0, n).sum())
        prefix, off = ref.prefix_of(total, 200)
        assert off == 0.0 and ref.counts(0, prefix) == total
        if mix.camera_pulses_per_frame == 1:
            assert prefix == n
        else:
            assert prefix <= n and (prefix - 1) % mix.camera_pulses_per_frame == 0


def test_the_frames_kinds_least_bytes_join_the_one_roofline():
    """Per frame its bytes in (2 B a pixel of uint16) and 16 B a pixel for
    the two states, per publish the fold's four passes and the two
    images fetched: by hand for 64 x 64 pixels, 140 frames, 10 publishes."""
    job, pixels = TOY_ODIN["jobs"][0], 64 * 64
    by_hand = 140 * (2 * pixels + 16 * pixels) + 10 * (16 * pixels + 8 * pixels)
    assert FRAMES.work_bytes(job, TOY_ODIN, 140, 10) == by_hand == 11_304_960
    assert roofline.least_seconds(TOY_ODIN, {"camera": 140}, {"camera": 10}, "TPU v5 lite",
                                  CAMERA_KINDS) == pytest.approx(by_hand / 819e9)
    assert reference.check_names(TOY_ODIN, CAMERA_KINDS) == ["frame_bins_wrong", "prefix_off_pulses"]


@pytest.mark.parametrize("fault", sorted(FRAMES.faults()))
def test_each_frames_fault_breaks_one_frame_of_the_pools_first_pulse(fault):
    mix = Traffic.from_dict({**CAMERA_MIX.__dict__, "camera_frames_per_pulse": 2})
    pools, refs = _refs(TOY_ODIN, 13, mix)
    broken = reference.build(TOY_ODIN, mix, pools, CAMERA_KINDS, f"frames.{fault}")["camera"]
    first = pools[0][0][0][0].astype(np.float64)
    moved = broken.expected("current", 0, 1) - refs["camera"].expected("current", 0, 1)
    want = {"frame_dropped": -first, "frame_twice": first, "frame_transposed": first.T - first,
            "frame_uint8": first % 256 - first}[fault]
    assert np.array_equal(moved, FRAMES.shown(want, TOY_ODIN["jobs"][0]["params"]))
    assert np.count_nonzero(moved) > 0.9 * moved.size or fault == "frame_uint8"
    assert np.count_nonzero(moved) > 0.1 * moved.size  # the beam spot, an eighth of it, passes a byte
    assert np.array_equal(broken.expected("current", 1, 5), refs["camera"].expected("current", 1, 5))

"""The seeded traffic and the two wire schemas of the client side, each
held against the program's own codec (the harness imports none of it)."""

from __future__ import annotations

import json

import numpy as np
import pytest
from bench_support import FIXTURE, REPO
from harness import traffic, wire
from harness.traffic import Traffic, make_pool

PACED = json.loads((REPO / "benchmark" / "traffic" / "paced14.json").read_text())
TOY = json.loads((FIXTURE / "traffic" / "toy_paced.json").read_text())
PERIOD = 1e9 / 14


def small(**changes) -> Traffic:
    return Traffic.from_dict(
        {**PACED, "events_per_pulse": 4096, "out_of_range_probes": 128, **changes}
    )


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3_000_000_001])
def test_the_same_seed_gives_the_same_pool_and_another_seed_another(seed):
    mix = small()
    a = make_pool(seed, 0, 1, 4096, mix)
    b = make_pool(seed, 0, 1, 4096, mix)
    c = make_pool(seed + 1, 0, 1, 4096, mix)
    other_stream = make_pool(seed, 1, 1, 4096, mix)
    assert len(a) == mix.pool_pulses
    for (ids, toa), (ids2, toa2) in zip(a, b):
        assert ids.dtype == toa.dtype == np.int32
        assert np.array_equal(ids, ids2) and np.array_equal(toa, toa2)
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][0], other_stream[0][0])


@pytest.mark.parametrize("dist", ["blob", "uniform", "hotspot"])
def test_every_pulse_has_the_same_in_range_count_and_the_stated_out_of_range_probes(dist):
    mix = small(pixel_dist=dist, hotspot_share=0.5, hotspot_pixels=7)
    first, n_pixels = 1001, 4096
    counted = []
    for ids, toa in make_pool(5, 0, first, n_pixels, mix):
        bad_id = (ids < first) | (ids >= first + n_pixels)
        bad_toa = (toa < 0) | (toa >= PERIOD)
        assert bad_id.sum() == bad_toa.sum() == mix.out_of_range_probes == 128
        assert not (bad_id & bad_toa).any()
        counted.append(int((~bad_id & ~bad_toa).sum()))
        # inside the middle half of a TOA bin: float32 and float64 binning agree
        frac = (toa[~bad_toa] / (PERIOD / 100)) % 1
        assert frac.min() > 0.2 and frac.max() < 0.8
    assert len(set(counted)) == 1


def test_hotspot_puts_its_share_on_a_few_pixels():
    mix = small(pixel_dist="hotspot", hotspot_share=0.5, hotspot_pixels=7)
    ids = np.concatenate([p[0] for p in make_pool(3, 0, 1, 4096, mix)])
    ids = ids[(ids >= 1) & (ids <= 4096)]
    top = np.sort(np.bincount(ids))[::-1]
    assert 0.45 < top[:7].sum() / ids.size < 0.56
    uniform = np.concatenate([p[0] for p in make_pool(3, 0, 1, 4096, small(pixel_dist="uniform"))])
    assert np.sort(np.bincount(uniform[uniform > 0]))[::-1][:7].sum() / uniform.size < 0.02


def test_blob_is_the_dev_stack_producers_wrapping_gaussian():
    """sigma n/8 around a centre that swings 0.5 +- 0.4 of the id space
    over one turn of the pool, wrapped and never clipped onto an edge."""
    n_pixels, first = 1 << 16, 101
    mix = Traffic.from_dict({**PACED, "events_per_pulse": 1 << 16, "out_of_range_probes": 0,
                             "pool_pulses": 8})
    assert mix.pixel_dist == "blob" and mix.blob_sigma_share == 0.125 and mix.blob_swing == 0.4
    centres = []
    for entry, (ids, _toa) in enumerate(make_pool(4, 0, first, n_pixels, mix)):
        assert ids.min() >= first and ids.max() < first + n_pixels
        want = (0.5 + 0.4 * np.sin(2 * np.pi * entry / 8)) * n_pixels
        turn = np.exp(2j * np.pi * (ids - first) / n_pixels)
        centre = (np.angle(turn.mean()) % (2 * np.pi)) / (2 * np.pi) * n_pixels
        assert abs(centre - want) < 0.01 * n_pixels
        # a wrapped normal of sigma s has a mean resultant of exp(-(2 pi s)**2 / 2)
        assert abs(abs(turn.mean()) - np.exp(-((2 * np.pi / 8) ** 2) / 2)) < 0.01
        edge = np.bincount(ids - first, minlength=n_pixels)[[0, -1]].sum()
        assert edge < 20  # clipping would pile the tails onto the edge pixels
        centres.append(want)
    assert max(centres) > 0.85 * n_pixels and min(centres) < 0.15 * n_pixels


def test_pulse_times_sit_on_the_programs_grid():
    from esslivedata_tpu.core.timestamp import Timestamp

    for index in (0, 1, 13, 14, 24_000_000_123):
        ns = traffic.pulse_time_ns(index)
        assert Timestamp.from_ns(ns).pulse_index() == index
        assert Timestamp.from_ns(ns - 1).pulse_index() == index - 1


def test_ev44_templates_decode_in_the_programs_codec_with_fresh_stamps():
    from esslivedata_tpu.kafka.wire import decode_ev44

    ids, toa = make_pool(9, 0, 1, 4096, small())[0]
    template = wire.Ev44Template("panel_a", toa, ids)
    for message_id, stamp in ((0, 1_700_000_000_000_000_000), (2**40, 1_800_000_000_123_456_789)):
        got = decode_ev44(bytes(template.stamp(message_id, stamp)))
        assert got.source_name == "panel_a" and got.message_id == message_id
        assert got.reference_time.tolist() == [stamp]
        assert got.reference_time_index.tolist() == [0]
        assert np.array_equal(got.pixel_id, ids) and np.array_equal(got.time_of_flight, toa)


def test_messages_per_pulse_splits_a_pulse_and_loses_nothing():
    mix = Traffic.from_dict(TOY)
    assert mix.messages_per_pulse == 4
    chunk = mix.events_per_pulse // 4
    ids, toa = make_pool(11, 0, 1, 4096, mix)[2]
    parts = [wire.Ev44Template("panel_a", toa[k * chunk:(k + 1) * chunk],
                               ids[k * chunk:(k + 1) * chunk]) for k in range(4)]
    from esslivedata_tpu.kafka.wire import decode_ev44

    back = [decode_ev44(bytes(p.stamp(k, 10**18))) for k, p in enumerate(parts)]
    assert np.array_equal(np.concatenate([m.pixel_id for m in back]), ids)
    assert np.array_equal(np.concatenate([m.time_of_flight for m in back]), toa)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32, np.int64])
def test_da00_of_the_programs_encoder_reads_back(dtype):
    from esslivedata_tpu.kafka.wire import Da00Variable, encode_da00

    image = np.arange(12, dtype=dtype).reshape(3, 4)
    raw = encode_da00(
        "wid|src|job-1|image_current",
        123456789,
        [
            Da00Variable("signal", "counts", ("y", "x"), image),
            Da00Variable("y", "", ("y",), np.arange(4, dtype=np.float64)),
            Da00Variable("total", "counts", (), np.asarray(7.0, dtype=np.float32)),
        ],
    )
    source, stamp, variables = wire.decode_da00(raw)
    assert source == "wid|src|job-1|image_current" and stamp == 123456789
    assert variables["signal"].dtype == dtype and np.array_equal(variables["signal"], image)
    assert variables["total"].shape == () and float(variables["total"]) == 7.0
    with pytest.raises(ValueError):
        wire.decode_da00(b"\x00" * 4 + b"ev44" + b"\x00" * 16)

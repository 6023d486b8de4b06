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


@pytest.mark.parametrize("seconds", [3, 10, 51])
def test_a_paced_window_holds_its_pulses_by_due_time(seconds):
    """A window of whole seconds holds 14 pulses a second by their due
    times, however late it closes within a pulse period: the base windows
    a run attempts do not hang on when the pulses were sent."""
    mix = small()
    for late_ns in (0, 1, 70_000_000):
        span = seconds * 10**9 + late_ns
        due = mix.pulses_due(span)
        assert [mix.due_ns(k) < span for k in range(due + 2)] == [True] * due + [False] * 2
        assert due // 14 == seconds
        assert mix.due_ns(due - 1) == int((due - 1) * PERIOD)


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


CAMERA_TOY = json.loads((FIXTURE / "traffic" / "toy_camera.json").read_text())


def camera_mix(**changes) -> Traffic:
    return Traffic.from_dict({**CAMERA_TOY, **changes})


@pytest.mark.parametrize("dtype", ["uint16", "float32"])
def test_ad00_templates_decode_in_the_programs_codec_with_fresh_stamps(dtype):
    """The harness's ad00 reads back in the program's decoder, with the
    stamps patched in place, the type and the shape intact (a frame of
    6 x 10, not square, so that the axes cannot be swapped unseen)."""
    from esslivedata_tpu.kafka.wire import decode_ad00

    frame = traffic.make_frame_pool(5, 0, (6, 10), dtype, camera_mix()).frames[0]
    assert frame.dtype == np.dtype(dtype)
    template = wire.Ad00Template("odin_orca", frame)
    for frame_id, stamp in ((0, 1_700_000_000_000_000_000), (2**40, 1_800_000_000_123_456_789)):
        got = decode_ad00(bytes(template.stamp(frame_id, stamp)))
        assert got.source_name == "odin_orca" and got.timestamp_ns == stamp
        assert got.data.dtype == np.dtype(dtype) and got.data.shape == (6, 10)
        assert np.array_equal(got.data, frame)
        assert bytes(template.buf[4:8]) == b"ad00"
    # ad00's own DType enum: uint16 is 3 and float32 8 (da00 would say 4 and 9)
    assert traffic.AD00_DTYPES.index("uint16") == 3 and traffic.AD00_DTYPES.index("float32") == 8


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 3_000_000_001])
def test_the_same_seed_gives_the_same_frame_pool_and_another_seed_another(seed):
    mix = camera_mix(camera_frames_per_pulse=2)
    a = traffic.make_frame_pool(seed, 1, (16, 24), "uint16", mix)
    b = traffic.make_frame_pool(seed, 1, (16, 24), "uint16", mix)
    c = traffic.make_frame_pool(seed + 1, 1, (16, 24), "uint16", mix)
    other_stream = traffic.make_frame_pool(seed, 2, (16, 24), "uint16", mix)
    assert a.frames.shape == (2 * mix.pool_pulses, 16, 24) and len(a) == mix.pool_pulses
    assert np.array_equal(a.frames, b.frames) and a.entries == b.entries
    assert not np.array_equal(a.frames, c.frames)
    assert not np.array_equal(a.frames, other_stream.frames)
    # the camera's draws are keyed apart from the events': a stream index shared takes nothing
    assert np.array_equal(make_pool(seed, 1, 1, 4096, small())[0][0],
                          make_pool(seed, 1, 1, 4096, small())[0][0])


def test_a_frame_is_a_flat_field_with_a_brighter_spot_clipped_to_its_type():
    mix = camera_mix(camera_mean_counts=50.0)
    frames = traffic.make_frame_pool(3, 0, (64, 64), "uint16", mix).frames.astype(np.float64)
    y, x = np.ogrid[:64, :64]
    spot = np.hypot(y - 31.5, x - 31.5) <= 0.2 * 64
    assert abs(frames[:, ~spot].mean() - 50) < 1 and abs(frames[:, spot].mean() - 8 * 50) < 4
    clipped = traffic.make_frame_pool(3, 0, (64, 64), "uint8", mix).frames
    assert clipped.dtype == np.uint8 and clipped.max() == 255  # the spot's ~400 counts, clipped
    totals = clipped.reshape(len(clipped), -1).sum(axis=1, dtype=np.int64)
    assert len(set(totals.tolist())) == len(totals)  # distinct, however the clip ties them
    saturated = traffic.make_frame_pool(3, 0, (4, 4), "uint8", camera_mix(camera_mean_counts=400.0)).frames
    assert sorted(saturated.reshape(len(saturated), -1).sum(axis=1).tolist()) == list(range(4076, 4081))
    with pytest.raises(ValueError, match="no ad00 type"):
        traffic.make_frame_pool(3, 0, (8, 8), "float16", mix)


@pytest.mark.parametrize("frames, pulses, entries", [
    (1, 1, [(0,), (1,), (2,), (3,), (4,)]),
    (3, 1, [(0, 1, 2), (3, 4, 5), (6, 7, 8), (9, 10, 11), (12, 13, 14)]),
    (1, 5, [(0,), (), (), (), ()]),
])
def test_a_camera_sends_its_frames_per_pulse_or_one_every_few_pulses(frames, pulses, entries):
    mix = camera_mix(camera_frames_per_pulse=frames, camera_pulses_per_frame=pulses)
    assert mix.frames_per_pulse() == frames / pulses
    pool = traffic.make_frame_pool(1, 0, (4, 4), "uint16", mix)
    assert [tuple(e) for e in pool.entries] == entries
    assert [len(pool[e]) for e in range(len(pool))] == [len(e) for e in entries]


@pytest.mark.parametrize("bad", [
    {"camera_frames_per_pulse": 2, "camera_pulses_per_frame": 5},
    {"camera_frames_per_pulse": 0},
    {"camera_pulses_per_frame": 1.5},
    {"camera_pulses_per_frame": 2},  # does not divide pool_pulses 5
    {"camera_mean_counts": 0},
    {"camera_gain": 3},
])
def test_camera_keys_of_a_traffic_mix_are_checked(bad):
    with pytest.raises(ValueError):
        camera_mix(**bad)
    # every mix of the benchmark states none of them and keeps the defaults
    for path in (REPO / "benchmark" / "traffic").glob("*.json"):
        mix = Traffic.from_dict(json.loads(path.read_text()))
        assert (mix.camera_frames_per_pulse, mix.camera_pulses_per_frame) == (1, 1)


@pytest.mark.parametrize("frames, pulses", [(2, 1), (1, 5)])
def test_a_pulses_frames_go_after_its_events_on_the_cameras_topic_with_its_stamp(tmp_path, frames, pulses):
    from esslivedata_tpu.kafka.wire import decode_ad00, decode_ev44
    from harness.broker import Consumer, ensure_topics
    from harness.generator import Generator

    mix = Traffic.from_dict({**TOY, "pool_pulses": 5, "camera_frames_per_pulse": frames,
                             "camera_pulses_per_frame": pulses})
    panel = {"name": "panel_0", "topic": "dummy_detector", "wire_source": "panel_a", "first_id": 1,
             "n_pixels": 4096}
    camera = {"name": "orca", "kind": "camera", "wire_source": "odin_orca", "topic": "odin_camera",
              "frame_shape": [8, 12], "dtype": "uint16"}
    ensure_topics(tmp_path, ["dummy_detector", "odin_camera"])
    source = Generator({"seed": 3, "traffic": mix.__dict__, "streams": [camera, panel],
                        "broker_dir": str(tmp_path), "log_path": str(tmp_path / "log")})
    per_entry = [len(m) for m in source.templates]
    assert per_entry == [4 + (frames if e % pulses == 0 else 0) for e in range(5)]
    for entry in range(5):  # the events first, as without the camera; then the frames
        topics = [topic for topic, _ in source.templates[entry]]
        assert topics == ["dummy_detector"] * 4 + ["odin_camera"] * (per_entry[entry] - 4)
    for _ in range(6):
        source.send_pulse(None)
    source.producer.close()
    camera_log = Consumer(tmp_path, "odin_camera").poll(100)
    events = Consumer(tmp_path, "dummy_detector").poll(100)
    assert len(events) == 24 and len(camera_log) == sum(per_entry[e % 5] for e in range(6)) - 24
    stamps = [decode_ev44(raw).reference_time[0] for raw in events[::4]]
    frames_got = [decode_ad00(raw) for raw in camera_log]
    want = [stamps[k] for k in range(6) for _ in range(per_entry[k % 5] - 4)]
    assert [f.timestamp_ns for f in frames_got] == want
    assert source.producer.bytes_written == sum(8 + len(raw) for raw in camera_log + events)
    # the panel's messages are the bytes they are without the camera
    (tmp_path / "two").mkdir()
    alone, both = (
        Generator({"seed": 3, "traffic": mix.__dict__, "streams": streams,
                   "broker_dir": str(tmp_path / "two"), "log_path": str(tmp_path / "log2")})
        for streams in ([panel], [panel, camera])
    )
    for entry in range(5):
        assert [bytes(t.buf) for _, t in alone.templates[entry]] == [
            bytes(t.buf) for topic, t in both.templates[entry] if topic == "dummy_detector"]
    alone.producer.close()
    both.producer.close()

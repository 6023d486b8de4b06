"""The camera view against a plain numpy reference (``camera_frames_reference``):
a camera's frames summed exactly in uint32 on the device (ADR 0132),
shown as the view's parameters say, restarted when the frame's
structure changes; and the path, the span and the counters beside it."""

from __future__ import annotations

import itertools
import logging

import jax.numpy as jnp
import numpy as np
import pytest
from camera_frames_reference import camera_frames, publishes

from esslivedata_tpu.ops import frames as frame_ops
from esslivedata_tpu.telemetry.instruments import (
    CAMERA_FRAME_BYTES,
    CAMERA_FRAMES,
)
from esslivedata_tpu.telemetry.trace import TRACER
from esslivedata_tpu.utils.labeled import DataArray, Variable
from esslivedata_tpu.workflows.area_detector_view import (
    AreaDetectorParams,
    AreaDetectorView,
)

TRANSFORMS = list(itertools.product((False, True), repeat=3))


def as_message(frame: np.ndarray) -> DataArray:
    """A frame as the ad00 adapter hands it over: a read-only view."""
    frame = frame.copy()
    frame.flags.writeable = False
    return DataArray(Variable(frame, ("y", "x"), "counts"), name="odin_orca")


def run_view(windows, **params):
    """Feed each window's frames as the preprocessor hands them over (one
    list a window) and publish once a window."""
    view = AreaDetectorView(params=AreaDetectorParams(**params))
    out = []
    for frames in windows:
        view.accumulate({"orca_camera": [as_message(f) for f in frames]})
        got = view.finalize()
        out.append((got["current"], got["cumulative"]))
    return out


def assert_published_as_reference(got, expected):
    assert len(got) == len(expected)
    for (current, cumulative), (want_current, want_cumulative) in zip(
        got, expected, strict=True
    ):
        for image, want in ((current, want_current), (cumulative, want_cumulative)):
            values = np.asarray(image.values)
            assert values.dtype == np.uint32
            assert values.flags.c_contiguous
            assert image.dims == ("y", "x") and values.shape == want.shape
            np.testing.assert_array_equal(values.astype(np.float64), want)


def test_seeded_frames_at_64_by_64_are_summed_as_the_reference_sums_them():
    rng = np.random.default_rng(4201)
    windows = [camera_frames(rng, n, (64, 64), 100.0) for n in (14, 5, 9, 14)]
    assert_published_as_reference(run_view(windows), publishes(windows))


@pytest.mark.parametrize(
    "transpose, flip_y, flip_x",
    TRANSFORMS,
    ids=["".join(name for name, on in zip(("T", "Y", "X"), t) if on) or "none" for t in TRANSFORMS],
)
def test_every_transform_shows_the_sums_as_the_reference_does(transpose, flip_y, flip_x):
    """A frame of 48 x 64 (not square, so that a transpose shows), every
    combination of the three parameters."""
    rng = np.random.default_rng(4202)
    windows = [camera_frames(rng, n, (48, 64), 200.0) for n in (3, 4)]
    params = {"transpose": transpose, "flip_y": flip_y, "flip_x": flip_x}
    got = run_view(windows, **params)
    assert_published_as_reference(got, publishes(windows, **params))
    assert got[0][0].shape == ((64, 48) if transpose else (48, 64))


def test_a_window_that_wraps_uint16_is_summed_exactly():
    """3 000 counts a pixel, 24 000 in the beam spot, 14 frames a window:
    the spot's pixels pass 65 535 within every window, where a sum in the
    frame's own uint16 wraps, and the flat field's within two."""
    rng = np.random.default_rng(4203)
    windows = [camera_frames(rng, 14, (64, 64), 3000.0) for _ in range(2)]
    expected = publishes(windows)
    top = np.iinfo(np.uint16).max
    spot = expected[0][0] > top
    assert 0.1 < spot.mean() < 0.2 and expected[0][0].max() > 14 * 23_000
    assert expected[1][1].min() > top
    wrapped = np.zeros((64, 64), np.uint16)
    for frame in windows[0]:
        wrapped += frame
    assert np.array_equal(wrapped != expected[0][0], spot)  # what a uint16 sum publishes
    assert_published_as_reference(run_view(windows), expected)


def test_a_structure_change_mid_run_restarts_both_images():
    """The camera's ROI changes between two frames of one window: both
    images restart at the new shape, and the frames before it are gone
    from both; a later window of the new shape sums on."""
    rng = np.random.default_rng(4204)
    before = camera_frames(rng, 6, (64, 64), 300.0)
    after = camera_frames(rng, 8, (32, 48), 300.0)
    windows = [before, before[:2] + after[:3], after[3:]]
    got = run_view(windows)
    assert_published_as_reference(got, publishes(windows))
    assert got[1][1].shape == (32, 48)
    assert np.asarray(got[2][1].values).sum() == sum(int(f.sum()) for f in after)


def test_a_window_without_frames_publishes_a_zero_current_and_the_cumulative():
    rng = np.random.default_rng(4205)
    windows = [camera_frames(rng, 4, (16, 16), 50.0), []]
    assert_published_as_reference(run_view(windows), publishes(windows))


def test_a_view_with_no_frame_publishes_nothing_and_clear_restarts():
    view = AreaDetectorView()
    assert view.finalize() == {}
    frame = camera_frames(np.random.default_rng(4206), 1, (8, 8), 10.0)[0]
    view.accumulate({"orca_camera": [as_message(frame)]})
    view.clear()
    assert view.finalize() == {}
    view.accumulate({"orca_camera": as_message(frame)})  # one frame, not a list
    np.testing.assert_array_equal(view.finalize()["cumulative"].values, frame)


def test_each_frame_is_counted_by_its_path_and_spanned():
    def counts():
        return (
            CAMERA_FRAMES.value(path="device"),
            CAMERA_FRAMES.value(path="host"),
            CAMERA_FRAME_BYTES.value(),
        )

    spans_before = sum(s.name == "frame" for s in TRACER.spans())
    device, host, nbytes = counts()
    rng = np.random.default_rng(4207)
    frames = camera_frames(rng, 3, (16, 8), 20.0)
    view = AreaDetectorView()
    trace = TRACER.new_trace()
    with TRACER.bind(trace):
        view.accumulate({"orca_camera": [as_message(f) for f in frames]})
    assert counts() == (device + 3, host, nbytes + 3 * 16 * 8 * 2)
    frame_spans = [s for s in TRACER.spans(trace) if s.name == "frame"]
    assert len(frame_spans) == 3 and frame_spans[0].args == {"bytes": 16 * 8 * 2}
    assert sum(s.name == "frame" for s in TRACER.spans()) == spans_before + 3
    view.accumulate({"orca_camera": [as_message(frames[0].astype(np.float32))]})
    assert counts()[:2] == (device + 3, host + 1)


@pytest.mark.parametrize(
    "dtype, path, summed",
    [
        (np.uint8, "device", np.uint32),
        (np.uint16, "device", np.uint32),
        (np.int16, "host", np.int64),
        (np.uint32, "host", np.int64),
        (np.int64, "host", np.int64),
        (np.uint64, "host", np.uint64),
        (np.float32, "host", np.float64),
        (np.float64, "host", np.float64),
    ],
)
def test_each_ad00_type_is_summed_in_a_type_that_holds_it(dtype, path, summed):
    assert frame_ops.on_device(dtype) == (path == "device")
    frames = [np.full((4, 6), 7, dtype), np.full((4, 6), 9, dtype)]
    view = AreaDetectorView()
    view.accumulate({"orca_camera": [as_message(f) for f in frames]})
    cumulative = np.asarray(view.finalize()["cumulative"].values)
    assert cumulative.dtype == summed
    np.testing.assert_array_equal(cumulative, np.full((4, 6), 16))


def test_a_type_of_the_other_path_restarts_the_sums():
    view = AreaDetectorView()
    view.accumulate({"orca_camera": [as_message(np.full((4, 4), 3, np.uint16))]})
    view.accumulate({"orca_camera": [as_message(np.full((4, 4), 5, np.int32))]})
    cumulative = np.asarray(view.finalize()["cumulative"].values)
    assert cumulative.dtype == np.int64 and cumulative.sum() == 5 * 16
    view.accumulate({"orca_camera": [as_message(np.full((4, 4), 2, np.uint8))]})
    view.accumulate({"orca_camera": [as_message(np.full((4, 4), 1, np.uint16))]})
    cumulative = np.asarray(view.finalize()["cumulative"].values)
    assert cumulative.dtype == np.uint32 and cumulative.sum() == 3 * 16  # uint8 and uint16 share a sum


def test_uint32_holds_78_minutes_of_the_brightest_uint16_pixel():
    frames = frame_ops.UINT32_MAX // int(np.iinfo(np.uint16).max)
    assert frames == 65_537
    assert 78 <= frames / 14 / 60 < 79
    assert frames * 65_535 <= frame_ops.UINT32_MAX < (frames + 1) * 65_535


def test_a_sum_past_uint32_saturates_and_warns_and_never_wraps(caplog):
    frame = np.full((4, 4), 65_535, np.uint16)
    sums = frame_ops.FrameSums(frame, "counts")
    near_top = frame_ops.UINT32_MAX - 100_000
    sums._cumulative = jnp.full((4, 4), near_top, jnp.uint32)
    sums.add(frame)
    with caplog.at_level(logging.WARNING, logger="esslivedata_tpu.ops.frames"):
        current, cumulative = sums.publish()
    np.testing.assert_array_equal(current, frame.astype(np.uint32))
    np.testing.assert_array_equal(cumulative, near_top + 65_535)
    assert not caplog.records
    sums.add(frame)
    with caplog.at_level(logging.WARNING, logger="esslivedata_tpu.ops.frames"):
        _, cumulative = sums.publish()
        sums.add(frame)
        _, again = sums.publish()
    np.testing.assert_array_equal(cumulative, frame_ops.UINT32_MAX)
    np.testing.assert_array_equal(again, frame_ops.UINT32_MAX)
    assert len(caplog.records) == 1 and "saturated" in caplog.records[0].getMessage()


def test_the_device_add_saturates_the_window_too():
    window = jnp.full((2, 3), frame_ops.UINT32_MAX - 10, jnp.uint32)
    frame = jnp.asarray(np.array([[0, 10, 11], [65_535, 5, 1]], np.uint16))
    got = np.asarray(frame_ops._add(window, frame))
    top = frame_ops.UINT32_MAX
    np.testing.assert_array_equal(got, [[top - 10, top, top], [top, top - 5, top - 9]])


def test_the_published_uint32_images_render_on_the_dashboard():
    from esslivedata_tpu.dashboard.plots import render_png

    rng = np.random.default_rng(4208)
    (current, cumulative), = run_view([camera_frames(rng, 14, (32, 48), 3000.0)])
    for image in (current, cumulative):
        assert render_png(image, title=image.name)[:4] == b"\x89PNG"

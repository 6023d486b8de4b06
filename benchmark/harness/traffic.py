"""Seeded traffic: one general generator of pulse pools, driven by a
traffic file's parameters and a configuration's streams.

A pool is ``pool_pulses`` pulses per stream, made once from the seed and
cycled: pulse ``k`` of a run carries pool entry ``k % pool_pulses`` of
every stream, stamped with its own time. The reference sums pool
entries, the generator process encodes them; both call ``make_pool``.

The shape of a pulse is the one the package's dev-stack producer sends
(``services/fake_sources.py:FakeDetectorStream``, the mirror of
upstream's ``services/fake_detectors.py``): one ev44 message per pulse
per source, pixel ids from a Gaussian blob of sigma ``n_pixels / 8``
that wraps over the id space and whose centre swings ``0.5 +- 0.4`` of
it, TOA uniform over the pulse period. The pool turns the producer's
slow swing (``sin(pulse / 50)``) into one turn of the sine per turn of
the pool, so that every second of a run holds the same mix.

A ``camera`` stream sends ad00 frames instead (upstream's
``services/fake_detectors.py:FakeAreaDetectorSource``): its pool is a
``FramePool`` of seeded frames, ``camera_frames_per_pulse`` of them on
every pulse or one on every ``camera_pulses_per_frame``-th.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

PULSE_HZ_GRID = 14  # the data-time grid of the program (core/constants.py)
#: ad00's ``DType`` (schemas/ad00_area_detector_array.fbs), by code:
#: int8 = 0 .. float64 = 9. Not da00's order.
AD00_DTYPES = (
    "int8", "uint8", "int16", "uint16", "int32", "uint32", "int64", "uint64", "float32", "float64",
)
#: The beam spot of a camera frame: a disc of this share of the shorter
#: side as its radius, at the frame's centre, this many times as bright.
SPOT_RADIUS_SHARE = 0.2
SPOT_GAIN = 8.0


def pulse_time_ns(index: int) -> int:
    """Smallest ns whose pulse index on the 14 Hz grid is ``index``."""
    return -((-index * 10**9) // PULSE_HZ_GRID)


def pulse_period_ns() -> float:
    return 1e9 / PULSE_HZ_GRID


@dataclass(frozen=True)
class Traffic:
    """Parameters of one traffic mix (``benchmark/traffic/<name>.json``)."""

    pulse_hz: float  # open loop: pulse k is due at start + due_ns(k)
    events_per_pulse: int  # per stream
    pixel_dist: str = "blob"  # or "uniform", "hotspot"
    blob_sigma_share: float = 0.125  # blob: sigma as a share of the id space
    blob_swing: float = 0.4  # blob: the centre swings 0.5 +- this share
    hotspot_share: float = 0.0  # share of in-range events on the hot pixels
    hotspot_pixels: int = 0
    out_of_range_probes: int = 0  # ids, and as many TOAs, out of range in a pulse
    messages_per_pulse: int = 1
    pool_pulses: int = 14
    toa_bins: int = 100
    camera_frames_per_pulse: int = 1  # ad00 frames each pulse, per camera stream
    camera_pulses_per_frame: int = 1  # or one frame on every this-many pulses
    camera_mean_counts: float = 100.0  # of a pixel of the flat field

    @classmethod
    def from_dict(cls, doc: dict) -> "Traffic":
        known = {k: doc[k] for k in cls.__dataclass_fields__ if k in doc}
        unknown = set(doc) - set(cls.__dataclass_fields__) - {"name", "why", "sources"}
        if unknown:
            raise ValueError(f"traffic file has unknown keys {sorted(unknown)}")
        traffic = cls(**known)
        if traffic.pixel_dist not in ("blob", "uniform", "hotspot"):
            raise ValueError(f"pixel_dist {traffic.pixel_dist!r}")
        if traffic.pixel_dist == "hotspot" and not (
            0 < traffic.hotspot_share <= 1 and traffic.hotspot_pixels > 0
        ):
            raise ValueError("hotspot needs hotspot_share and hotspot_pixels")
        if traffic.events_per_pulse % traffic.messages_per_pulse:
            raise ValueError("messages_per_pulse must divide events_per_pulse")
        if not 0 <= 2 * traffic.out_of_range_probes <= traffic.events_per_pulse:
            raise ValueError("out_of_range_probes does not fit in a pulse")
        per_pulse, per_frame = traffic.camera_frames_per_pulse, traffic.camera_pulses_per_frame
        if not (isinstance(per_pulse, int) and isinstance(per_frame, int)
                and per_pulse >= 1 and per_frame >= 1 and min(per_pulse, per_frame) == 1):
            raise ValueError("camera_frames_per_pulse and camera_pulses_per_frame are whole "
                             "numbers of at least 1, and at most one of them is above 1")
        if traffic.pool_pulses % per_frame:
            raise ValueError("camera_pulses_per_frame must divide pool_pulses")
        if not traffic.camera_mean_counts > 0:
            raise ValueError("camera_mean_counts must be above 0")
        return traffic

    def frames_per_pulse(self) -> float:
        """ad00 frames a camera stream sends a pulse, on average."""
        return self.camera_frames_per_pulse / self.camera_pulses_per_frame

    def due_ns(self, k: int) -> int:
        """How long after a paced run's start its pulse ``k`` is due."""
        return int(k * (1e9 / self.pulse_hz))

    def pulses_due(self, span_ns: int) -> int:
        """How many pulses of a paced run are due in its first ``span_ns``."""
        k = 0
        while self.due_ns(k) < span_ns:
            k += 1
        return k


STREAM_KINDS = ("detector", "monitor", "camera")


def stream_events(stream: dict, traffic: Traffic) -> int:
    """Events in one pulse of ``stream``: its ``rate_share`` (1 where
    the configuration gives none) of the mix's ``events_per_pulse``, a
    whole number that ``messages_per_pulse`` divides and that holds the
    pulse's out-of-range probes."""
    if "rate_share" not in stream:
        return traffic.events_per_pulse
    events = stream["rate_share"] * traffic.events_per_pulse
    if not (
        events > 0
        and events == int(events)
        and int(events) % traffic.messages_per_pulse == 0
        and 2 * traffic.out_of_range_probes <= events
    ):
        raise ValueError(
            f"stream {stream['name']}: rate_share {stream['rate_share']} of "
            f"{traffic.events_per_pulse} events is no multiple of "
            f"{traffic.messages_per_pulse} messages a pulse that holds the probes"
        )
    return int(events)


def stream_pool(seed: int, stream_index: int, stream: dict, traffic: Traffic):
    """``make_pool`` for one stream of a configuration, at the stream's
    own size. A ``monitor`` stream is ev44 with TOA only (as
    ``services/fake_sources.py:FakeMonitorStream`` sends one): the same
    draws in the same order, the ids left out. A ``camera`` stream's
    pool is ``make_frame_pool``'s."""
    kind = stream.get("kind", "detector")
    if kind not in STREAM_KINDS:
        raise ValueError(f"stream {stream['name']}: kind {kind!r}")
    if kind == "camera":
        return make_frame_pool(seed, stream_index, tuple(stream["frame_shape"]), stream["dtype"],
                               traffic)
    own = replace(traffic, events_per_pulse=stream_events(stream, traffic))
    pool = make_pool(
        seed, stream_index, stream.get("first_id", 0), stream.get("n_pixels", 1), own
    )
    if kind == "monitor":
        pool = [(ids[:0], toa) for ids, toa in pool]
    return pool


def make_pool(seed: int, stream_index: int, first_id: int, n_pixels: int, traffic: Traffic):
    """``pool_pulses`` pulses of (ids int32, toa int32) for one stream.

    Ids: the producer's wrapping blob (see the module's text), or
    uniform over the stream's pixels, or a ``hotspot_share`` of them on
    ``hotspot_pixels`` pixels drawn from the seed. TOA: inside the
    middle half of a uniformly drawn bin, so float32 and float64 binning
    agree. ``out_of_range_probes`` ids at the front of a pulse (zero,
    negative, just below, just above, a detector further) and as many
    TOAs at its end (negative, past the frame) are out of range: a few
    events that weigh nothing in the work and let the comparison see a
    clip. Every pulse therefore has the same number of in-range events.
    """
    rng = np.random.default_rng([int(seed), int(stream_index), 0x6C697665])
    n = traffic.events_per_pulse
    bad = traffic.out_of_range_probes
    width = pulse_period_ns() / traffic.toa_bins
    hot = None
    if traffic.pixel_dist == "hotspot":
        hot = rng.choice(n_pixels, min(traffic.hotspot_pixels, n_pixels), replace=False)
    pool = []
    for entry in range(traffic.pool_pulses):
        if traffic.pixel_dist == "blob":
            turn = 2 * np.pi * entry / traffic.pool_pulses
            centre = (0.5 + traffic.blob_swing * np.sin(turn)) * n_pixels
            spread = rng.normal(centre, traffic.blob_sigma_share * n_pixels, n)
            ids = first_id + np.floor(spread).astype(np.int64) % n_pixels
        else:
            ids = rng.integers(first_id, first_id + n_pixels, n, dtype=np.int64)
        if hot is not None:
            on_hot = rng.random(n) < traffic.hotspot_share
            ids[on_hot] = first_id + rng.choice(hot, int(on_hot.sum()))
        if bad:
            ids[:bad] = rng.choice(
                [0, -7, first_id - 1, first_id + n_pixels, first_id + 2 * n_pixels], bad
            )
        toa = (rng.integers(0, traffic.toa_bins, n) + rng.uniform(0.25, 0.75, n)) * width
        if bad:
            toa[n - bad :] = rng.choice(
                [-5.0e5, pulse_period_ns() + 1.0e4, 9.0e7], bad
            )
        pool.append((ids.astype(np.int32), toa.astype(np.int32)))
    return pool


@dataclass(frozen=True)
class FramePool:
    """A camera stream's pool: ``frames`` [n, ny, nx] in the stream's
    ad00 type, and for each pool entry (a pulse) the frames it sends, in
    order. Entry ``e`` as a list of frames is ``pool[e]``."""

    frames: np.ndarray
    entries: tuple[tuple[int, ...], ...]

    def __len__(self) -> int:
        return len(self.entries)

    def __getitem__(self, entry: int) -> list[np.ndarray]:
        return [self.frames[i] for i in self.entries[entry]]


def make_frame_pool(seed: int, stream_index: int, shape: tuple[int, int], dtype: str,
                    traffic: Traffic) -> FramePool:
    """The frames of one camera stream: Poisson counts of mean
    ``camera_mean_counts`` over a flat field, ``SPOT_GAIN`` times that on
    a disc at the centre (the beam spot), clipped to ``dtype``'s range.
    Every frame's total differs from every other's (a pixel of the
    corner is moved one count at a time, away from the type's top, while
    it ties), so that a prefix of a cycled pool can be told from its
    sum. Drawn from a key of their own: no event stream's draws change."""
    if dtype not in AD00_DTYPES:
        raise ValueError(f"camera dtype {dtype!r} is no ad00 type")
    rng = np.random.default_rng([int(seed), int(stream_index), 0x63616D65])
    ny, nx = shape
    y, x = np.ogrid[:ny, :nx]
    spot = np.hypot(y - (ny - 1) / 2, x - (nx - 1) / 2) <= SPOT_RADIUS_SHARE * min(ny, nx)
    mean = traffic.camera_mean_counts * np.where(spot, SPOT_GAIN, 1.0)
    per_pulse, per_frame = traffic.camera_frames_per_pulse, traffic.camera_pulses_per_frame
    entries = tuple(
        tuple(range(e * per_pulse, (e + 1) * per_pulse)) if per_frame == 1
        else ((e // per_frame,) if e % per_frame == 0 else ())
        for e in range(traffic.pool_pulses)
    )
    n = sum(map(len, entries))
    kind = np.dtype(dtype)
    top = np.iinfo(kind).max if kind.kind in "iu" else np.inf
    frames = np.empty((n, ny, nx), kind)
    seen = set()
    for k in range(n):
        frame = np.minimum(rng.poisson(mean), top)
        total = int(frame.sum())
        step = 1 if frame[0, 0] + n < top else -1
        while total in seen:
            frame[0, 0] += step
            total += step
        seen.add(total)
        frames[k] = frame
    return FramePool(frames, entries)

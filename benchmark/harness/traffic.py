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
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import numpy as np

PULSE_HZ_GRID = 14  # the data-time grid of the program (core/constants.py)


def pulse_time_ns(index: int) -> int:
    """Smallest ns whose pulse index on the 14 Hz grid is ``index``."""
    return -((-index * 10**9) // PULSE_HZ_GRID)


def pulse_period_ns() -> float:
    return 1e9 / PULSE_HZ_GRID


@dataclass(frozen=True)
class Traffic:
    """Parameters of one traffic mix (``benchmark/traffic/<name>.json``)."""

    pulse_hz: float  # open loop: pulse k is due at start + k / pulse_hz
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
        return traffic


STREAM_KINDS = ("detector", "monitor")


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
    draws in the same order, the ids left out."""
    kind = stream.get("kind", "detector")
    if kind not in STREAM_KINDS:
        raise ValueError(f"stream {stream['name']}: kind {kind!r}")
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

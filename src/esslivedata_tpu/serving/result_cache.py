"""Host-side result cache: the broadcast tier's source of truth.

One entry per (job, output) stream holds the latest published da00
frame plus a bounded ring of recent ticks — the ADR 0113 static-output
host cache generalized from "layout-constant leaves, stored once per
digest" to "every output, stored once per publish tick". Subscribers
never touch the compute loop: an attach (or a slow consumer's resync)
is served a keyframe from here, so N dashboards cost the publish path
exactly zero extra device work (ROADMAP open item 3).

Epoch discipline: ``put`` takes an opaque ``token`` describing the
frame's generation — the serving plane builds it from the output's
structural layout (variable names/shapes/dtypes/axes) and the job's
``state_epoch`` (core/job.py: bumped on clear/reset and on a
``state_lost`` buffer-donation failure). A token change bumps the
stream's integer epoch, which forces the delta encoder onto a keyframe
and tells subscribers the accumulation restarted (a delta across
epochs would splice unrelated state generations).

Locking: ONE lock, ONE acquisition per operation.
``latest`` returns frame, epoch and seq
from the same critical section, so a scraping subscriber can never pair
a frame with the wrong epoch tag (pinned by the lock hammer in
tests/serving/result_cache_test.py); ``put`` is a dict store + deque
append under that lock — O(1), no encoding, nothing that could extend
the publish critical path.
"""

from __future__ import annotations

import threading
from collections import deque
from collections.abc import Hashable
from dataclasses import dataclass

__all__ = ["CachedFrame", "ResultCache"]


@dataclass(frozen=True, slots=True)
class CachedFrame:
    """One coherent (frame, epoch, seq) snapshot."""

    frame: bytes
    epoch: int
    seq: int


class _Entry:
    __slots__ = ("token", "epoch", "seq", "ring")

    def __init__(self, ring: int) -> None:
        self.token: Hashable = None
        self.epoch = -1
        self.seq = -1
        self.ring: deque[CachedFrame] = deque(maxlen=ring)


class ResultCache:
    """Latest frame + bounded recent ring per (job, output) stream."""

    def __init__(self, *, ring: int = 8) -> None:
        if ring < 1:
            raise ValueError("ring must hold at least the latest frame")
        self._ring = int(ring)
        self._lock = threading.Lock()
        self._entries: dict[str, _Entry] = {}

    def put(
        self, stream: str, frame: bytes, token: Hashable
    ) -> CachedFrame:
        """Record one published frame; returns its coherent
        (frame, epoch, seq) tag. A ``token`` differing from the
        previous put's bumps the epoch (and the ring resets — frames
        across a generation boundary must not look contiguous)."""
        with self._lock:
            entry = self._entries.get(stream)
            if entry is None:
                entry = self._entries[stream] = _Entry(self._ring)
            if entry.epoch < 0 or entry.token != token:
                entry.epoch += 1
                entry.token = token
                entry.ring.clear()
            entry.seq += 1
            cached = CachedFrame(frame, entry.epoch, entry.seq)
            entry.ring.append(cached)
            return cached

    def latest(self, stream: str) -> CachedFrame | None:
        """The newest frame with ITS epoch and seq — one acquisition,
        so the triple is always self-consistent."""
        with self._lock:
            entry = self._entries.get(stream)
            if entry is None or not entry.ring:
                return None
            return entry.ring[-1]

    def recent(self, stream: str) -> list[CachedFrame]:
        """The bounded ring, oldest first (current epoch only — the
        ring resets on epoch bumps)."""
        with self._lock:
            entry = self._entries.get(stream)
            return [] if entry is None else list(entry.ring)

    def streams(self) -> dict[str, CachedFrame]:
        """stream -> latest snapshot, for the /results index."""
        with self._lock:
            return {
                stream: entry.ring[-1]
                for stream, entry in self._entries.items()
                if entry.ring
            }

    def invalidate(self, stream: str | None = None) -> None:
        """Drop one stream's entry (or all) — a removed job's outputs
        must not serve stale keyframes forever."""
        with self._lock:
            if stream is None:
                self._entries.clear()
            else:
                self._entries.pop(stream, None)

"""Event staging: ev44 chunks -> fixed-shape padded device batches.

TPU-native equivalent of the reference's ``to_nxevent_data.py`` +
``group_by_pixel.py``: the reference builds a scipp binned array (events
binned by pulse) and then groups by detector_number so workflows can
histogram; here the accumulator only *stages* raw event arrays into a
reusable padded host buffer (ops/event_batch.StagingBuffer) — the jitted
scatter kernel does projection+grouping+binning in one pass on device. The
zero-copy / release_buffers contract is the same as the reference's
(_buffers_in_use guard, to_nxevent_data.py:166-171).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, ClassVar

import numpy as np

from ..core.timestamp import Timestamp
from ..ops.event_batch import (
    EventBatch,
    bucket_size,
    make_staging_buffer,
    sanitize_pixel_id,
)
from ..telemetry.trace import TRACER

__all__ = [
    "DetectorEvents",
    "EventChunkRef",
    "MonitorEvents",
    "StagedEvents",
    "ToEventBatch",
]


@dataclass(frozen=True, slots=True)
class MonitorEvents:
    """Decoded ev44 monitor chunk: times of arrival only (the fast-path
    adapter skips pixel ids, reference message_adapter.py:360)."""

    time_of_arrival: np.ndarray  # ns within pulse

    @property
    def n_events(self) -> int:
        return int(self.time_of_arrival.shape[0])


@dataclass(frozen=True, slots=True)
class DetectorEvents:
    """Decoded ev44 detector chunk: pixel ids + times of arrival."""

    pixel_id: np.ndarray
    time_of_arrival: np.ndarray

    @property
    def n_events(self) -> int:
        return int(self.pixel_id.shape[0])


@dataclass(frozen=True, slots=True)
class EventChunkRef:
    """Lazy event chunk: a wire header view instead of decoded arrays.

    The batch decode plane's adapted-message payload (ADR 0125): wraps a
    ``kafka.wire.Ev44View`` (duck-typed — n_tof/n_pid counts, zero-copy
    ``time_of_flight``/``pixel_id`` properties, ``fill_into``) so the
    adapter allocates NO per-message ndarrays; the payload bytes are
    read exactly once, when the accumulator lands the whole window into
    a decode arena. ``monitor`` carries the adapter's routing decision:
    a monitor chunk zero-fills pixel ids whatever the wire holds (the
    reference's pixel-less monitor semantics).

    The ``pixel_id``/``time_of_arrival`` properties materialize arrays
    with the same dtypes the eager adapters produced — the compatibility
    surface for consumers outside the ref-mode accumulator.
    """

    view: Any  # kafka.wire.Ev44View (duck-typed; no kafka import here)
    monitor: bool = False

    @property
    def n_events(self) -> int:
        return int(self.view.n_tof)

    @property
    def pixel_id(self) -> np.ndarray:
        if self.monitor:
            return np.zeros(self.view.n_tof, dtype=np.int32)
        return self.view.pixel_id

    @property
    def time_of_arrival(self) -> np.ndarray:
        return self.view.time_of_flight.astype(np.float32)

    def fill_into(self, pid_dst: np.ndarray, toa_dst: np.ndarray) -> None:
        """Land the payload into arena slices of length ``n_events``
        (int32→float32 toa cast fused into the assignment)."""
        if self.monitor:
            toa_dst[:] = self.view.time_of_flight
            pid_dst[:] = 0
        else:
            self.view.fill_into(pid_dst, toa_dst)


@dataclass(frozen=True, slots=True)
class _ArrayChunk:
    """Eager arrays adopted into a ref-mode window (mixed producers):
    pays the per-message host sanitize the eager path always paid."""

    pixel_id: np.ndarray
    time_of_arrival: np.ndarray

    @property
    def n_events(self) -> int:
        return int(np.asarray(self.time_of_arrival).shape[0])

    def fill_into(self, pid_dst: np.ndarray, toa_dst: np.ndarray) -> None:
        pid_dst[:] = sanitize_pixel_id(self.pixel_id)
        toa_dst[:] = self.time_of_arrival


@dataclass(slots=True)
class StagedEvents:
    """One window's worth of staged events, ready for the device kernel."""

    batch: EventBatch
    first_timestamp: Timestamp | None
    last_timestamp: Timestamp | None
    n_chunks: int
    #: Window stream-cache slot (core/device_event_cache.StreamStageSlot),
    #: attached by the JobManager before fan-out: workflows thread it into
    #: their kernels so K jobs sharing this stream stage the batch once.
    #: None outside the managed path (tests, direct workflow use).
    cache: object | None = None

    @property
    def n_events(self) -> int:
        return self.batch.n_valid

    def detach(self) -> StagedEvents:
        """A copy owning its event arrays (see ``EventBatch.detach``) —
        the pipelined hand-off form; the cache slot is dropped (the
        pipeline's stage worker attaches the next window generation's)."""
        return StagedEvents(
            batch=self.batch.detach(),
            first_timestamp=self.first_timestamp,
            last_timestamp=self.last_timestamp,
            n_chunks=self.n_chunks,
        )


class ToEventBatch:
    """Accumulator staging event chunks into one padded device batch.

    Accepts DetectorEvents or MonitorEvents (monitor events get pixel_id 0,
    so a monitor is screen row 0 of a 1-row histogram), plus the batch
    decode plane's :class:`EventChunkRef` (ADR 0125). A window whose
    FIRST chunk is a ref runs in **ref mode**: instead of appending
    decoded arrays into the staging buffer per message, the accumulator
    records (chunk, offset) bookkeeping only, and ``get()`` leases a
    decode arena and lands every payload straight off the wire in one
    sequential fill — no per-message ndarray, one copy total
    (wire → arena; ``stage_raw`` then device-puts the arena views and
    runs the device decode prologue). Eager chunks arriving mid-window
    are adopted (:class:`_ArrayChunk`), refs arriving into an eager
    window materialize through their array properties — either mix is
    byte-identical to the all-eager path.

    Landing a window's chunks into its one wire is observed as the
    aggregate span ``land``, once per ``get()``: the eager appends of
    the window (one copy a message, summed over ``add``) plus the pad
    to the bucket, or the one arena fill of a ref-mode window. It lies
    inside ``decode``, so it has no ring entry (telemetry/trace.py).
    """

    is_context: ClassVar[bool] = False

    def __init__(
        self, min_bucket: int | None = None, prefer_native: bool = True
    ) -> None:
        if min_bucket:
            self._buffer = make_staging_buffer(min_bucket, prefer_native)
        else:
            self._buffer = make_staging_buffer(prefer_native=prefer_native)
        self._min_bucket = min_bucket or 0
        self._first: Timestamp | None = None
        self._last: Timestamp | None = None
        self._n_chunks = 0
        #: Ref-mode window state: None = eager mode. The list holds
        #: fill_into-capable chunks in arrival order (message order is
        #: the arena order — part of the byte-identity contract).
        self._chunks: list | None = None
        self._ref_total = 0
        self._ref_taken = False
        #: Seconds the window's eager appends have taken so far.
        self._land_s = 0.0

    def add(
        self,
        timestamp: Timestamp,
        data: DetectorEvents | MonitorEvents | EventChunkRef,
    ) -> None:
        if self._ref_taken:
            raise RuntimeError(
                "ToEventBatch.add called before release_buffers() of the "
                "last ref-mode batch"
            )
        lazy = hasattr(data, "fill_into")
        if lazy and self._chunks is None and self._n_chunks == 0:
            self._chunks = []  # first chunk is a ref: ref-mode window
        if self._chunks is not None:
            if lazy:
                view = getattr(data, "view", None)
                if (
                    view is not None
                    and not data.monitor
                    and view.n_pid
                    and view.n_pid != view.n_tof
                ):
                    # Same containment point as the eager path's
                    # broadcast failure: raise at add(), the message
                    # preprocessor skips this message.
                    raise ValueError(
                        f"ev44 pixel_id length {view.n_pid} != "
                        f"time_of_flight length {view.n_tof}"
                    )
                self._chunks.append(data)
            else:
                if isinstance(data, MonitorEvents) or not hasattr(
                    data, "pixel_id"
                ):
                    pixel_id = np.zeros(
                        np.asarray(data.time_of_arrival).shape[0],
                        dtype=np.int32,
                    )
                else:
                    pixel_id = data.pixel_id
                self._chunks.append(
                    _ArrayChunk(
                        pixel_id=pixel_id,
                        time_of_arrival=data.time_of_arrival,
                    )
                )
            self._ref_total += self._chunks[-1].n_events
        else:
            # Eager mode. The staging buffer's own add() sanitizes pixel
            # ids (no-op pass for wire int32) and casts on assignment —
            # no defensive asarray/astype copies on this hot path.
            toa = data.time_of_arrival
            if isinstance(data, MonitorEvents) or not hasattr(
                data, "pixel_id"
            ):
                pixel_id = np.zeros(
                    np.asarray(toa).shape[0], dtype=np.int32
                )
            else:
                pixel_id = data.pixel_id
            began = time.perf_counter()
            self._buffer.add(pixel_id, toa)
            self._land_s += time.perf_counter() - began
        if self._first is None or timestamp < self._first:
            self._first = timestamp
        if self._last is None or timestamp > self._last:
            self._last = timestamp
        self._n_chunks += 1

    def _take_ref_batch(self) -> EventBatch:
        """Land every recorded chunk into a leased decode arena: the
        window's single contiguous (pixel, toa) pair, padded to the
        bucket boundary, owned by the arena lease (``detach`` is free).
        ``prologue=True`` defers pixel-id validation to the device
        decode prologue fused into ``stage_raw``."""
        from ..core.device_event_cache import default_decode_pool

        n = self._ref_total
        b = (
            bucket_size(n, self._min_bucket)
            if self._min_bucket
            else bucket_size(n)
        )
        lease = default_decode_pool().lease(b)
        pid = lease.pixel[:b]
        toa = lease.toa[:b]
        pos = 0
        for chunk in self._chunks:
            k = chunk.n_events
            chunk.fill_into(pid[pos : pos + k], toa[pos : pos + k])
            pos += k
        pid[n:b] = -1
        toa[n:b] = 0.0
        self._ref_taken = True
        return EventBatch(
            pixel_id=pid,
            toa=toa,
            n_valid=n,
            owner=lease,
            owned=True,
            prologue=True,
        )

    def get(self) -> StagedEvents:
        began = time.perf_counter()
        batch = (
            self._take_ref_batch()
            if self._chunks is not None
            else self._buffer.take()
        )
        TRACER.observe("land", self._land_s + time.perf_counter() - began)
        self._land_s = 0.0
        staged = StagedEvents(
            batch=batch,
            first_timestamp=self._first,
            last_timestamp=self._last,
            n_chunks=self._n_chunks,
        )
        return staged

    def clear(self) -> None:
        self._buffer.clear()
        self._chunks = None
        self._ref_total = 0
        self._ref_taken = False
        self._land_s = 0.0
        self._first = None
        self._last = None
        self._n_chunks = 0

    def release_buffers(self) -> None:
        self._buffer.release()
        self._chunks = None
        self._ref_total = 0
        self._ref_taken = False
        self._land_s = 0.0
        self._first = None
        self._last = None
        self._n_chunks = 0

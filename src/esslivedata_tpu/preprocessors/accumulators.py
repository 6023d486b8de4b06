"""Dense-data accumulators over labeled DataArrays.

Parity with reference ``preprocessors/accumulators.py``: ``Cumulative``
(+= with restart on structural mismatch, reference :238-261),
``LatestValueAccumulator`` (context, :57), ``NullAccumulator`` (:46).
The reference's NoCopyAccumulator exists to avoid deepcopying a 500 MB
histogram on every read (:96-97). That problem does not arise here *by
construction*: large histograms are device-resident kernel state with
fold semantics (ops/histogram.py — window and cumulative share one
scatter, reads are device views), and host-side accumulators only ever
hold the small dense outputs. ``Cumulative`` therefore defaults to
no-copy reads. A camera's ad00 frames are not summed here at all:
``WindowFrames`` keeps the window's frames as they were decoded, and
the view that reads them sums them on the device in a wider type
(``ops/frames.py``, ADR 0132).
"""

from __future__ import annotations

from typing import ClassVar

from ..core.timestamp import Timestamp
from ..utils.labeled import DataArray

__all__ = [
    "Cumulative",
    "LatestValueAccumulator",
    "NullAccumulator",
    "WindowFrames",
]


class NullAccumulator:
    """Swallows everything; for streams a service must consume but ignore."""

    is_context: ClassVar[bool] = False

    def add(self, timestamp: Timestamp, data: object) -> None:
        pass

    def get(self) -> None:
        return None

    def clear(self) -> None:
        pass

    def release_buffers(self) -> None:
        pass


class LatestValueAccumulator:
    """Keeps the most recent value — context streams (motor positions,
    chopper settings) that parameterize workflows. is_context=True gates
    job activation until a value exists (ADR 0002)."""

    is_context: ClassVar[bool] = True

    def __init__(self) -> None:
        self._value = None
        self._timestamp: Timestamp | None = None

    def add(self, timestamp: Timestamp, data: object) -> None:
        if self._timestamp is None or timestamp >= self._timestamp:
            self._value = data
            self._timestamp = timestamp

    @property
    def has_value(self) -> bool:
        return self._value is not None

    def get(self):
        if self._value is None:
            raise ValueError("LatestValueAccumulator is empty")
        return self._value

    def clear(self) -> None:
        self._value = None
        self._timestamp = None

    def release_buffers(self) -> None:
        pass


class Cumulative:
    """Running += of DataArrays, restarting when structure changes.

    A structural mismatch (different dims/shape/unit/coords — e.g. the
    upstream reconfigured its binning or an ad00 camera changed ROI) resets
    the accumulation to the new value instead of erroring, matching the
    reference's restart-on-mismatch behavior (accumulators.py:238-261).

    This subsumes the reference's ``reset_coord`` knob
    (NoCopyAccumulator:114-127): geometry is carried as coordinates
    (monitor position, detector transform), and ``same_structure`` compares
    coordinate *values* — so accumulation already restarts when the
    geometry moves, without naming the coord up front.

    ``clear_on_get`` gives window semantics (value since last read);
    otherwise since-start. Reads are no-copy by default: callers must not
    mutate the returned array (copy_on_get=True for defensive copies).
    """

    is_context: ClassVar[bool] = False

    def __init__(
        self, *, clear_on_get: bool = False, copy_on_get: bool = False
    ) -> None:
        self._clear_on_get = clear_on_get
        self._copy_on_get = copy_on_get
        self._value: DataArray | None = None

    def add(self, timestamp: Timestamp, data: DataArray) -> None:
        if self._value is not None and self._value.same_structure(data):
            self._value += data
        else:
            # restart: first value, or structure changed upstream (incl.
            # geometry coords — see class docstring)
            self._value = data.copy()

    @property
    def is_empty(self) -> bool:
        return self._value is None

    @property
    def current(self) -> DataArray | None:
        """No-copy peek at the accumulated value (None when empty);
        callers must not mutate it."""
        return self._value

    def get(self) -> DataArray:
        if self._value is None:
            raise ValueError("Cumulative accumulator is empty")
        value = self._value
        if self._copy_on_get:
            value = value.copy()
        if self._clear_on_get:
            self._value = None
        return value

    def clear(self) -> None:
        self._value = None

    def release_buffers(self) -> None:
        pass


class WindowFrames:
    """The window's ad00 frames in order of arrival, none summed.

    Summed here in the frame's own type, as :class:`Cumulative` would, a
    16-bit camera wraps past 65 535 counts a pixel a window. The view
    that reads the stream sums the frames itself, on the device and in
    uint32 (``workflows/area_detector_view.py``, ADR 0132), so this
    keeps each frame as the decoder gave it: a read-only view of its
    message, which nothing overwrites, so nothing is copied. ``get``
    hands the window's list over and starts the next.
    """

    is_context: ClassVar[bool] = False

    def __init__(self) -> None:
        self._frames: list[DataArray] = []

    def add(self, timestamp: Timestamp, data: DataArray) -> None:
        self._frames.append(data)

    def get(self) -> list[DataArray]:
        frames, self._frames = self._frames, []
        return frames

    def clear(self) -> None:
        self._frames = []

    def release_buffers(self) -> None:
        pass

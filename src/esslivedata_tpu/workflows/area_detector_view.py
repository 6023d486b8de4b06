"""Area-detector (camera) view: ad00 images with current+cumulative outputs
and an optional logical transform (reference: workflows/area_detector_view.py:22).

The frames are summed exactly, on the device and in uint32 where the
frame is an unsigned integer of at most 16 bits (``ops/frames.py``, ADR
0132): the detector service's preprocessor hands the window's frames
over one by one (``preprocessors.accumulators.WindowFrames``), and each
is staged and added as it is read here.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import numpy as np
from pydantic import BaseModel, ConfigDict

from ..ops.frames import FrameSums
from ..telemetry.instruments import CAMERA_FRAME_BYTES, CAMERA_FRAMES
from ..telemetry.trace import TRACER
from ..utils.labeled import DataArray, Variable

__all__ = ["AreaDetectorParams", "AreaDetectorView"]

_DEVICE_FRAMES = CAMERA_FRAMES.labels(path="device")
_HOST_FRAMES = CAMERA_FRAMES.labels(path="host")


class AreaDetectorParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    transpose: bool = False
    flip_y: bool = False
    flip_x: bool = False


class AreaDetectorView:
    """Sums 2-D camera frames into a window and a cumulative image. Both
    restart when a frame's structure changes (camera ROI reconfigured
    upstream, another unit, a type summed on the other path)."""

    def __init__(self, *, params: AreaDetectorParams | None = None) -> None:
        self._params = params or AreaDetectorParams()
        self._sums: FrameSums | None = None

    def accumulate(self, data: Mapping[str, Any]) -> None:
        for value in data.values():
            # A window's frames as the preprocessor keeps them, or one.
            frames = value if isinstance(value, list) else [value]
            for frame in frames:
                if isinstance(frame, DataArray) and frame.data.ndim == 2:
                    self._add(frame)

    def _add(self, frame: DataArray) -> None:
        """One frame's way on the host, in the span ``frame``: new sums
        where its structure changed, then the staging copy, the put and
        the dispatch of the add."""
        values = np.asarray(frame.values)
        with TRACER.span("frame", args={"bytes": values.nbytes}):
            unit = str(frame.unit)
            if self._sums is None or not self._sums.fits(values, unit):
                p = self._params
                self._sums = FrameSums(
                    values,
                    unit,
                    transpose=p.transpose,
                    flip_y=p.flip_y,
                    flip_x=p.flip_x,
                )
            self._sums.add(values)
        (_DEVICE_FRAMES if self._sums.device else _HOST_FRAMES).inc()
        CAMERA_FRAME_BYTES.inc(values.nbytes)

    def finalize(self) -> dict[str, DataArray]:
        if self._sums is None:
            return {}
        window, cumulative = self._sums.publish()
        unit = self._sums.unit
        return {
            "current": self._image(window, unit, "current"),
            "cumulative": self._image(cumulative, unit, "cumulative"),
        }

    @staticmethod
    def _image(values: np.ndarray, unit: str, name: str) -> DataArray:
        ny, nx = values.shape
        return DataArray(
            Variable(values, ("y", "x"), unit),
            coords={
                "y": Variable(np.arange(ny, dtype=np.float64), ("y",), ""),
                "x": Variable(np.arange(nx, dtype=np.float64), ("x",), ""),
            },
            name=name,
        )

    def clear(self) -> None:
        self._sums = None

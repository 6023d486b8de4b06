"""Detector ratemeter: counts in a selected analyzer arc + pixel range
(reference: bifrost/specs.py:350 detector_ratemeter, :59
DetectorRatemeterRegionParams).

The region — one analyzer arc (selected by its final energy) and a
pixel index range along it — precompiles into a pixel LUT mapping
selected pixels to one screen bin and everything else to drop, so the
streaming cost is the standard scatter kernel with n_screen=1 and one
TOA bin. Current/cumulative outputs carry the time coords the job layer
stamps on results, which the dashboard's Rate option divides by.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import numpy as np
from pydantic import BaseModel, ConfigDict, Field, model_validator

from ..config.models import TOARange
from ..ops.histogram import EventHistogrammer
from ..preprocessors.event_data import StagedEvents
from ..utils.labeled import DataArray, Variable

__all__ = ["RatemeterParams", "RatemeterWorkflow"]

#: Match tolerance when selecting an arc by final energy (meV).
_ARC_EF_TOL = 0.05


class RatemeterParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    # Arc selected by its analyzer final energy (BIFROST: 2.7, 3.2,
    # 3.8, 4.4 or 5.0 meV).
    arc_ef_mev: float = 5.0
    pixel_start: int = 0  # index along the arc (two_theta order)
    pixel_stop: int = 900
    # Accepted arrival window. It covers the wire's frame [0, 1/14 s)
    # (an ev44 TOA is relative to its own pulse) and, beyond it,
    # unwrapped flight times where a producer sends those.
    toa_range: TOARange = Field(
        default_factory=lambda: TOARange(low=0.0, high=4.0e8)
    )

    @model_validator(mode="after")
    def _range_valid(self) -> RatemeterParams:
        if self.pixel_start < 0:
            raise ValueError("pixel_start must be >= 0")
        if self.pixel_start >= self.pixel_stop:
            raise ValueError("pixel_start must be less than pixel_stop")
        return self


class RatemeterWorkflow:
    """Counts for a selected arc + pixel range, window and cumulative."""

    def __init__(
        self,
        *,
        two_theta: np.ndarray,
        ef_mev: np.ndarray,
        pixel_ids: np.ndarray,
        params: RatemeterParams | None = None,
        primary_stream: str | None = None,
    ) -> None:
        params = params or RatemeterParams()
        self._params = params
        ef = np.asarray(ef_mev, dtype=np.float64)
        ids = np.asarray(pixel_ids)
        on_arc = np.abs(ef - params.arc_ef_mev) <= _ARC_EF_TOL
        if not on_arc.any():
            levels = sorted({float(x) for x in np.round(ef, 2)})
            raise ValueError(
                f"no pixels on an arc at Ef = {params.arc_ef_mev} meV; "
                f"available levels: {levels}"
            )
        # Order the arc by scattering angle, then apply the index range.
        arc_ids = ids[on_arc][np.argsort(np.asarray(two_theta)[on_arc])]
        selected = arc_ids[params.pixel_start : params.pixel_stop]
        if selected.size == 0:
            raise ValueError(
                f"pixel range [{params.pixel_start}, {params.pixel_stop}) "
                f"is beyond the arc's {arc_ids.size} pixels"
            )
        lut = np.full((1, int(ids.max()) + 1), -1, dtype=np.int32)
        lut[0, selected] = 0
        self._n_region_pixels = int(selected.size)
        self._hist = EventHistogrammer(
            toa_edges=np.array([params.toa_range.low, params.toa_range.high]),
            n_screen=1,
            pixel_lut=lut,
        )
        self._state = self._hist.init_state()
        self._primary_stream = primary_stream

    @property
    def n_region_pixels(self) -> int:
        return self._n_region_pixels

    def accumulate(self, data: Mapping[str, Any]) -> None:
        for key, value in data.items():
            if isinstance(value, StagedEvents):
                if self._primary_stream is None or key == self._primary_stream:
                    # Stage-once (ADR 0110): K ratemeters on one stream
                    # share the window's staged batch by reference.
                    self._state = self._hist.step_batch(
                        self._state, value.batch, cache=value.cache
                    )

    def event_ingest(self, stream: str, staged: StagedEvents):
        """Fused-stepping offer (core/job_manager.py): same shape as the
        detector view — one histogrammer step per primary-stream batch."""
        if self._primary_stream is not None and stream != self._primary_stream:
            return None
        from ..core.device_event_cache import EventIngest

        def set_state(state) -> None:
            self._state = state

        return EventIngest(
            key=self._hist.fuse_key + ("",),
            hist=self._hist,
            batch=staged.batch,
            batch_tag="",
            get_state=lambda: self._state,
            set_state=set_state,
        )

    def finalize(self) -> dict[str, DataArray]:
        cum, win = self._hist.read(self._state)
        self._state = self._hist.clear_window(self._state)
        return {
            "detector_region_counts": DataArray(
                Variable(np.asarray(float(win.sum())), (), "counts"),
                name="detector_region_counts",
            ),
            "detector_region_counts_cumulative": DataArray(
                Variable(np.asarray(float(cum.sum())), (), "counts"),
                name="detector_region_counts_cumulative",
            ),
        }

    def clear(self) -> None:
        self._state = self._hist.clear(self._state)

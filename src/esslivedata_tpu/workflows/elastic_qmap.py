"""Elastic-line Q-space map (BIFROST; reference: bifrost/specs.py:376
elastic_qmap, :188 BifrostElasticQMapParams).

A 2-D map of scattering intensity over two selectable momentum-transfer
components (Qx/Qy/Qz) for quasi-elastic events. The TPU shape matches
the other reduction families: the component selection, bin edges AND
the elastic cut all precompile into one host-built (pixel, toa-bin) ->
flat-bin table (ops/qhistogram.build_elastic_q2d_map); streaming cost
is the same gather+scatter as every other family.
"""

from __future__ import annotations

from typing import Literal

import numpy as np
from pydantic import BaseModel, ConfigDict, Field, model_validator

from ..config.models import PULSE_PERIOD_NS, TOARange
from ..ops.qhistogram import QHistogrammer, build_elastic_q2d_map
from ..utils.labeled import DataArray, Variable
from .qshared import QStreamingMixin

__all__ = ["ElasticQAxis", "ElasticQMapParams", "ElasticQMapWorkflow"]


class ElasticQAxis(BaseModel):
    """One axis of the Q-space map: which component it spans + edges."""

    model_config = ConfigDict(frozen=True)

    component: Literal["Qx", "Qy", "Qz"]
    low: float = -3.0  # 1/angstrom
    high: float = 3.0
    bins: int = 100

    @model_validator(mode="after")
    def _ordered(self) -> ElasticQAxis:
        if self.high <= self.low:
            raise ValueError("axis range must satisfy low < high")
        return self

    def edges(self) -> np.ndarray:
        return np.linspace(self.low, self.high, self.bins + 1)


class ElasticQMapParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    axis1: ElasticQAxis = Field(
        default_factory=lambda: ElasticQAxis(component="Qx")
    )
    axis2: ElasticQAxis = Field(
        default_factory=lambda: ElasticQAxis(component="Qz")
    )
    e_window_mev: float = 0.25  # |Ei - Ef| accepted as elastic
    toa_bins: int = 320
    # The wire's frame and the frame offset that turns a TOA into a
    # flight time: see QESpectroscopyParams (qe_spectroscopy.py).
    toa_range: TOARange = Field(default_factory=TOARange)
    toa_offset_ns: float = 2 * PULSE_PERIOD_NS
    l1: float = 162.0  # m, moderator->sample

    @model_validator(mode="after")
    def _distinct_axes(self) -> ElasticQMapParams:
        if self.axis1.component == self.axis2.component:
            raise ValueError("axis1 and axis2 must span different components")
        if self.e_window_mev <= 0:
            raise ValueError("e_window_mev must be positive")
        return self


class ElasticQMapWorkflow(QStreamingMixin):
    """Detector events -> I(axis1, axis2) on the elastic line."""

    def __init__(
        self,
        *,
        two_theta: np.ndarray,
        azimuth: np.ndarray,
        ef_mev: np.ndarray,
        l2: np.ndarray,
        pixel_ids: np.ndarray,
        params: ElasticQMapParams | None = None,
        primary_stream: str | None = None,
        monitor_streams: set[str] | None = None,
    ) -> None:
        params = params or ElasticQMapParams()
        self._params = params
        a1, a2 = params.axis1, params.axis2
        e1, e2 = a1.edges(), a2.edges()
        toa_edges = np.linspace(
            params.toa_range.low, params.toa_range.high, params.toa_bins + 1
        )
        table = build_elastic_q2d_map(
            two_theta=two_theta,
            azimuth=azimuth,
            ef_mev=ef_mev,
            l2=l2,
            pixel_ids=pixel_ids,
            toa_edges=toa_edges,
            axis1=a1.component,
            axis1_edges=e1,
            axis2=a2.component,
            axis2_edges=e2,
            l1=params.l1,
            e_window_mev=params.e_window_mev,
            toa_offset_ns=params.toa_offset_ns,
        )
        self._n1, self._n2 = a1.bins, a2.bins
        self._hist = QHistogrammer(
            qmap=table, toa_edges=toa_edges, n_q=a1.bins * a2.bins, method="auto"
        )
        self._state = self._hist.init_state()
        self._a1_var = Variable(e1, (a1.component,), "1/angstrom")
        self._a2_var = Variable(e2, (a2.component,), "1/angstrom")
        self._dims = (a1.component, a2.component)
        self._primary_stream = primary_stream
        self._monitor_streams = monitor_streams or set()
        self._publish = None

    def _map2d(self, flat: np.ndarray, name: str, unit: str = "counts") -> DataArray:
        return DataArray(
            Variable(flat.reshape(self._n1, self._n2), self._dims, unit),
            coords={self._dims[0]: self._a1_var, self._dims[1]: self._a2_var},
            name=name,
        )

    def finalize(self) -> dict[str, DataArray]:
        win, cum, mon_win, mon_cum = self._take_publish()
        return {
            "qmap_current": self._map2d(win, "qmap_current"),
            "qmap_cumulative": self._map2d(cum, "qmap_cumulative"),
            "qmap_normalized": self._map2d(
                cum / max(mon_cum, 1.0), "qmap_normalized", unit=""
            ),
            "counts_current": DataArray(
                Variable(np.asarray(win.sum()), (), "counts"),
                name="counts_current",
            ),
        }

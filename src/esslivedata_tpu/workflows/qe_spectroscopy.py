"""Indirect-geometry Q–E rebinning workflow (BIFROST spectroscopy).

The reference reduces BIFROST through scippneutron/sciline conversion
graphs per cycle; the TPU-native shape is the same as SANS I(Q): all
per-event physics precompiles into a host-built (pixel, toa-bin) →
flat (Q, E)-bin map (ops/qhistogram.build_qe_map), and the streaming
work is one gather+scatter per batch into a ``[n_q * n_e]`` state with
fold semantics. Outputs are S(Q, ω)-style 2-D maps in current and
cumulative views, raw and monitor-normalized, published through the
fused single-round-trip program (ops/publish.py).
"""

from __future__ import annotations

import numpy as np
from pydantic import BaseModel, ConfigDict, Field

from ..config.models import PULSE_PERIOD_NS, TOARange
from ..ops.qhistogram import QHistogrammer, build_qe_map
from ..utils.labeled import DataArray, Variable
from .qshared import QStreamingMixin

__all__ = ["QESpectroscopyParams", "QESpectroscopyWorkflow"]


class QESpectroscopyParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    q_bins: int = 80
    q_min: float = 0.2  # 1/angstrom
    q_max: float = 2.6
    e_bins: int = 60
    e_min: float = -3.0  # meV energy transfer
    e_max: float = 6.0
    toa_bins: int = 320
    # The TOA axis is the wire's: an ev44 time of arrival is relative
    # to its own pulse and lies in [0, 1/14 s), whatever the flight
    # time was. BIFROST's 162 m incident path puts cold-neutron
    # arrivals hundreds of ms after the pulse that made them, so the
    # flight time is the TOA plus a whole number of pulse periods: the
    # frame offset, added to every TOA-bin centre when the table is
    # built. Two periods put incident energies of ~3.0-6.9 meV into the
    # frame, which the five analyzer energies turn into transfers of
    # about -2..4 meV: inside the default E axis.
    toa_range: TOARange = Field(default_factory=TOARange)
    toa_offset_ns: float = 2 * PULSE_PERIOD_NS
    l1: float = 162.0  # m, moderator->sample


class QESpectroscopyWorkflow(QStreamingMixin):
    """Detector events -> S(Q, E); aux monitor events -> normalization."""

    def __init__(
        self,
        *,
        two_theta: np.ndarray,
        ef_mev: np.ndarray,
        l2: np.ndarray,
        pixel_ids: np.ndarray,
        params: QESpectroscopyParams | None = None,
        primary_stream: str | None = None,
        monitor_streams: set[str] | None = None,
    ) -> None:
        params = params or QESpectroscopyParams()
        self._params = params
        q_edges = np.linspace(params.q_min, params.q_max, params.q_bins + 1)
        e_edges = np.linspace(params.e_min, params.e_max, params.e_bins + 1)
        toa_edges = np.linspace(
            params.toa_range.low, params.toa_range.high, params.toa_bins + 1
        )
        qe_map = build_qe_map(
            two_theta=two_theta,
            ef_mev=ef_mev,
            l2=l2,
            pixel_ids=pixel_ids,
            toa_edges=toa_edges,
            q_edges=q_edges,
            e_edges=e_edges,
            l1=params.l1,
            toa_offset_ns=params.toa_offset_ns,
        )
        self._n_q = params.q_bins
        self._n_e = params.e_bins
        self._hist = QHistogrammer(
            qmap=qe_map,
            toa_edges=toa_edges,
            n_q=params.q_bins * params.e_bins,
            method="auto",
        )
        self._state = self._hist.init_state()
        self._q_var = Variable(q_edges, ("Q",), "1/angstrom")
        self._e_var = Variable(e_edges, ("dE",), "meV")
        self._primary_stream = primary_stream
        self._monitor_streams = monitor_streams or set()
        self._publish = None

    def _map2d(self, flat: np.ndarray, name: str) -> DataArray:
        return DataArray(
            Variable(
                flat.reshape(self._n_q, self._n_e), ("Q", "dE"), "counts"
            ),
            coords={"Q": self._q_var, "dE": self._e_var},
            name=name,
        )

    def finalize(self) -> dict[str, DataArray]:
        win, cum, mon_win, mon_cum = self._take_publish()
        results = {
            "sqw_current": self._map2d(win, "sqw_current"),
            "sqw_cumulative": self._map2d(cum, "sqw_cumulative"),
            "counts_current": DataArray(
                Variable(np.asarray(win.sum()), (), "counts"),
                name="counts_current",
            ),
            "monitor_counts_current": DataArray(
                Variable(np.asarray(mon_win), (), "counts"),
                name="monitor_counts_current",
            ),
        }
        norm = self._map2d(cum / max(mon_cum, 1.0), "sqw_normalized")
        norm.data = Variable(norm.values, ("Q", "dE"), "")
        results["sqw_normalized"] = norm
        return results



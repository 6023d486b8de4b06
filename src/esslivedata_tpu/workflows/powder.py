"""Powder-diffraction d-spacing workflow (DREAM).

The reference reduces DREAM through ess.powder's sciline graph
(reference: instruments/dream/factories.py — CorrectedDspacing with
proton-charge run normalization). The TPU-native shape matches the
other reductions: Bragg physics precompiles into a host-built
(pixel, toa-bin) -> d-bin map (ops/qhistogram.build_dspacing_map), the
streaming work is one gather+scatter per batch into fold-semantics
state, and normalization divides by the aux-monitor counts (this
framework's stand-in for accumulated proton charge).

The emission-time correction (a WFM subframe T0 from the chopper
cascade) is LIVE: when an ``emission_offset`` context stream is bound,
its value overrides the static ``toa_offset_ns`` param and changes
rebuild + swap the Bragg table into the running kernel (ADR 0105) —
counts persist because the d bin space is unchanged.
"""

from __future__ import annotations

import time
from collections.abc import Mapping
from typing import Any

import numpy as np
from pydantic import BaseModel, ConfigDict, Field

from ..config.models import TOARange
from ..ops.chopper_cascade import ALPHA_NS_PER_M_A
from ..ops.qhistogram import PixelBinMap, QHistogrammer, build_dspacing_map
from ..telemetry.instruments import TABLE_BUILD_SECONDS
from ..utils.labeled import DataArray, Variable
from .qshared import QStreamingMixin, latest_sample_value

__all__ = [
    "PowderDiffractionParams",
    "PowderDiffractionWorkflow",
    "PowderVanadiumWorkflow",
    "vanadium_acceptance",
]


class PowderDiffractionParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    d_bins: int = 400
    d_min: float = 0.4  # angstrom
    d_max: float = 2.8
    toa_bins: int = 500
    toa_range: TOARange = Field(default_factory=TOARange)
    #: Emission-time correction (e.g. WFM subframe T0 from the chopper
    #: cascade); a live recalibration rebuilds + swaps the table.
    toa_offset_ns: float = 0.0
    #: Offset moves below this are jitter, not a recalibration.
    offset_tolerance_ns: float = 1000.0
    #: 2-theta resolution of the d-2theta map (reference:
    #: FocussedDataDspacingTwoTheta, dream/factories.py:249). The 1-D
    #: I(d) is the marginal of this map, so one kernel feeds both.
    two_theta_bins: int = Field(default=8, ge=1)


def vanadium_acceptance(
    table: np.ndarray, n_bins: int, *, n_bands: int = 1
) -> np.ndarray:
    """Per-d-bin instrument acceptance from the Bragg table itself.

    A vanadium run measures the incoherent (flat-in-d) response of the
    instrument: how many (pixel, TOF-bin) cells feed each d bin. That
    count IS readable off the precompiled table — ``bincount`` of its
    valid entries — giving the live-mode analog of the reference's
    vanadium normalization (reference: dream/factories.py:267, which
    divides by a recorded vanadium run). The result is scaled to mean 1
    over the populated bins so normalized intensities keep the
    magnitude of the monitor-normalized spectrum; bins with zero
    acceptance stay 0 and are masked at division time. A measured
    vanadium spectrum can replace this via
    ``PowderVanadiumWorkflow.set_vanadium``.

    ``n_bands``: the tables :class:`PowderDiffractionWorkflow` builds are
    composite — entry ``d_bin * n_bands + band`` — so pass the workflow's
    2-theta band count to decompose them back to d bins. The default 1
    accepts raw ``build_dspacing_map`` tables whose entries are plain
    d bins.
    """
    from ..ops.qhistogram import _MAP_CHUNK

    # Chunk over leading-axis rows (a same-shape reshape never copies,
    # unlike reshape(-1) on a non-contiguous table).
    arr = np.asarray(table)
    rows = arr.reshape(1, -1) if arr.ndim == 1 else arr.reshape(arr.shape[0], -1)
    rows_per_chunk = max(1, _MAP_CHUNK // rows.shape[1]) if rows.shape[1] else 1
    counts = np.zeros(n_bins, dtype=np.float64)
    # Chunked: no full-table boolean/quotient temporary.
    for lo in range(0, rows.shape[0], rows_per_chunk):
        sl = np.ravel(rows[lo : lo + rows_per_chunk])
        valid = sl[sl >= 0].astype(np.int64) // n_bands
        counts += np.bincount(valid, minlength=n_bins)
    populated = counts > 0
    if populated.any():
        counts[populated] /= counts[populated].mean()
    return counts


class PowderDiffractionWorkflow(QStreamingMixin):
    """Detector events -> I(d); aux monitor events -> normalization."""

    def __init__(
        self,
        *,
        two_theta: np.ndarray,
        l_total: np.ndarray,
        pixel_ids: np.ndarray,
        params: PowderDiffractionParams | None = None,
        primary_stream: str | None = None,
        monitor_streams: set[str] | None = None,
        offset_stream: str = "emission_offset",
    ) -> None:
        params = params or PowderDiffractionParams()
        self._params = params
        d_edges = np.linspace(params.d_min, params.d_max, params.d_bins + 1)
        toa_edges = np.linspace(
            params.toa_range.low, params.toa_range.high, params.toa_bins + 1
        )
        self._geometry = {
            "two_theta": np.asarray(two_theta, dtype=np.float64),
            "l_total": np.asarray(l_total, dtype=np.float64),
            "pixel_ids": np.asarray(pixel_ids),
        }
        self._d_edges = d_edges
        self._toa_edges = toa_edges
        self._offset_stream = offset_stream
        self._offset_ns = float(params.toa_offset_ns)
        self._built_offset_ns = self._offset_ns
        # Per-pixel 2-theta band for the (d, 2theta) map; the composite
        # flat bin is d_bin * n_bands + band.
        tt = self._geometry["two_theta"]
        self._n_bands = int(params.two_theta_bins)
        self._tt_edges = np.linspace(
            float(tt.min()), float(np.nextafter(tt.max(), np.inf)),
            self._n_bands + 1,
        )
        self._band = np.clip(
            np.searchsorted(self._tt_edges, tt, side="right") - 1,
            0,
            self._n_bands - 1,
        )
        dmap = self._build_table()
        self._hist = QHistogrammer(
            qmap=dmap,
            toa_edges=toa_edges,
            n_q=params.d_bins * self._n_bands,
            method="auto",
        )
        self._state = self._hist.init_state()
        self._d_var = Variable(d_edges, ("dspacing",), "angstrom")
        self._tt_var = Variable(self._tt_edges, ("two_theta",), "rad")
        # DIFC from the mean geometry: tof = ALPHA * L * 2 sin(theta) * d
        # (the reference's d -> TOF conversion for the focussed spectrum,
        # dream/factories.py:180).
        difc = (
            ALPHA_NS_PER_M_A
            * float(self._geometry["l_total"].mean())
            * 2.0
            * np.sin(float(tt.mean()) / 2.0)
        )
        self._tof_var = Variable(d_edges * difc, ("tof",), "ns")
        self._primary_stream = primary_stream
        self._monitor_streams = monitor_streams or set()
        self._publish = None

    def _build_table(self) -> PixelBinMap:
        dmap = build_dspacing_map(
            **self._geometry,
            toa_edges=self._toa_edges,
            d_edges=self._d_edges,
            toa_offset_ns=self._offset_ns,
        )
        # Compose the per-pixel 2-theta band into the flat bin. Band is
        # indexed by table row (bank-local ids), widening to int32 when
        # the composite bin space outgrows int16. Chunked over rows to
        # keep peak host memory at the same chunk-bound the map builders
        # guarantee (mantle-scale tables are ~GB as int32).
        from ..ops.qhistogram import _MAP_CHUNK

        began = time.perf_counter()
        ids = self._geometry["pixel_ids"]
        band_by_row = np.zeros(dmap.table.shape[0], dtype=np.int32)
        band_by_row[np.asarray(ids) - dmap.id_base] = self._band
        n_flat = (len(self._d_edges) - 1) * self._n_bands
        dtype = np.int16 if n_flat < np.iinfo(np.int16).max else np.int32
        composite = np.empty(dmap.table.shape, dtype=dtype)
        for lo in range(0, dmap.table.shape[0], _MAP_CHUNK):
            sl = slice(lo, min(lo + _MAP_CHUNK, dmap.table.shape[0]))
            t = dmap.table[sl].astype(np.int32)
            composite[sl] = np.where(
                t >= 0, t * self._n_bands + band_by_row[sl, None], -1
            ).astype(dtype)
        # The composite is still the builder's table: it keeps the
        # family, and this pass is part of what the table cost.
        TABLE_BUILD_SECONDS.inc(
            time.perf_counter() - began, family=dmap.family
        )
        return dmap._replace(table=composite)

    def set_context(self, context: Mapping[str, Any]) -> None:
        """A live emission-time calibration (WFM subframe T0) arrives as
        context; moves beyond the tolerance swap a rebuilt Bragg table
        into the running kernel — no recompile, counts persist."""
        if (
            value := latest_sample_value(context.get(self._offset_stream))
        ) is not None:
            self._offset_ns = value

    def accumulate(self, data: Mapping[str, Any]) -> None:
        if (
            abs(self._offset_ns - self._built_offset_ns)
            >= self._params.offset_tolerance_ns
        ):
            self._hist.swap_table(self._build_table())
            self._built_offset_ns = self._offset_ns
        super().accumulate(data)

    def _spectrum(self, values: np.ndarray, name: str, unit="counts"):
        return DataArray(
            Variable(values, ("dspacing",), unit),
            coords={"dspacing": self._d_var},
            name=name,
        )

    def finalize(self) -> dict[str, DataArray]:
        win2d, cum2d, mon_win, mon_cum = self._take_publish()
        shape = (self._params.d_bins, self._n_bands)
        win2d = win2d.reshape(shape)
        cum2d = cum2d.reshape(shape)
        win = win2d.sum(axis=1)
        cum = cum2d.sum(axis=1)
        return {
            "dspacing_current": self._spectrum(win, "dspacing_current"),
            "dspacing_cumulative": self._spectrum(
                cum, "dspacing_cumulative"
            ),
            "dspacing_normalized": self._spectrum(
                cum / max(mon_cum, 1.0), "dspacing_normalized", unit=""
            ),
            "dspacing_two_theta": DataArray(
                Variable(cum2d, ("dspacing", "two_theta"), "counts"),
                coords={"dspacing": self._d_var, "two_theta": self._tt_var},
                name="dspacing_two_theta",
            ),
            "focussed_tof": DataArray(
                Variable(cum, ("tof",), "counts"),
                coords={"tof": self._tof_var},
                name="focussed_tof",
            ),
            "counts_current": DataArray(
                Variable(np.asarray(win.sum()), (), "counts"),
                name="counts_current",
            ),
            "monitor_counts_current": DataArray(
                Variable(np.asarray(mon_win), (), "counts"),
                name="monitor_counts_current",
            ),
        }


class PowderVanadiumWorkflow(PowderDiffractionWorkflow):
    """I(d) with vanadium normalization (reference:
    dream/specs.py:356 powder_reduction_with_vanadium).

    Divides the monitor-normalized spectrum per d bin by a vanadium
    response — by default the acceptance correction derived from the
    Bragg table (``vanadium_acceptance``), replaceable with a measured
    spectrum. The table-derived default recomputes automatically when a
    live emission-offset recalibration swaps the table.
    """

    _measured_vanadium: np.ndarray | None = None

    def _build_table(self):
        # Derive the acceptance as the table passes through — both the
        # initial build and live emission-offset swaps land here, so the
        # correction always matches the active table without retaining a
        # host copy of the (large) table anywhere.
        table = super()._build_table()
        if self._measured_vanadium is None:
            self._vanadium = vanadium_acceptance(
                table.table, self._params.d_bins, n_bands=self._n_bands
            )
        return table

    def set_vanadium(self, spectrum: np.ndarray) -> None:
        """Install a measured vanadium d-spectrum (same d binning)."""
        spectrum = np.asarray(spectrum, dtype=np.float64)
        if spectrum.shape != (self._params.d_bins,):
            raise ValueError(
                f"vanadium spectrum must have {self._params.d_bins} bins"
            )
        self._measured_vanadium = spectrum
        self._vanadium = spectrum

    def finalize(self) -> dict[str, DataArray]:
        results = super().finalize()
        norm = results["dspacing_normalized"].values
        with np.errstate(divide="ignore", invalid="ignore"):
            intensity = np.where(
                self._vanadium > 0, norm / self._vanadium, 0.0
            )
        results["intensity_dspacing"] = self._spectrum(
            intensity, "intensity_dspacing", unit=""
        )
        return results

"""LOKI factories: projection tables + kernels built lazily."""

from __future__ import annotations

from functools import lru_cache

from ....workflows.detector_view.projectors import ProjectionTable, project_geometric
from ....workflows.detector_view.workflow import DetectorViewWorkflow
from ....workflows.monitor_workflow import MonitorWorkflow
from ....workflows.sans import SansIQWorkflow
from ....workflows.wavelength_spectrum import WavelengthSpectrumWorkflow
from ....workflows.timeseries import TimeseriesWorkflow
from .specs import (
    DETECTOR_VIEW_HANDLE,
    INSTRUMENT,
    MONITOR_HANDLE,
    SANS_IQ_HANDLE,
    TIMESERIES_HANDLE,
    WAVELENGTH_SPECTRUM_HANDLE,
    XY_PROJECTION_HANDLE,
)


@lru_cache(maxsize=None)
def _projection_for(detector_name: str) -> ProjectionTable:
    det = INSTRUMENT.detectors[detector_name]
    return project_geometric(
        det.positions,
        det.pixel_ids,
        mode=det.projection,
        resolution=det.resolution,
        noise_sigma=det.noise_sigma,
        n_replica=det.n_replica,
    )


@XY_PROJECTION_HANDLE.attach_factory
@DETECTOR_VIEW_HANDLE.attach_factory
def make_detector_view(*, source_name: str, params) -> DetectorViewWorkflow:
    return DetectorViewWorkflow(
        projection=_projection_for(source_name), params=params
    )


@MONITOR_HANDLE.attach_factory
def make_monitor(*, source_name: str, params) -> MonitorWorkflow:
    return MonitorWorkflow(params=params)


@SANS_IQ_HANDLE.attach_factory
def make_sans_iq(*, source_name: str, params, aux_source_names=None) -> SansIQWorkflow:
    det = INSTRUMENT.detectors[source_name]
    aux = aux_source_names or {}
    # Transmission only runs when the aux slot is bound: with no binding
    # there is no second monitor to ratio against, fraction stays 1.
    transmission = (
        {aux["transmission_monitor"]} if "transmission_monitor" in aux else None
    )
    # An unbound incident slot falls back to all monitors MINUS the
    # transmission stream — counting it on both channels would inflate
    # the incident denominator and skew T.
    monitors = (
        {aux["monitor"]}
        if "monitor" in aux
        else set(INSTRUMENT.monitor_names) - (transmission or set())
    )
    if transmission and monitors & transmission:
        # Same stream on both channels would make T identically 1 —
        # vacuous but plausible-looking; refuse instead.
        raise ValueError(
            "incident and transmission monitor must be different streams; "
            f"both bound to {sorted(monitors & transmission)}"
        )
    return SansIQWorkflow(
        positions=det.positions,
        pixel_ids=det.pixel_ids,
        params=params,
        primary_stream=source_name,
        monitor_streams=monitors,
        transmission_streams=transmission,
    )


@TIMESERIES_HANDLE.attach_factory
def make_timeseries(*, source_name: str, params) -> TimeseriesWorkflow:
    return TimeseriesWorkflow()


@WAVELENGTH_SPECTRUM_HANDLE.attach_factory
def make_wavelength_spectrum(
    *, source_name: str, params, aux_source_names=None
) -> WavelengthSpectrumWorkflow:
    det = INSTRUMENT.detectors[source_name]
    aux = aux_source_names or {}
    monitors = (
        {aux["monitor"]} if "monitor" in aux else set(INSTRUMENT.monitor_names)
    )
    return WavelengthSpectrumWorkflow(
        positions=det.positions,
        pixel_ids=det.pixel_ids,
        params=params,
        primary_stream=source_name,
        monitor_streams=monitors,
    )

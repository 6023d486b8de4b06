"""Per-service preprocessor factories.

Parity with reference ``preprocessors/detector_data.py:23``,
``data_reduction.py:15``, ``timeseries.py:30``: each service maps stream
kinds to accumulator types; streams returning None are dropped at the
preprocessor (the service consumes but ignores them).
"""

from __future__ import annotations

from ..core.message import StreamId, StreamKind
from .accumulators import Cumulative, LatestValueAccumulator, WindowFrames
from .event_data import ToEventBatch
from .to_nxlog import ToNXlog

__all__ = [
    "DetectorPreprocessorFactory",
    "MonitorPreprocessorFactory",
    "ReductionPreprocessorFactory",
    "TimeseriesPreprocessorFactory",
]


class _KindBasedFactory:
    """Shared kind -> accumulator dispatch."""

    event_kinds: frozenset[StreamKind] = frozenset()
    dense_kinds: frozenset[StreamKind] = frozenset()
    #: Camera frames: kept one by one for the view to sum (ADR 0132).
    frame_kinds: frozenset[StreamKind] = frozenset()
    log_kinds: frozenset[StreamKind] = frozenset()
    latest_kinds: frozenset[StreamKind] = frozenset(
        {StreamKind.LIVEDATA_ROI, StreamKind.DEVICE}
    )

    def __init__(self, *, min_bucket: int | None = None) -> None:
        self._min_bucket = min_bucket

    def make_preprocessor(self, stream: StreamId):
        kind = stream.kind
        if kind in self.event_kinds:
            return ToEventBatch(min_bucket=self._min_bucket)
        if kind in self.dense_kinds:
            return Cumulative(clear_on_get=True)
        if kind in self.frame_kinds:
            return WindowFrames()
        if kind in self.log_kinds:
            return ToNXlog(name=stream.name)
        if kind in self.latest_kinds:
            return LatestValueAccumulator()
        return None


class DetectorPreprocessorFactory(_KindBasedFactory):
    """Detector service: ev44 events, ad00 frames, ROI, logs as context."""

    event_kinds = frozenset({StreamKind.DETECTOR_EVENTS})
    frame_kinds = frozenset({StreamKind.AREA_DETECTOR})
    log_kinds = frozenset({StreamKind.LOG})


class MonitorPreprocessorFactory(_KindBasedFactory):
    """Monitor service: ev44 monitor events + da00 histogram-mode."""

    event_kinds = frozenset({StreamKind.MONITOR_EVENTS})
    dense_kinds = frozenset({StreamKind.MONITOR_COUNTS})
    log_kinds = frozenset({StreamKind.LOG})


class ReductionPreprocessorFactory(_KindBasedFactory):
    """Full reduction: detectors + monitors (both modes) + logs."""

    event_kinds = frozenset(
        {StreamKind.DETECTOR_EVENTS, StreamKind.MONITOR_EVENTS}
    )
    dense_kinds = frozenset({StreamKind.MONITOR_COUNTS})
    frame_kinds = frozenset({StreamKind.AREA_DETECTOR})
    log_kinds = frozenset({StreamKind.LOG})


class TimeseriesPreprocessorFactory(_KindBasedFactory):
    """Timeseries service: logs are the primary data, not context."""

    log_kinds = frozenset()

    def make_preprocessor(self, stream: StreamId):
        if stream.kind in (StreamKind.LOG, StreamKind.DEVICE):
            # Logs and synthesised device streams are primary here
            # (republished as data — the device case is the NICOS readback
            # history) but additionally exposed as context so jobs may
            # gate/parameterize on them — the wavelength-LUT job consumes
            # chopper setpoint streams this way while the plain timeseries
            # job republishes them. Other services consume both kinds as
            # context only, via the kind-based default.
            acc = ToNXlog(name=stream.name)
            acc.is_context = False  # type: ignore[misc]
            acc.also_context = True  # type: ignore[attr-defined]
            return acc
        return None

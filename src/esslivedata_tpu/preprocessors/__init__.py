"""Per-stream accumulators — the host-side half of the hot path.

Parity with reference ``src/ess/livedata/preprocessors/`` (SURVEY.md
section 2.3), redesigned for the TPU pipeline: where the reference
accumulates ev44 chunks into scipp *binned* arrays (ToNXevent_data) and
pre-groups them by pixel (GroupByPixel), here events are only *staged* into
fixed-shape padded device batches (``ToEventBatch``) — projection, grouping
and binning all happen inside the jitted scatter kernel (ops/histogram.py).
Dense accumulators (Cumulative, LatestValue, ToNXlog) remain host-side over
labeled DataArrays; a camera's frames are kept one by one (WindowFrames)
for the view that sums them on the device.
"""

from .accumulators import (
    Cumulative,
    LatestValueAccumulator,
    NullAccumulator,
    WindowFrames,
)
from .event_data import DetectorEvents, MonitorEvents, StagedEvents, ToEventBatch
from .to_nxlog import LogData, ToNXlog

__all__ = [
    "Cumulative",
    "DetectorEvents",
    "LatestValueAccumulator",
    "LogData",
    "MonitorEvents",
    "NullAccumulator",
    "StagedEvents",
    "ToEventBatch",
    "ToNXlog",
    "WindowFrames",
]

"""Monitor histogram workflow (reference: workflows/monitor_workflow.py).

Handles both monitor data modes like the reference (_histogram_monitor:65):
event-mode (ev44 -> staged event batches -> 1-row device histogram) and
histogram-mode (da00 dense histograms -> host rebin onto the target edges,
accumulated with Cumulative). Outputs current/cumulative 1-D spectra on
the configured coordinate: TOA (ns) or wavelength (angstrom) — the
latter via the same device kernel over lambda-derived edges.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any, Literal

import numpy as np
from pydantic import BaseModel, ConfigDict, Field, model_validator

from ..config.models import TOARange
from ..ops.histogram import EventHistogrammer, HistogramState
from ..preprocessors.event_data import StagedEvents
from ..utils.labeled import DataArray, Variable

__all__ = ["MonitorWorkflow", "MonitorParams", "rebin_1d"]




class MonitorParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    toa_bins: int = 100
    toa_range: TOARange = Field(default_factory=TOARange)
    # Coordinate mode (reference: monitor_workflow.py:169 coordinate_mode):
    # "toa" histograms time-of-arrival; "wavelength" histograms
    # lambda = (h/m_n) * t / L. lambda is linear in t for a fixed flight
    # path, so wavelength mode is the SAME device kernel over transformed
    # edges — no per-event conversion, no second code path on device.
    coordinate: Literal["toa", "wavelength"] = "toa"
    wavelength_min: float = 0.5  # angstrom (wavelength mode)
    wavelength_max: float = 12.0
    distance_m: float = 25.0  # source->monitor flight path (m)
    toa_offset_ns: float = 0.0  # emission-time / frame offset correction
    # Position moves beyond this clear accumulation (reference:
    # monitor_workflow.py:36 MONITOR_TRANSFORM geometry-signal coord —
    # a moved monitor samples a different beam, so stale counts lie).
    # In the position log's NATIVE units — set it per instrument to
    # match what the positioner publishes (mm at ESS beamlines).
    position_tolerance: float = 1.0

    @model_validator(mode="after")
    def _wavelength_mode_consistent(self) -> MonitorParams:
        if self.wavelength_max <= self.wavelength_min:
            raise ValueError("wavelength range must satisfy min < max")
        if self.distance_m <= 0:
            raise ValueError("distance_m must be positive")
        if self.coordinate == "wavelength":
            default = TOARange()
            narrowed = self.toa_range.enabled and (
                self.toa_range.low != default.low
                or self.toa_range.high != default.high
            )
            if narrowed:
                raise ValueError(
                    "toa_range does not apply in wavelength mode — the "
                    "spectrum is windowed by wavelength_min/max instead; "
                    "reset toa_range or switch coordinate back to 'toa'"
                )
        return self


def rebin_1d(
    values: np.ndarray, src_edges: np.ndarray, dst_edges: np.ndarray
) -> np.ndarray:
    """Conservative rebin of a dense 1-D histogram onto new edges
    (fractional-overlap weighting, the host-side analog of scipp's rebin
    used by the reference for histogram-mode monitors)."""
    src_edges = np.asarray(src_edges, dtype=np.float64)
    dst_edges = np.asarray(dst_edges, dtype=np.float64)
    out = np.zeros(dst_edges.size - 1)
    # Overlap of each src bin [a,b) with each dst bin via interval clipping.
    a = src_edges[:-1]
    b = src_edges[1:]
    widths = b - a
    for j in range(dst_edges.size - 1):
        lo, hi = dst_edges[j], dst_edges[j + 1]
        overlap = np.clip(np.minimum(b, hi) - np.maximum(a, lo), 0.0, None)
        with np.errstate(invalid="ignore", divide="ignore"):
            frac = np.where(widths > 0, overlap / widths, 0.0)
        out[j] = float((values * frac).sum())
    return out


class MonitorWorkflow:
    """1-D monitor spectrum (TOA or wavelength axis), event- or
    histogram-mode."""

    def __init__(
        self,
        *,
        params: MonitorParams | None = None,
        position_stream: str | None = None,
    ) -> None:
        params = params or MonitorParams()
        self._params = params
        if params.coordinate == "wavelength":
            from ..ops.chopper_cascade import ALPHA_NS_PER_M_A

            lam_edges = np.linspace(
                params.wavelength_min, params.wavelength_max, params.toa_bins + 1
            )
            # t[ns] = ALPHA * L * lambda, shifted back by the emission
            # offset so event TOA (not true TOF) bins correctly.
            self._edges = (
                lam_edges * params.distance_m * ALPHA_NS_PER_M_A
                - params.toa_offset_ns
            )
            self._axis = "wavelength"
            self._axis_var = Variable(lam_edges, ("wavelength",), "angstrom")
        else:
            self._edges = np.linspace(
                params.toa_range.low, params.toa_range.high, params.toa_bins + 1
            )
            self._axis = "toa"
            self._axis_var = Variable(self._edges, ("toa",), "ns")
        self._hist = EventHistogrammer(
            toa_edges=self._edges, n_screen=1, method="auto"
        )
        self._state: HistogramState = self._hist.init_state()

        def publish_program(state):
            cum, win = self._hist.views_of(state)
            return (
                {"cum": cum[0], "win": win[0]},
                self._hist.fold_window(state),
            )

        from ..ops.publish import PackedPublisher

        # One execute + one fetch per publish (see ops/publish.py).
        self._publish = PackedPublisher(publish_program, name="monitor")
        #: Combined-publish hand-off (ADR 0113): outputs prefetched by
        #: the JobManager's fused tick round trip, consumed in finalize.
        self._prefetched_publish: dict | None = None
        # Dense-mode accumulation happens host-side (tiny arrays).
        self._dense_cumulative = np.zeros(params.toa_bins)
        self._dense_window = np.zeros(params.toa_bins)
        # Which context stream carries this monitor's position, injected
        # by the instrument factory (same pattern as the powder/
        # reflectometry workflows' stream-name injection); None = fixed
        # monitor, feature off. _position anchors at the last CLEAR (or
        # first sample) — comparing against the last sample instead
        # would let a slow scan creep arbitrarily far without reset.
        self._position_stream = position_stream
        self._position: float | None = None

    def set_context(self, context: Mapping[str, Any]) -> None:
        """Track the monitor's position (optional context stream): a move
        beyond the tolerance clears accumulated spectra — a moved monitor
        samples a different beam."""
        from .qshared import latest_sample_value

        if self._position_stream is None:
            return
        value = latest_sample_value(context.get(self._position_stream))
        if value is None:
            return
        if self._position is None:
            self._position = value
        elif abs(value - self._position) > self._params.position_tolerance:
            self.clear()
            self._position = value

    @staticmethod
    def _row0_impl(batch):
        if batch.pixel_id.size and batch.pixel_id.max() > 0:
            from ..ops import EventBatch

            return (
                EventBatch(
                    pixel_id=np.where(
                        batch.pixel_id >= 0, 0, -1
                    ).astype(np.int32),
                    toa=batch.toa,
                    n_valid=batch.n_valid,
                    owner=batch.owner,
                ),
                "mon-row0",
            )
        return batch, ""

    @classmethod
    def _row0_batch(cls, batch, cache=None):
        """(batch, batch_tag) with pixel ids folded onto screen row 0.

        A pixellated monitor's staged events carry real pixel ids; this
        1-D TOA histogram is id-agnostic, so every valid event folds onto
        screen row 0 (the -1 padding sentinel stays excluded). Without
        the clamp the n_screen=1 kernel would mask ids >= 1 and silently
        zero the spectrum. The non-empty tag keeps the clamped wire from
        ever colliding with the raw stream in the window stream-cache —
        and lets every monitor job SHARE the clamped staging. The clamp
        itself (a full-array scan + rewrite) memoizes through the same
        slot, so K monitor jobs pay it once per window, not K times."""
        if cache is None:
            return cls._row0_impl(batch)
        return cache.get_or_stage(
            ("mon-row0-host", batch.padded_size),
            lambda: cls._row0_impl(batch),
        )

    def accumulate(self, data: Mapping[str, Any]) -> None:
        for value in data.values():
            if isinstance(value, StagedEvents):
                batch, tag = self._row0_batch(value.batch, value.cache)
                self._state = self._hist.step_batch(
                    self._state, batch, cache=value.cache, batch_tag=tag
                )
            elif isinstance(value, DataArray):
                self._add_dense(value)

    def event_ingest(self, stream: str, staged: StagedEvents):
        """Fused-stepping offer (core/job_manager.py): K same-axis
        monitor jobs on one stream advance in a single dispatch from one
        (possibly row0-clamped) staged batch; on publish ticks the tick
        program (ops/tick.py, ADR 0114) fuses that step with the packed
        publish into the same dispatch. The row0 clamp stays a
        host-side batch transform keyed by its ``batch_tag``, so K
        monitor ticks share one clamped staging either way. Dense
        histogram-mode data never arrives as StagedEvents, so it keeps
        the private path."""
        from ..core.device_event_cache import EventIngest

        batch, tag = self._row0_batch(staged.batch, staged.cache)

        def set_state(state) -> None:
            self._state = state

        return EventIngest(
            key=self._hist.fuse_key + (tag,),
            hist=self._hist,
            batch=batch,
            batch_tag=tag,
            get_state=lambda: self._state,
            set_state=set_state,
        )

    def _add_dense(self, da: DataArray) -> None:
        coord_name = next(
            (c for c in ("toa", "time_of_arrival", "tof") if c in da.coords), None
        )
        if coord_name is None or da.data.ndim != 1:
            raise ValueError(
                f"Histogram-mode monitor data needs a 1-D TOA coord, got {da!r}"
            )
        src_edges = da.coords[coord_name].to_unit("ns").numpy
        if coord_name == "tof" and self._params.toa_offset_ns:
            # True time-of-flight -> event-TOA space (our edges' frame):
            # toa = tof - offset. Without this a nonzero offset would be
            # applied twice for tof-coord dense data in wavelength mode.
            src_edges = src_edges - self._params.toa_offset_ns
        values = np.asarray(da.values, dtype=np.float64)
        if src_edges.size == values.size:  # midpoints: synthesize edges
            mids = src_edges
            steps = np.diff(mids)
            edges = np.concatenate(
                [
                    [mids[0] - steps[0] / 2],
                    mids[:-1] + steps / 2,
                    [mids[-1] + steps[-1] / 2],
                ]
            )
            src_edges = edges
        rebinned = rebin_1d(values, src_edges, self._edges)
        self._dense_window += rebinned
        self._dense_cumulative += rebinned

    def publish_offer(self):
        """Combined-publish offer (ADR 0113): K monitor jobs due in one
        tick share a single device round trip — under the tick program
        (ADR 0114) that round trip also carries the event step, args[0]
        being the pre-step state per the make_publish_offer contract.
        The dense histogram-mode accumulation is host-side and merges at
        finalize as always (the device publish never sees it, so the
        tick's in-dispatch publish stays correct when dense data and
        staged events share a window — the manager only ticks
        single-stream windows regardless)."""
        from ..ops.publish import make_publish_offer

        return make_publish_offer(
            self,
            self._publish,
            (self._state,),
            fresh_state=self._hist.init_state,
        )

    def finalize(self) -> dict[str, DataArray]:
        out = self._prefetched_publish
        if out is not None:
            self._prefetched_publish = None
        else:
            out, self._state = self._publish(self._state)
        win = out["win"] + self._dense_window
        cum = out["cum"] + self._dense_cumulative
        self._dense_window = np.zeros_like(self._dense_window)
        axis = self._axis
        coords = {axis: self._axis_var}
        return {
            "current": DataArray(
                Variable(win, (axis,), "counts"), coords=coords, name="current"
            ),
            "cumulative": DataArray(
                Variable(cum, (axis,), "counts"), coords=coords, name="cumulative"
            ),
            "counts_current": DataArray(
                Variable(np.asarray(win.sum()), (), "counts"), name="counts_current"
            ),
            "counts_cumulative": DataArray(
                Variable(np.asarray(cum.sum()), (), "counts"),
                name="counts_cumulative",
            ),
        }

    def clear(self) -> None:
        self._state = self._hist.clear(self._state)
        self._dense_cumulative[:] = 0.0
        self._dense_window[:] = 0.0
        self._prefetched_publish = None

    # -- state snapshots (core/state_snapshot.py, ADR 0107) ----------------
    def state_fingerprint(self) -> str:
        """Axis edges + full params: everything that gives the spectrum
        bins physical meaning (a position move resets accumulation
        in-process, so the anchor position itself is not part of the
        bins' meaning and travels with the dump instead)."""
        import hashlib

        h = hashlib.sha1()
        h.update(self._edges.tobytes())
        h.update(self._params.model_dump_json().encode())
        return h.hexdigest()

    def dump_state(self) -> dict[str, np.ndarray]:
        out = EventHistogrammer.dump_state_arrays(self._state)
        out["dense_window"] = self._dense_window.copy()
        out["dense_cumulative"] = self._dense_cumulative.copy()
        if self._position is not None:
            # The reset-on-move anchor: without it, a restart during a
            # slow scan would re-anchor at the next sample and blend
            # pre-move counts with post-move ones.
            out["position"] = np.asarray(float(self._position))
        return out

    def restore_state(self, arrays: dict[str, np.ndarray]) -> bool:
        dense_w = np.asarray(arrays.get("dense_window"))
        dense_c = np.asarray(arrays.get("dense_cumulative"))
        if (
            dense_w.shape != self._dense_window.shape
            or dense_c.shape != self._dense_cumulative.shape
        ):
            return False
        restored = self._hist.restore_state_arrays(self._state, arrays)
        if restored is None:
            return False
        self._state = restored
        self._dense_window = dense_w.astype(self._dense_window.dtype)
        self._dense_cumulative = dense_c.astype(self._dense_cumulative.dtype)
        if "position" in arrays:
            self._position = float(arrays["position"])
        return True


#: Wire-schema contract (graftlint trace pass, JGL105 / ADR 0123):
#: output name -> (ndim, dtype); see detector_view/workflow.py.
TICK_WIRE_SCHEMA = {
    "cum": (1, "float32"),
    "win": (1, "float32"),
}

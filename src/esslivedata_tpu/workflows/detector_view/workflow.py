"""The detector-view streaming workflow.

Reference parity: workflows/detector_view/workflow.py:67 (pipeline),
providers.py:169-328 (histogram, image, counts, spectrum, ROI spectra),
roi.py:31-188 (ROI masks/spectra). The whole per-cycle pipeline is two
jitted programs: ``step`` (scatter-add accumulate, ops/histogram.py) and
``_finalize`` (image/spectrum/counts/ROI summaries computed on device and
pulled to host as small dense outputs).
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any, Literal

import json

import jax.numpy as jnp
import numpy as np
from pydantic import BaseModel, ConfigDict, Field

from ...config.models import ROI, PolygonROI, RectangleROI, TOARange
from ...config.roi_names import default_roi_mapper
from ...ops.histogram import EventHistogrammer, HistogramState
from ...preprocessors.event_data import StagedEvents
from ...utils.labeled import DataArray, Variable
from .projectors import ProjectionTable

__all__ = ["DetectorViewParams", "DetectorViewWorkflow", "MAX_ROIS"]



MAX_ROIS = 8
"""ROI mask matrix rows are fixed at this size so ROI edits never trigger
an XLA recompile — unused rows are zero."""


class DetectorViewParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    toa_bins: int = 100
    toa_range: TOARange = Field(default_factory=TOARange)
    pixel_weighting: bool = False
    # Optional TOA sub-range restricting the IMAGE sums (reference:
    # providers.py:236-255 HistogramSlice / counts_in_range:328). The
    # spectrum keeps the full axis. Bin edges are static under jit, so
    # the slice compiles to a static index range — zero runtime cost.
    image_toa_slice: TOARange | None = None
    # How the view's bin indices reach the count (ops/histogram.py).
    # 'scatter' (the default): unpartitioned, and the histogrammer picks
    # the kernel from what it observes (``EventHistogrammer(method=
    # "auto")``, ADR 0131): on a TPU the chip sorts the indices and
    # counts them block by block on the MXU wherever the update is a
    # scalar, replica LUTs included; XLA's scatter-add for pixel
    # weighting and off the TPU. 'pallas2d': partitioned on the host for
    # the same MXU kernel, host-flattenable configurations only; the
    # others (pixel weighting, replica LUTs) count as 'scatter' does.
    histogram_method: Literal["scatter", "pallas2d"] = "scatter"


def _density_weights(lut: np.ndarray) -> np.ndarray:
    """Per-pixel 1/occupancy weights compensating projection density
    (reference providers.py:98): screen bins fed by many pixels are
    downweighted so the image reflects rate per screen area."""
    valid = lut[0] >= 0
    counts = np.bincount(lut[0][valid])
    w = np.zeros(lut.shape[1], dtype=np.float32)
    w[valid] = 1.0 / np.maximum(counts[lut[0][valid]], 1)
    return w


class DetectorViewWorkflow:
    """Histogram events on a projected 2-D screen; emit image, spectrum,
    total counts and ROI spectra in current (window) and cumulative views.
    """

    def __init__(
        self,
        *,
        projection: ProjectionTable,
        params: DetectorViewParams | None = None,
        primary_stream: str | None = None,
        filters=None,
    ) -> None:
        params = params or DetectorViewParams()
        self._proj = projection
        self._params = params
        # Optional per-event filter chain (workloads/filters.py, ADR
        # 0122): a digest-tagged host batch transform — rejected events
        # become pixel_id -1 before staging, so filtering costs zero
        # extra device dispatches and same-chain jobs share one
        # filtered wire. None/empty = identity (tag "").
        if filters is None:
            from ...workloads.filters import FilterChain

            filters = FilterChain()
        self._filters = filters
        edges = np.linspace(
            params.toa_range.low, params.toa_range.high, params.toa_bins + 1
        )
        weights = (
            _density_weights(projection.lut) if params.pixel_weighting else None
        )
        # pallas2d consumes host-partitioned flat indices; weighted and
        # replica configurations, like 'scatter', leave the kernel to the
        # histogrammer.
        method = "auto"
        if params.histogram_method == "pallas2d" and not (
            weights is not None
            or (projection.lut is not None and projection.lut.shape[0] > 1)
        ):
            method = "pallas2d"
        self._hist = EventHistogrammer(
            toa_edges=edges,
            n_screen=projection.n_screen,
            pixel_lut=projection.lut,
            pixel_weights=weights,
            method=method,
        )
        self._state: HistogramState = self._hist.init_state()
        self._primary_stream = primary_stream
        self._roi_mapper = default_roi_mapper()
        assert self._roi_mapper.total_rois <= MAX_ROIS
        self._roi_names: list[str] = []
        self._rois_by_index: dict[int, tuple[str, ROI]] = {}
        self._roi_masks = jnp.zeros(
            (MAX_ROIS, projection.n_screen), dtype=jnp.float32
        )
        ny, nx = projection.ny, projection.nx
        n_toa = self._hist.n_toa
        n_bins = projection.n_screen * n_toa
        # Static slice bounds for the image sums: full axis when the
        # param is absent/disabled. Any bin OVERLAPPING [low, high) is
        # included, so the realized range always covers the request.
        sl = params.image_toa_slice
        if sl is not None and sl.enabled:
            a = max(int(np.searchsorted(edges, sl.low, side="right")) - 1, 0)
            b = min(int(np.searchsorted(edges, sl.high, side="left")), n_toa)
            if a >= b:
                raise ValueError(
                    "image_toa_slice selects no bins within toa_range"
                )
        else:
            a, b = 0, n_toa

        def publish_program(state, roi_masks):
            # The histogrammer owns the state layout (flat, dump bin, lazy
            # decay scale); compose its traceable view here so the fold
            # into the cumulative fuses into the reductions below, and the
            # window fold into the same program — publish is ONE execute
            # plus ONE packed fetch (ops/publish.py).
            win = self._hist.physical_window(state)[:n_bins].reshape(
                projection.n_screen, n_toa
            )
            cum = win + state.folded[:n_bins].reshape(
                projection.n_screen, n_toa
            )
            win_img = win[:, a:b]
            cum_img = cum[:, a:b]
            outputs = {
                "image_current": win_img.sum(axis=1).reshape(ny, nx),
                "image_cumulative": cum_img.sum(axis=1).reshape(ny, nx),
                "spectrum_current": win.sum(axis=0),
                "spectrum_cumulative": cum.sum(axis=0),
                "counts_current": win.sum(),
                "counts_cumulative": cum.sum(),
                "counts_in_range_current": win_img.sum(),
                "counts_in_range_cumulative": cum_img.sum(),
                # [MAX_ROIS, n_toa] on the MXU; unused rows are zero.
                "roi_spectra": roi_masks @ win,
                "roi_spectra_cumulative": roi_masks @ cum,
            }
            return outputs, self._hist.fold_window(state)

        from ...ops.publish import PackedPublisher

        # The ROI spectra blocks are layout-constant (all zeros) until
        # real masks are installed: on the common no-ROI dashboard they
        # are 6.4 KB/tick of fetched-and-discarded data (the majority
        # of the packed vector for small screens), so they ride the
        # static channel — fetched once per layout digest, served from
        # the host cache after (ADR 0113). ``set_rois`` flips them
        # dynamic the moment masks make them carry data.
        self._publish = PackedPublisher(
            publish_program,
            static_keys=self._STATIC_ROI_KEYS,
            name="detector_view",
        )
        #: Combined-publish hand-off (ops/publish.py PublishOffer): the
        #: JobManager prefetches this job's outputs through one fused
        #: device round trip; finalize consumes instead of dispatching.
        self._prefetched_publish: dict | None = None
        self._toa_edges_var = Variable(edges, ("toa",), "ns")
        assert n_toa == edges.size - 1

    _STATIC_ROI_KEYS = ("roi_spectra", "roi_spectra_cumulative")

    def swap_projection(self, projection: ProjectionTable) -> bool:
        """Adopt a rebuilt projection WITHOUT recompiling anything.

        Live-geometry moves (motor-driven LUT rebuilds) land here first:
        when the new table has the same screen shape and this
        configuration runs the host-flatten fast path, the swap is a
        host-side LUT replacement — the jitted step, fold and publish
        programs are untouched. State resets (moved-geometry counts must
        not blend) and installed ROI masks recompute against the new
        screen edges. Returns False when only a full rebuild is correct
        (shape change, per-pixel weighting, device-projection configs).
        """
        if (
            projection.n_screen != self._proj.n_screen
            or projection.ny != self._proj.ny
            or projection.nx != self._proj.nx
            or self._params.pixel_weighting
            or not self._hist.supports_host_flatten
        ):
            return False
        if not self._hist.swap_projection(projection.lut):
            return False  # LUT shape mismatch: full rebuild
        self._proj = projection
        self._state = self._hist.clear(self._state)
        self._prefetched_publish = None
        if self._rois_by_index:
            self.set_rois(
                {name: roi for name, roi in self._rois_by_index.values()}
            )
        return True

    # -- ROI management ----------------------------------------------------
    def set_rois(self, rois: Mapping[str, ROI]) -> None:
        """Install ROI masks (from the dashboard's ROI topic round trip,
        reference roi.py:293).

        Each ROI is assigned a *global index* following the
        ``config/roi_names.py`` partition (rectangles and polygons own
        disjoint index ranges), which is also its mask-matrix row — so the
        ``roi`` coord on the spectra outputs and the readback indices agree
        with the naming convention the dashboard uses for labels. Per-type
        capacity is bounded by the mapper so ROI edits never change array
        shapes (no XLA recompile).
        """
        from ...utils.labeled import midpoints

        xc = midpoints(self._proj.x_edges).numpy
        yc = midpoints(self._proj.y_edges).numpy
        masks = np.zeros((MAX_ROIS, self._proj.n_screen), dtype=np.float32)
        counters = {g.geometry_type: iter(g.index_range) for g in self._roi_mapper.geometries}
        indexed: dict[int, tuple[str, ROI]] = {}
        for name, roi in rois.items():
            gtype = next(
                (
                    g.geometry_type
                    for g in self._roi_mapper.geometries
                    if isinstance(roi, g.roi_class)
                ),
                None,
            )
            if gtype is None:
                raise ValueError(
                    f"ROI {name!r} has unsupported type {type(roi).__name__}"
                )
            try:
                index = next(counters[gtype])
            except StopIteration:
                limit = next(
                    g.num_rois
                    for g in self._roi_mapper.geometries
                    if g.geometry_type == gtype
                )
                raise ValueError(
                    f"At most {limit} {gtype} ROIs supported"
                ) from None
            masks[index] = roi.mask(xc, yc).reshape(-1).astype(np.float32)
            indexed[index] = (name, roi)
        self._rois_by_index = dict(sorted(indexed.items()))
        self._roi_names = [name for name, _ in self._rois_by_index.values()]
        self._roi_masks = jnp.asarray(masks)
        # Installed masks make the ROI spectra carry data: publish them
        # on the dynamic (per-tick) channel. Clearing every ROI flips
        # them back to the static zero blocks.
        self._publish.set_static_keys(
            () if self._rois_by_index else self._STATIC_ROI_KEYS
        )

    @property
    def roi_names(self) -> list[str]:
        return list(self._roi_names)

    # -- Workflow protocol -------------------------------------------------
    def accumulate(self, data: Mapping[str, Any]) -> None:
        for key, value in data.items():
            if isinstance(value, StagedEvents):
                if self._primary_stream is None or key == self._primary_stream:
                    # value.cache (the window's stream slot, attached by
                    # the JobManager) makes flatten + transfer run once
                    # per (stream, layout) across every subscribed job.
                    batch, tag = self._filters.apply(
                        value.batch, value.cache
                    )
                    self._state = self._hist.step_batch(
                        self._state, batch, cache=value.cache,
                        batch_tag=tag,
                    )

    def event_ingest(self, stream: str, staged: StagedEvents):
        """Fused-stepping offer (core/job_manager.py): ingesting a
        primary-stream batch is exactly one histogrammer step over
        this job's private state, so K same-layout detector views can
        advance in one dispatch from one staged batch. On publish ticks
        the same offer feeds the tick program (ops/tick.py, ADR 0114),
        which composes this step with the packed publish below into ONE
        dispatch — ``get_state`` must return the same object
        ``publish_offer`` passes as args[0] (the manager verifies the
        identity and degrades to separate dispatches otherwise)."""
        from ...workloads.filters import filtered_event_ingest

        return filtered_event_ingest(
            self,
            hist=self._hist,
            filters=self._filters,
            primary_stream=self._primary_stream,
            stream=stream,
            staged=staged,
        )

    def publish_offer(self):
        """Combined-publish offer (core/job_manager.py, ADR 0113): this
        job's packed publish program joins the tick's fused device round
        trip; ``finalize`` then consumes the prefetched tree. Under the
        tick program (ADR 0114) args[0] is the PRE-step state — the
        program steps it in-dispatch and publishes the stepped result,
        so one execute + one fetch covers the whole window. The ROI
        static split and the layout-digest token carry through both
        paths unchanged."""
        from ...ops.publish import make_publish_offer

        return make_publish_offer(
            self,
            self._publish,
            (self._state, self._roi_masks),
            static_token=self._hist.layout_digest,
            fresh_state=self._hist.init_state,
        )

    def finalize(self) -> dict[str, DataArray]:
        out = self._prefetched_publish
        if out is not None:
            self._prefetched_publish = None
        else:
            out, self._state = self._publish(
                self._state,
                self._roi_masks,
                static_token=self._hist.layout_digest,
            )

        img_coords = {
            "x": self._proj.x_edges,
            "y": self._proj.y_edges,
        }
        spec_coords = {"toa": self._toa_edges_var}
        results: dict[str, DataArray] = {
            "image_current": DataArray(
                Variable(out["image_current"], ("y", "x"), "counts"),
                coords=img_coords,
                name="image_current",
            ),
            "image_cumulative": DataArray(
                Variable(out["image_cumulative"], ("y", "x"), "counts"),
                coords=img_coords,
                name="image_cumulative",
            ),
            "spectrum_current": DataArray(
                Variable(out["spectrum_current"], ("toa",), "counts"),
                coords=spec_coords,
                name="spectrum_current",
            ),
            "spectrum_cumulative": DataArray(
                Variable(out["spectrum_cumulative"], ("toa",), "counts"),
                coords=spec_coords,
                name="spectrum_cumulative",
            ),
            **{
                k: DataArray(
                    Variable(np.asarray(out[k]), (), "counts"), name=k
                )
                for k in (
                    "counts_current",
                    "counts_cumulative",
                    "counts_in_range_current",
                    "counts_in_range_cumulative",
                )
            },
        }
        if self._rois_by_index:
            indices = np.asarray(list(self._rois_by_index), dtype=np.int32)
            roi_idx = Variable(indices, ("roi",), "")
            for key in ("roi_spectra", "roi_spectra_cumulative"):
                spectra = out[key][indices]
                results[key] = DataArray(
                    Variable(spectra, ("roi", "toa"), "counts"),
                    coords={"toa": self._toa_edges_var, "roi": roi_idx},
                    name=key,
                )
        results.update(self._roi_readbacks())
        return results

    def _roi_readbacks(self) -> dict[str, DataArray]:
        """Applied-ROI readback outputs (reference roi.py:293-355): the
        dashboard renders what the backend actually applied, not what it
        asked for. da00 is numeric-only, so shapes ride as index-keyed
        coordinate arrays (config/roi_names.py convention): rectangles as
        per-ROI bound coords, polygons as per-vertex coords with a roi
        index. Always emitted — an empty readback tells the frontend the
        coordinate units to use when creating ROIs."""
        x_unit = self._proj.x_edges.unit
        y_unit = self._proj.y_edges.unit
        rects = [
            (i, r)
            for i, (_, r) in self._rois_by_index.items()
            if isinstance(r, RectangleROI)
        ]
        polys = [
            (i, r)
            for i, (_, r) in self._rois_by_index.items()
            if isinstance(r, PolygonROI)
        ]
        rect_idx = np.asarray([i for i, _ in rects], dtype=np.int32)
        rect = DataArray(
            Variable(rect_idx, ("roi",), ""),
            coords={
                "x_min": Variable(
                    np.asarray([r.x_min for _, r in rects]), ("roi",), x_unit
                ),
                "x_max": Variable(
                    np.asarray([r.x_max for _, r in rects]), ("roi",), x_unit
                ),
                "y_min": Variable(
                    np.asarray([r.y_min for _, r in rects]), ("roi",), y_unit
                ),
                "y_max": Variable(
                    np.asarray([r.y_max for _, r in rects]), ("roi",), y_unit
                ),
            },
            name="roi_rectangle",
        )
        vert_roi = np.asarray(
            [i for i, p in polys for _ in p.x], dtype=np.int32
        )
        poly = DataArray(
            Variable(vert_roi, ("vertex",), ""),
            coords={
                "x": Variable(
                    np.asarray([x for _, p in polys for x in p.x]),
                    ("vertex",),
                    x_unit,
                ),
                "y": Variable(
                    np.asarray([y for _, p in polys for y in p.y]),
                    ("vertex",),
                    y_unit,
                ),
            },
            name="roi_polygon",
        )
        return {"roi_rectangle": rect, "roi_polygon": poly}

    def clear(self) -> None:
        self._state = self._hist.clear(self._state)
        # A reset between prefetch and finalize must not resurrect the
        # pre-reset window on the next publish.
        self._prefetched_publish = None

    # -- state snapshots (core/state_snapshot.py) --------------------------
    def state_fingerprint(self) -> str:
        """Hash over everything that gives the accumulated bins physical
        meaning; a restored state with a different fingerprint would put
        counts in bins that mean something else."""
        import hashlib

        h = hashlib.sha1()
        h.update(np.ascontiguousarray(self._proj.lut).tobytes())
        h.update(self._toa_edges_var.numpy.tobytes())
        h.update(
            f"{self._proj.ny}x{self._proj.nx}:{self._hist.n_toa}:".encode()
        )
        # Full params EXCEPT the kernel choice: two jobs differing in any
        # physically meaningful parameter must not exchange state, but
        # histogram_method only selects HOW the same bins accumulate —
        # the snapshot codec adapts the layouts (restore_state_arrays),
        # so a kernel switch between runs keeps its recovery snapshot.
        h.update(
            json.dumps(
                self._params.model_dump(exclude={"histogram_method"}),
                sort_keys=True,
            ).encode()
        )
        # Filtered and unfiltered accumulations must never exchange
        # state: the bins mean "events that PASSED this chain".
        h.update(self._filters.digest.encode())
        return h.hexdigest()

    def dump_state(self) -> dict[str, np.ndarray]:
        """Host copy of the device accumulation (folded, window, scale)."""
        return EventHistogrammer.dump_state_arrays(self._state)

    def restore_state(self, arrays: dict[str, np.ndarray]) -> bool:
        """Adopt a dumped accumulation; shape-checked against the current
        kernel (fingerprint matching happens in the store, but a corrupt
        file must not poison the device state)."""
        restored = self._hist.restore_state_arrays(self._state, arrays)
        if restored is None:
            return False
        self._state = restored
        return True

    # -- introspection -----------------------------------------------------
    @property
    def histogrammer(self) -> EventHistogrammer:
        return self._hist

    @property
    def state(self) -> HistogramState:
        return self._state


#: Wire-schema contract (graftlint trace pass, JGL105 / ADR 0123):
#: publish output name -> (ndim, dtype) as serialized on the da00
#: wire. Pinned HERE, next to the publish program it constrains, so a
#: program edit and its schema change ride the same diff — drift
#: between the two breaks the delta codec's keyframe contract and is
#: caught at lint time, not by a subscriber.
TICK_WIRE_SCHEMA = {
    "counts_cumulative": (0, "float32"),
    "counts_current": (0, "float32"),
    "counts_in_range_cumulative": (0, "float32"),
    "counts_in_range_current": (0, "float32"),
    "image_cumulative": (2, "float32"),
    "image_current": (2, "float32"),
    "roi_spectra": (2, "float32"),
    "roi_spectra_cumulative": (2, "float32"),
    "spectrum_cumulative": (1, "float32"),
    "spectrum_current": (1, "float32"),
}

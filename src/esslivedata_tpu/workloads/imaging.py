"""Imaging/tomography dense 2-D view (ADR 0122).

The pallas2d MXU-tiled kernel's natural second customer (the first is
the big detector view): a dense ``[ny, nx]`` image accumulated over a
small number of time-gate frames, flat-field-corrected at publish via a
device-resident calibration map. The ingest is the plain flat wire —
pixel grid × frame gate — so the family rides fused stepping, the
one-dispatch tick program (ADR 0114) and mesh placement unchanged, and
``histogram_method='pallas2d'`` exercises the host partition kernels
under per-event filters (ROADMAP item 4's "stresses the partition
kernels" axis, asserted in ``bench.py --workloads``).

The flat-field map is a :class:`~.calibration.CalibrationTable` column
in SCREEN space: it rides the publish program as an ARGUMENT (the
ADR 0105 tables-as-jit-arguments discipline — a swap is one transfer,
never a retrace) and publishes as a STATIC readback keyed by the
combined layout+calibration digest, so dashboards always see the
correction actually applied and a swap refetches it exactly once.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import numpy as np
from pydantic import BaseModel, ConfigDict

from ..ops.histogram import EventHistogrammer, HistogramState
from ..preprocessors.event_data import StagedEvents
from ..telemetry.instruments import CALIBRATION_SWAPS
from ..utils.labeled import DataArray, Variable
from .calibration import CalibrationTable
from .filters import FilterChain

__all__ = ["ImagingViewParams", "ImagingViewWorkflow"]


class ImagingViewParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    #: Time-gate frames per pulse window (tomography phase bins); 1 =
    #: plain integrated image.
    frames: int = 4
    toa_low: float = 0.0  # ns, frame-gate axis range
    toa_high: float = 71_000_000.0
    histogram_method: str = "scatter"  # or 'pallas2d' (MXU tiles)


class ImagingViewWorkflow:
    """Events on a logical pixel grid -> dense flat-field-corrected
    2-D image (+ per-frame gate counts), current and cumulative."""

    def __init__(
        self,
        *,
        detector_number: np.ndarray,
        params: ImagingViewParams | None = None,
        calibration: CalibrationTable | None = None,
        primary_stream: str | None = None,
        filters: FilterChain | None = None,
    ) -> None:
        params = params or ImagingViewParams()
        self._params = params
        det = np.asarray(detector_number)
        if det.ndim != 2:
            raise ValueError("detector_number must be a 2-D grid")
        self._ny, self._nx = det.shape
        n_screen = self._ny * self._nx
        # Logical projection: pixel id -> its grid cell (row-major), the
        # detector_view project_logical convention without the packaging.
        lut = np.full(int(det.max()) + 1, -1, dtype=np.int32)
        lut[det.reshape(-1)] = np.arange(n_screen, dtype=np.int32)
        edges = np.linspace(
            params.toa_low, params.toa_high, params.frames + 1
        )
        self._hist = EventHistogrammer(
            toa_edges=edges,
            n_screen=n_screen,
            pixel_lut=lut,
            method=params.histogram_method,
        )
        self._state: HistogramState = self._hist.init_state()
        self._primary_stream = primary_stream
        self._filters = filters or FilterChain()
        self._frame_var = Variable(edges, ("frame",), "ns")
        self._calib: CalibrationTable | None = None
        self._ff_dev = None
        self.publish_epoch = 0
        self._install_flatfield(calibration)
        ny, nx, n_frames = self._ny, self._nx, params.frames

        def publish_program(state, flatfield):
            cum, win = self._hist.views_of(state)  # [n_screen, frames]
            img_win = win.sum(axis=1).reshape(ny, nx)
            img_cum = cum.sum(axis=1).reshape(ny, nx)
            outputs = {
                "image_current": img_win,
                "image_cumulative": img_cum,
                # Flat-field correction: one dense elementwise multiply
                # fused into the publish program (MXU-friendly, zero
                # extra dispatches).
                "image_corrected": img_cum * flatfield,
                "frame_counts_current": win.sum(axis=0),
                "counts_current": win.sum(),
                "counts_cumulative": cum.sum(),
                # The applied correction, on the static channel: layout-
                # constant until a calibration swap re-tokens it.
                "flatfield": flatfield,
            }
            return outputs, self._hist.fold_window(state)

        from ..ops.publish import PackedPublisher

        self._publish = PackedPublisher(
            publish_program, static_keys=("flatfield",), name="imaging"
        )
        self._prefetched_publish: dict | None = None
        assert n_frames == edges.size - 1

    def _install_flatfield(self, calibration: CalibrationTable | None) -> None:
        """Adopt a flat-field table (None = unit correction). Screen
        space: the column length must equal ny*nx. Only __init__ and
        set_flatfield route here (the JGL027 discipline: the device
        constant and its digest move together)."""
        import jax.numpy as jnp

        if calibration is None:
            host = np.ones((self._ny, self._nx), dtype=np.float32)
        else:
            calibration.require("flatfield")
            host = np.asarray(
                calibration.column("flatfield"), dtype=np.float32
            ).reshape(self._ny, self._nx)
        self._calib = calibration
        self._ff_dev = jnp.asarray(host)

    @property
    def calibration(self) -> CalibrationTable | None:
        return self._calib

    @property
    def histogrammer(self) -> EventHistogrammer:
        return self._hist

    def _static_token(self) -> str:
        calib = "none" if self._calib is None else self._calib.digest
        return f"{self._hist.layout_digest}:{calib}"

    # graft: protocol=epoch (ADR 0124: a flat-field swap is a modeled
    # state mutation — publish_epoch must bump before the next frame)
    def set_flatfield(self, calibration: CalibrationTable) -> bool:
        """Swap the flat-field correction live. The map is a publish-
        program ARGUMENT (ADR 0105), so the swap is one device transfer
        — no retrace of the ingest or publish bodies; the static token
        changes, so the readback refetches once, and the serving epoch
        bumps so subscribers resync on a keyframe (counts continue)."""
        try:
            self._install_flatfield(calibration)
        except (KeyError, ValueError):
            return False
        self.publish_epoch += 1
        self._prefetched_publish = None
        CALIBRATION_SWAPS.inc(kind="flatfield")
        return True

    # -- Workflow protocol --------------------------------------------------
    def accumulate(self, data: Mapping[str, Any]) -> None:
        for key, value in data.items():
            if not isinstance(value, StagedEvents):
                continue
            if self._primary_stream is not None and key != self._primary_stream:
                continue
            batch, tag = self._filters.apply(value.batch, value.cache)
            self._state = self._hist.step_batch(
                self._state, batch, cache=value.cache, batch_tag=tag
            )

    def event_ingest(self, stream: str, staged: StagedEvents):
        from .filters import filtered_event_ingest

        return filtered_event_ingest(
            self,
            hist=self._hist,
            filters=self._filters,
            primary_stream=self._primary_stream,
            stream=stream,
            staged=staged,
        )

    def publish_offer(self):
        from ..ops.publish import make_publish_offer

        return make_publish_offer(
            self,
            self._publish,
            (self._state, self._ff_dev),
            static_token=self._static_token(),
            fresh_state=self._hist.init_state,
        )

    def finalize(self) -> dict[str, DataArray]:
        out = self._prefetched_publish
        if out is not None:
            self._prefetched_publish = None
        else:
            out, self._state = self._publish(
                self._state,
                self._ff_dev,
                static_token=self._static_token(),
            )
        y = Variable(np.arange(self._ny + 1, dtype=np.float64), ("y",), "")
        x = Variable(np.arange(self._nx + 1, dtype=np.float64), ("x",), "")
        img_coords = {"y": y, "x": x}
        results = {
            name: DataArray(
                Variable(np.asarray(out[name]), ("y", "x"), unit),
                coords=img_coords,
                name=name,
            )
            for name, unit in (
                ("image_current", "counts"),
                ("image_cumulative", "counts"),
                ("image_corrected", ""),
                ("flatfield", ""),
            )
        }
        results["frame_counts_current"] = DataArray(
            Variable(
                np.asarray(out["frame_counts_current"]), ("frame",), "counts"
            ),
            coords={"frame": self._frame_var},
            name="frame_counts_current",
        )
        for name in ("counts_current", "counts_cumulative"):
            results[name] = DataArray(
                Variable(np.asarray(out[name]), (), "counts"), name=name
            )
        return results

    def clear(self) -> None:
        self._state = self._hist.clear(self._state)
        self._prefetched_publish = None

    # -- state snapshots ----------------------------------------------------
    def state_fingerprint(self) -> str:
        import hashlib
        import json

        h = hashlib.sha1()
        h.update(type(self).__name__.encode())
        h.update(f"{self._ny}x{self._nx}".encode())
        h.update(
            json.dumps(
                self._params.model_dump(exclude={"histogram_method"}),
                sort_keys=True,
            ).encode()
        )
        h.update(self._filters.digest.encode())
        return h.hexdigest()

    def dump_state(self) -> dict[str, np.ndarray]:
        out = EventHistogrammer.dump_state_arrays(self._state)
        out["publish_epoch"] = np.asarray(self.publish_epoch, dtype=np.int64)
        if self._calib is not None:
            out["calibration_version"] = np.asarray(
                self._calib.version, dtype=np.int64
            )
        return out

    def restore_state(self, arrays: dict[str, np.ndarray]) -> bool:
        restored = self._hist.restore_state_arrays(self._state, arrays)
        if restored is None:
            return False
        self._state = restored
        if "publish_epoch" in arrays:
            self.publish_epoch = int(np.asarray(arrays["publish_epoch"]))
        dumped = arrays.get("calibration_version")
        active = None if self._calib is None else self._calib.version
        if dumped is not None and int(np.asarray(dumped)) != active:
            self.publish_epoch += 1
        return True

    @property
    def state(self) -> HistogramState:
        return self._state


#: Wire-schema contract (graftlint trace pass, JGL105 / ADR 0123):
#: output name -> (ndim, dtype); see detector_view/workflow.py.
TICK_WIRE_SCHEMA = {
    "counts_cumulative": (0, "float32"),
    "counts_current": (0, "float32"),
    "flatfield": (2, "float32"),
    "frame_counts_current": (1, "float32"),
    "image_corrected": (2, "float32"),
    "image_cumulative": (2, "float32"),
    "image_current": (2, "float32"),
}

"""Live powder-diffraction focusing on the calibration plane (ADR 0122).

The existing :mod:`..workflows.powder` reduces via a precompiled
(pixel, toa-bin)→d-bin map on the raw-wire device path (combined-publish
only). This family is the calibration plane's flagship consumer and the
second big static-output user (ADR 0113): per-pixel GSAS difc/difa/tzero
columns drive a host TOF→d flatten (:class:`~.calibration.
CalibratedHistogrammer`), so focusing rides the 4-byte flat wire, fused
stepping, the ONE-dispatch tick program (ADR 0114), mesh placement
(ADR 0115) and the serving plane (ADR 0117) exactly like a detector
view. The calibration-derived per-d-bin acceptance publishes on the
STATIC channel — fetched once per calibration digest, served from the
host cache after, refetched exactly once on a swap.

A live recalibration (:meth:`PowderFocusWorkflow.set_calibration`)
keeps accumulated counts (the d bin space is unchanged — the qshared
recalibration rule), re-keys staging + tick program under the new
digest, and bumps the workflow's ``publish_epoch`` so every subscriber
resyncs on ONE epoch-tagged keyframe whose decoded counts CONTINUE —
a calibration handover is a marked boundary, never a silent splice and
never a reset (pinned in tests/workloads/calibration_epoch_test.py).
"""

from __future__ import annotations

import hashlib
import json
from collections.abc import Mapping
from typing import Any

import numpy as np
from pydantic import BaseModel, ConfigDict

from ..ops.histogram import HistogramState
from ..preprocessors.event_data import StagedEvents
from ..utils.labeled import DataArray, Variable
from .calibration import CalibratedHistogrammer, CalibrationTable
from .filters import FilterChain

__all__ = ["PowderFocusParams", "PowderFocusWorkflow"]


class PowderFocusParams(BaseModel):
    model_config = ConfigDict(frozen=True)

    d_bins: int = 400
    d_min: float = 0.4  # angstrom
    d_max: float = 2.8
    #: Focussed output banks (0 = single bank). Per-pixel bank routing
    #: comes from the calibration's optional ``bank`` column.
    #: Histogram kernel (ops/histogram.py): 'scatter' is the safe
    #: default; 'pallas2d' runs the MXU-tiled kernel over the
    #: host-partitioned calibrated wire.
    histogram_method: str = "scatter"


class PowderFocusWorkflow:
    """Detector events -> focussed I(d) via per-pixel calibration LUTs,
    with optional per-event filtering and bank-resolved output."""

    def __init__(
        self,
        *,
        calibration: CalibrationTable,
        params: PowderFocusParams | None = None,
        primary_stream: str | None = None,
        filters: FilterChain | None = None,
    ) -> None:
        params = params or PowderFocusParams()
        self._params = params
        d_edges = np.linspace(params.d_min, params.d_max, params.d_bins + 1)
        bank = calibration.columns.get("bank")
        self._hist = CalibratedHistogrammer(
            calibration=calibration,
            d_edges=d_edges,
            bank_ids=None if bank is None else np.asarray(bank),
            method=params.histogram_method,
        )
        self._n_banks = self._hist.n_screen
        self._state: HistogramState = self._hist.init_state()
        self._primary_stream = primary_stream
        self._filters = filters or FilterChain()
        self._d_var = Variable(d_edges, ("dspacing",), "angstrom")
        self._acceptance_host = self._hist.acceptance()
        self._acceptance_dev = self._staged_acceptance()
        #: Serving-epoch contribution (core/job.py folds it into
        #: JobResult.state_epoch): bumped on every calibration swap so
        #: subscribers resync on a keyframe with CONTINUING counts.
        self.publish_epoch = 0
        n_banks, n_d = self._n_banks, self._hist.n_toa

        def publish_program(state, acceptance):
            cum, win = self._hist.views_of(state)  # [n_banks, n_d]
            d_win = win.sum(axis=0)
            d_cum = cum.sum(axis=0)
            outputs = {
                "dspacing_current": d_win,
                "dspacing_cumulative": d_cum,
                "dspacing_banked_cumulative": cum,
                "counts_current": win.sum(),
                "counts_cumulative": cum.sum(),
                # Calibration-derived acceptance: layout-constant until
                # the calibration swaps — the STATIC channel (ADR 0113).
                "acceptance": acceptance,
            }
            return outputs, self._hist.fold_window(state)

        from ..ops.publish import PackedPublisher

        self._publish = PackedPublisher(
            publish_program, static_keys=("acceptance",),
            name="powder_focus",
        )
        self._prefetched_publish: dict | None = None
        assert self._acceptance_host.shape == (n_banks, n_d)

    def _staged_acceptance(self):
        import jax.numpy as jnp

        return jnp.asarray(
            self._acceptance_host.astype(np.float32)
        )

    # -- calibration lifecycle ---------------------------------------------
    @property
    def calibration(self) -> CalibrationTable:
        return self._hist.calibration

    @property
    def histogrammer(self) -> CalibratedHistogrammer:
        return self._hist

    # graft: protocol=epoch (ADR 0124: a calibration swap is a modeled
    # state mutation — publish_epoch must bump before the next frame)
    def set_calibration(self, table: CalibrationTable) -> bool:
        """Adopt a new calibration epoch live: counts persist, the
        digest re-keys staging/tick/static caches, the acceptance
        rebuilds, and the serving epoch bumps (one keyframe, not a
        reset). Returns False untouched for incompatible tables."""
        if not self._hist.swap_calibration(table):
            return False
        self._acceptance_host = self._hist.acceptance()
        self._acceptance_dev = self._staged_acceptance()
        self.publish_epoch += 1
        # A prefetch from the old epoch must not publish as the new one.
        self._prefetched_publish = None
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
        """Fused-stepping/tick offer (ADR 0114): the filter chain is a
        host batch transform keyed by its digest, so K same-chain jobs
        share one filtered staging and the filtered tick stays ONE
        dispatch — filtering costs zero extra device round trips."""
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
        """Combined/tick publish offer (ADR 0113/0114): args[0] is the
        pre-step state per the make_publish_offer contract; the
        acceptance rides as the static-channel arg with the calibrated
        layout digest as its token — a swap refetches it exactly once."""
        from ..ops.publish import make_publish_offer

        return make_publish_offer(
            self,
            self._publish,
            (self._state, self._acceptance_dev),
            static_token=self._hist.layout_digest,
            fresh_state=self._hist.init_state,
        )

    def _spectrum(self, values, name: str, unit="counts") -> DataArray:
        return DataArray(
            Variable(np.asarray(values), ("dspacing",), unit),
            coords={"dspacing": self._d_var},
            name=name,
        )

    def finalize(self) -> dict[str, DataArray]:
        out = self._prefetched_publish
        if out is not None:
            self._prefetched_publish = None
        else:
            out, self._state = self._publish(
                self._state,
                self._acceptance_dev,
                static_token=self._hist.layout_digest,
            )
        acceptance = np.asarray(out["acceptance"]).sum(axis=0)
        cum = np.asarray(out["dspacing_cumulative"])
        with np.errstate(divide="ignore", invalid="ignore"):
            normalized = np.where(acceptance > 0, cum / np.maximum(acceptance, 1e-30), 0.0)
        bank_idx = Variable(
            np.arange(self._n_banks, dtype=np.int32), ("bank",), ""
        )
        return {
            "dspacing_current": self._spectrum(
                out["dspacing_current"], "dspacing_current"
            ),
            "dspacing_cumulative": self._spectrum(cum, "dspacing_cumulative"),
            "dspacing_focussed": self._spectrum(
                normalized, "dspacing_focussed", unit=""
            ),
            "dspacing_banked_cumulative": DataArray(
                Variable(
                    np.asarray(out["dspacing_banked_cumulative"]),
                    ("bank", "dspacing"),
                    "counts",
                ),
                coords={"dspacing": self._d_var, "bank": bank_idx},
                name="dspacing_banked_cumulative",
            ),
            "acceptance": self._spectrum(acceptance, "acceptance", unit=""),
            "counts_current": DataArray(
                Variable(np.asarray(out["counts_current"]), (), "counts"),
                name="counts_current",
            ),
            "counts_cumulative": DataArray(
                Variable(np.asarray(out["counts_cumulative"]), (), "counts"),
                name="counts_cumulative",
            ),
            "calibration_version": DataArray(
                Variable(
                    np.asarray(self.calibration.version, dtype=np.int64),
                    (),
                    "",
                ),
                name="calibration_version",
            ),
        }

    def clear(self) -> None:
        self._state = self._hist.clear(self._state)
        self._prefetched_publish = None

    # -- state snapshots (core/state_snapshot.py, ADR 0107/0118) ------------
    def state_fingerprint(self) -> str:
        """The BIN SPACE's identity — deliberately NOT the calibration
        bytes (the qshared rule): a recalibration changes where FUTURE
        events land, accumulated bins still mean "counts in d bin k of
        this binning", and counts persist across swaps by design. The
        calibration NAME anchors the family; its version/digest travel
        with the dump instead."""
        h = hashlib.sha1()
        h.update(type(self).__name__.encode())
        h.update(self.calibration.name.encode())
        h.update(np.int64(self._n_banks).tobytes())
        h.update(
            json.dumps(
                self._params.model_dump(exclude={"histogram_method"}),
                sort_keys=True,
            ).encode()
        )
        h.update(self._filters.digest.encode())
        return h.hexdigest()

    def dump_state(self) -> dict[str, np.ndarray]:
        out = self._hist.dump_state_arrays(self._state)
        # The active calibration epoch rides the dump: a restore adopts
        # the version + serving epoch so the restored stream continues
        # under the SAME epoch tag (gap-not-reset across restarts).
        out["calibration_version"] = np.asarray(
            self.calibration.version, dtype=np.int64
        )
        out["publish_epoch"] = np.asarray(self.publish_epoch, dtype=np.int64)
        return out

    def restore_state(self, arrays: dict[str, np.ndarray]) -> bool:
        restored = self._hist.restore_state_arrays(self._state, arrays)
        if restored is None:
            return False
        self._state = restored
        if "publish_epoch" in arrays:
            self.publish_epoch = int(np.asarray(arrays["publish_epoch"]))
        dumped_version = arrays.get("calibration_version")
        if (
            dumped_version is not None
            and int(np.asarray(dumped_version)) != self.calibration.version
        ):
            # Restored counts were accumulated under another calibration
            # epoch; they still mean "counts in d bin k" (fingerprint
            # gate holds), but the handover must be epoch-visible.
            self.publish_epoch += 1
        return True

    @property
    def state(self) -> HistogramState:
        return self._state


#: Wire-schema contract (graftlint trace pass, JGL105 / ADR 0123):
#: output name -> (ndim, dtype); see detector_view/workflow.py.
TICK_WIRE_SCHEMA = {
    "acceptance": (2, "float32"),
    "counts_cumulative": (0, "float32"),
    "counts_current": (0, "float32"),
    "dspacing_banked_cumulative": (2, "float32"),
    "dspacing_cumulative": (1, "float32"),
    "dspacing_current": (1, "float32"),
}

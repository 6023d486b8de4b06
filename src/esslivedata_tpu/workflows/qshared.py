"""Shared streaming plumbing for QHistogrammer-backed reductions.

SANS I(Q) and the Q-E spectrometer map differ only in the precompiled
(pixel, toa-bin) -> bin map and the output formatting; everything
between — aux-monitor counting, monitor-only windows via an empty
padded batch, and the fused single-round-trip publish of the QState —
lives here once.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any

import numpy as np

from ..ops.event_batch import EventBatch
from ..preprocessors.event_data import StagedEvents

__all__ = ["QStreamingMixin", "latest_sample_value"]


def latest_sample_value(sample: Any) -> float | None:
    """Latest numeric value of a context sample (NXlog DataArray latest,
    LogData, or plain scalar) — the one idiom every live-calibration
    consumer shares."""
    if sample is None:
        return None
    values = getattr(sample, "values", sample)
    arr = np.asarray(values).reshape(-1)
    return float(arr[-1]) if arr.size else None


class QStreamingMixin:
    """Requires ``_hist`` (QHistogrammer), ``_state``, ``_primary_stream``,
    ``_monitor_streams`` and ``_publish = None`` set by the subclass.

    An optional second monitor channel (``_transmission_streams``, e.g.
    the SANS transmission monitor, reference loki/specs.py:96) is counted
    host-side: event *counts* are already host data before staging, so a
    scalar channel needs no device round trip. The counters mirror the
    device monitor channel's fold semantics exactly — window zeroed at
    each publish fold, cumulative monotone — so the two channels stay
    comparable across windows.
    """

    _transmission_streams: frozenset[str] = frozenset()
    _trans_win: float = 0.0
    _trans_cum: float = 0.0
    #: Combined-publish hand-off (ADR 0113): outputs prefetched by the
    #: JobManager's fused tick round trip, consumed by ``_take_publish``.
    _prefetched_publish: dict | None = None

    def accumulate(self, data: Mapping[str, Any]) -> None:
        monitor_count = 0.0
        detector: EventBatch | None = None
        det_cache = None
        for key, value in data.items():
            if not isinstance(value, StagedEvents):
                continue
            is_trans = key in self._transmission_streams
            if is_trans:
                self._trans_win += float(value.n_events)
                self._trans_cum += float(value.n_events)
            if key in self._monitor_streams:
                monitor_count += float(value.n_events)
            elif not is_trans and (
                self._primary_stream is None or key == self._primary_stream
            ):
                detector = value.batch
                # Window stream-cache slot: the raw (pixel_id, toa) wire
                # is layout-independent, so K Q-family jobs — and any
                # device-path histogram job — share ONE transfer.
                det_cache = value.cache
        if detector is not None or monitor_count:
            if detector is None:
                # monitor-only window: empty padded batch keeps shapes static
                detector = EventBatch.from_arrays(
                    np.empty(0, dtype=np.int32), np.empty(0, dtype=np.float32)
                )
                det_cache = None
            self._state = self._hist.step(
                self._state, detector, monitor_count, cache=det_cache
            )

    # -- state snapshots (core/state_snapshot.py, ADR 0107) ----------------
    def state_fingerprint(self) -> str:
        """The BIN SPACE's identity, deliberately NOT the table bytes:
        accumulated counts mean "events in bin k of this binning" — a
        live table recalibration (powder emission offset, reflectometry
        omega move) changes where FUTURE events land but not what the
        accumulated bins mean, and these workflows preserve state across
        swaps by design. The bin space is fully determined by the
        workflow class and its params, both available even before a
        context-gated workflow builds its first table."""
        import hashlib

        h = hashlib.sha1()
        h.update(type(self).__name__.encode())
        params = getattr(self, "_params", None)
        if params is not None and hasattr(params, "model_dump_json"):
            h.update(params.model_dump_json().encode())
        return h.hexdigest()

    def dump_state(self) -> dict[str, np.ndarray]:
        if getattr(self, "_state", None) is None:
            # Context-gated workflows (reflectometry before the first
            # sample angle) have nothing to dump yet; an empty dict is
            # skipped by the snapshot writer rather than overwriting a
            # prior useful snapshot.
            return {}
        out = {
            field: np.asarray(getattr(self._state, field))
            for field in self._state._fields
        }
        # The host-side transmission counters share the fold semantics
        # of the device channels and must travel with them.
        out["trans_win"] = np.asarray(self._trans_win)
        out["trans_cum"] = np.asarray(self._trans_cum)
        return out

    def restore_state(self, arrays: dict[str, np.ndarray]) -> bool:
        if getattr(self, "_state", None) is None:
            # No device state to adopt into yet (schedule-time restore of
            # a context-gated workflow). Refusing here is safe: the
            # caller keeps the snapshot file for a later attempt.
            return False
        import jax.numpy as jnp

        from ..ops.qhistogram import QState

        restored = {}
        for field in QState._fields:
            if field not in arrays:
                return False
            value = np.asarray(arrays[field])
            current = getattr(self._state, field)
            if value.shape != current.shape:
                return False
            restored[field] = jnp.asarray(value, dtype=current.dtype)
        self._state = QState(**restored)
        self._trans_win = float(arrays.get("trans_win", 0.0))
        self._trans_cum = float(arrays.get("trans_cum", 0.0))
        return True

    def _publisher(self):
        if self._publish is None:
            from ..ops.publish import PackedPublisher

            def program(state):
                outputs = {
                    "win": state.window,
                    "cum": state.cumulative,
                    "mon_win": state.monitor_window,
                    "mon_cum": state.monitor_cumulative,
                }
                return outputs, self._hist.fold_window(state)

            self._publish = PackedPublisher(program, name="qshared")
        return self._publish

    def event_ingest(self, stream: str, staged: StagedEvents):
        """Fused-stepping/tick offer (core/job_manager.py, ADR 0114):
        the Q family's detector ingest is one table-gather step over
        this job's private state, so a detector-only window steps AND
        publishes in ONE tick dispatch (``QHistogrammer.tick_staging``/
        ``tick_step`` — the PR 6 coverage gap, closed). The fuse key
        carries the kernel's instance token, so Q groups are
        singletons: each job owns its own calibration table, and
        member[0]'s table must never reduce another job's events.
        Monitor/transmission streams decline — their counts fold
        host-side in ``accumulate``, and a window carrying them is not
        tick-eligible anyway (the manager requires a single-stream
        window)."""
        if getattr(self, "_state", None) is None:
            return None  # context-gated workflow before its first table
        if (
            stream in self._monitor_streams
            or stream in self._transmission_streams
        ):
            return None
        if self._primary_stream is not None and stream != self._primary_stream:
            return None
        from ..core.device_event_cache import EventIngest

        def set_state(state) -> None:
            self._state = state

        return EventIngest(
            key=self._hist.fuse_key + ("",),
            hist=self._hist,
            batch=staged.batch,
            batch_tag="",
            get_state=lambda: self._state,
            set_state=set_state,
        )

    def publish_offer(self):
        """Combined-publish offer (ADR 0113): every QHistogrammer-backed
        reduction due in a tick joins the one device round trip; with
        the ingest offer above, a detector-only window upgrades to the
        full tick program (ADR 0114) — step + publish in one dispatch.
        The host-side transmission counters never ride the device
        publish."""
        if getattr(self, "_state", None) is None:
            return None  # context-gated workflow before its first table
        from ..ops.publish import make_publish_offer

        return make_publish_offer(
            self,
            self._publisher(),
            (self._state,),
            fresh_state=self._hist.init_state,
        )

    def _take_publish(self) -> tuple[np.ndarray, np.ndarray, float, float]:
        """One fused publish: (window, cumulative, monitor_window,
        monitor_cumulative) on host; the window folds."""
        out = self._prefetched_publish
        if out is not None:
            self._prefetched_publish = None
        else:
            out, self._state = self._publisher()(self._state)
        return (
            out["win"],
            out["cum"],
            float(out["mon_win"]),
            float(out["mon_cum"]),
        )

    def _take_transmission(self) -> tuple[float, float]:
        """(window, cumulative) transmission-monitor counts; folds the
        window (zeroes it) like ``_take_publish`` folds the device state."""
        win = self._trans_win
        self._trans_win = 0.0
        return win, self._trans_cum

    def clear(self) -> None:
        self._state = self._hist.clear()
        self._trans_win = 0.0
        self._trans_cum = 0.0
        self._prefetched_publish = None


#: Wire-schema contract (graftlint trace pass, JGL105 / ADR 0123) for
#: every QHistogrammer-backed family publishing through _publisher():
#: output name -> (ndim, dtype); see detector_view/workflow.py.
TICK_WIRE_SCHEMA = {
    "cum": (1, "float32"),
    "mon_cum": (0, "float32"),
    "mon_win": (0, "float32"),
    "win": (1, "float32"),
}

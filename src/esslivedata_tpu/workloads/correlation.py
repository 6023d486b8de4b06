"""f144/timeseries correlation analytics (ADR 0122).

A NON-event workload exercising the da00 path (ROADMAP item 4): it
consumes NXlog-style timeseries ``DataArray`` streams — motor positions,
temperatures, chopper delays — and publishes rolling cross-statistics
(mean/std per stream, Pearson correlation matrix) so operators see
*which slow controls move together* live.

Architecture notes:

- The moment accumulator ``(count, sums, sums-of-products)`` is a small
  DEVICE state advanced by one tiny jitted donated step per window —
  deliberately the same state/fold/publish shape as the event families,
  so the workload rides the combined-publish round trip (ADR 0113): K
  correlation jobs due in a tick add ZERO extra fetches. It implements
  ``event_ingest`` (returns None — there is no event wire; documented
  as the protocol's no-op) and ``publish_offer`` (a real offer) like
  every other family.
- Sampling is window-cadenced: each stream's LATEST sample is read per
  window (``latest_sample_value``), and a moment update fires only when
  every correlated stream has reported at least once — correlation of
  partially-aligned vectors would silently bias toward whichever
  stream updates fastest.
"""

from __future__ import annotations

from collections.abc import Mapping, Sequence
from typing import Any, NamedTuple

import numpy as np

from ..utils.labeled import DataArray, Variable
from ..workflows.qshared import latest_sample_value

__all__ = ["CorrelationState", "TimeseriesCorrelationWorkflow"]


class CorrelationState(NamedTuple):
    """Device-resident moment accumulator over n streams."""

    count: Any  # scalar f32
    sums: Any  # [n]
    prods: Any  # [n, n] sums of outer products


class TimeseriesCorrelationWorkflow:
    """Correlate the latest values of N timeseries streams, sampled at
    window cadence, into a live correlation matrix."""

    def __init__(self, *, streams: Sequence[str]) -> None:
        if not streams:
            raise ValueError("correlation needs at least one stream")
        self._streams = tuple(dict.fromkeys(streams))  # ordered, unique
        self._n = len(self._streams)
        self._latest: dict[str, float] = {}
        self._pending = False
        self._state = self._init_state()
        self.publish_epoch = 0

        import jax
        import jax.numpy as jnp

        def step(state, x):
            return CorrelationState(
                count=state.count + 1.0,
                sums=state.sums + x,
                prods=state.prods + jnp.outer(x, x),
            )

        self._step = jax.jit(step, donate_argnums=(0,))

        n = self._n

        def publish_program(state):
            count = jnp.maximum(state.count, 1.0)
            mean = state.sums / count
            cov = state.prods / count - jnp.outer(mean, mean)
            var = jnp.clip(jnp.diag(cov), 0.0, None)
            std = jnp.sqrt(var)
            denom = jnp.outer(std, std)
            enough = (state.count > 1.0) & (denom > 1e-30)
            corr = jnp.where(enough, cov / jnp.where(enough, denom, 1.0), 0.0)
            # Self-correlation reads 1 wherever the stream has variance.
            corr = jnp.where(
                jnp.eye(n, dtype=bool) & (var[:, None] > 0), 1.0, corr
            )
            outputs = {
                "correlation": corr,
                "mean": mean,
                "stddev": std,
                "samples": state.count,
            }
            # Cumulative analytics: the state carries through unchanged
            # (no window fold — correlations sharpen monotonically until
            # a run-boundary reset).
            return outputs, state

        from ..ops.publish import PackedPublisher

        self._publish = PackedPublisher(publish_program, name="correlation")
        self._prefetched_publish: dict | None = None

    def _init_state(self) -> CorrelationState:
        import jax.numpy as jnp

        # Cold path only (construction, run-boundary reset, donation
        # recovery) — never per-window, so the per-call device zeros are
        # not a hot-path dispatch. Fresh buffers are REQUIRED here: the
        # step donates the state, so a cached zero state handed out
        # twice would donate already-deleted arrays.
        return CorrelationState(
            count=jnp.zeros((), dtype=jnp.float32),  # graftlint: disable=JGL006 cold-path fresh state; donation forbids caching
            sums=jnp.zeros((self._n,), dtype=jnp.float32),
            prods=jnp.zeros((self._n, self._n), dtype=jnp.float32),
        )

    @property
    def streams(self) -> tuple[str, ...]:
        return self._streams

    # -- Workflow protocol --------------------------------------------------
    def accumulate(self, data: Mapping[str, Any]) -> None:
        for key, value in data.items():
            if key not in self._streams:
                continue
            if not isinstance(value, (DataArray, int, float, np.ndarray)):
                # Timeseries-only workload: event batches or other
                # window payloads on a shared stream name are not
                # samples (the da00 path is the contract).
                continue
            sample = latest_sample_value(value)
            if sample is not None and np.isfinite(sample):
                self._latest[key] = sample
                self._pending = True
        if self._pending and len(self._latest) == self._n:
            x = np.asarray(
                [self._latest[s] for s in self._streams], dtype=np.float32
            )
            self._state = self._step(self._state, x)
            self._pending = False

    def event_ingest(self, stream: str, staged) -> None:
        """No event wire: this family is the da00-path workload — the
        protocol method exists (every ADR 0122 family implements the
        pair) and declines, so the manager's fused/tick planners skip
        it without special cases."""
        return None

    def publish_offer(self):
        """Combined-publish offer (ADR 0113): the tiny moment state
        joins the tick's one packed fetch — K analytics jobs cost zero
        extra device round trips."""
        from ..ops.publish import make_publish_offer

        return make_publish_offer(
            self,
            self._publish,
            (self._state,),
            fresh_state=self._init_state,
        )

    def finalize(self) -> dict[str, DataArray]:
        out = self._prefetched_publish
        if out is not None:
            self._prefetched_publish = None
        else:
            out, self._state = self._publish(self._state)
        idx = Variable(np.arange(self._n, dtype=np.int32), ("stream",), "")
        idx_b = Variable(np.arange(self._n, dtype=np.int32), ("stream_b",), "")
        return {
            "correlation": DataArray(
                Variable(
                    np.asarray(out["correlation"]),
                    ("stream", "stream_b"),
                    "",
                ),
                coords={"stream": idx, "stream_b": idx_b},
                name="correlation",
            ),
            "mean": DataArray(
                Variable(np.asarray(out["mean"]), ("stream",), ""),
                coords={"stream": idx},
                name="mean",
            ),
            "stddev": DataArray(
                Variable(np.asarray(out["stddev"]), ("stream",), ""),
                coords={"stream": idx},
                name="stddev",
            ),
            "samples": DataArray(
                Variable(np.asarray(out["samples"]), (), "counts"),
                name="samples",
            ),
        }

    def clear(self) -> None:
        self._state = self._init_state()
        self._latest.clear()
        self._pending = False
        self._prefetched_publish = None

    # -- state snapshots ----------------------------------------------------
    def state_fingerprint(self) -> str:
        import hashlib

        h = hashlib.sha1()
        h.update(type(self).__name__.encode())
        for s in self._streams:
            h.update(s.encode())
        return h.hexdigest()

    def dump_state(self) -> dict[str, np.ndarray]:
        out = {
            field: np.asarray(getattr(self._state, field))
            for field in self._state._fields
        }
        out["publish_epoch"] = np.asarray(self.publish_epoch, dtype=np.int64)
        return out

    def restore_state(self, arrays: dict[str, np.ndarray]) -> bool:
        import jax.numpy as jnp

        restored = {}
        for field in CorrelationState._fields:
            if field not in arrays:
                return False
            value = np.asarray(arrays[field])
            current = getattr(self._state, field)
            if value.shape != current.shape:
                return False
            restored[field] = jnp.asarray(value, dtype=current.dtype)
        self._state = CorrelationState(**restored)
        if "publish_epoch" in arrays:
            self.publish_epoch = int(np.asarray(arrays["publish_epoch"]))
        return True


#: Wire-schema contract (graftlint trace pass, JGL105 / ADR 0123):
#: output name -> (ndim, dtype); see detector_view/workflow.py.
TICK_WIRE_SCHEMA = {
    "correlation": (2, "float32"),
    "mean": (1, "float32"),
    "samples": (0, "float32"),
    "stddev": (1, "float32"),
}

"""Link monitor: EWMA bandwidth/RTT estimates driving ingest adaptation.

A fixed batch size, wire format, pipeline depth and publish cadence are
tuned for one host→device bandwidth and one publish round-trip time.
This module estimates both from real work and adapts the four to them
(ADR 0111). Whether its thresholds ever leave their dead zone on a
host-attached chip is ROADMAP D6's question; the policy runs as built:

- **Estimation costs nothing on the hot path.** There are no probes.
  Bandwidth observations are the wall time of real staging work
  (``DeviceEventCache`` times each stage-once miss and reports the bytes
  it moved); RTT observations are the wall time of real publishes (one
  execute + one fetch = one device round trip, ``ops/publish.py``) —
  or, on the tick-program fast path (``ops/tick.py``, ADR 0114), of the
  whole step+publish tick, which IS the round trip a steady-state
  window pays. Compile rounds are excluded on both paths (the
  combiner's and the tick combiner's ``last_compiled``).
  Both fold into exponentially weighted moving averages under a lock —
  observations arrive from stage workers, publish timings from the step
  worker, and the 30 s metrics reader from the service thread.

  The bandwidth estimate is *effective ingest throughput* — host
  flatten + transfer, the number the policy must react to — not a pure
  wire measurement. On a host-bound day it saturates at the flatten
  rate, which is exactly when batch scaling stops helping; the policy
  thresholds are set against the transfer-bound regime where adaptation
  pays.

- **Policy with hysteresis.** :meth:`policy` maps the estimates to a
  :class:`LinkPolicy`:

  (a) ``window_scale`` — the batch-size target multiplier fed to the
      batcher (``RateAwareMessageBatcher.set_window`` when available;
      the adaptive batcher reacts through ``report_processing_time``
      backpressure either way). A degraded link amortizes per-batch
      fixed costs (dispatch, publish round trip) over more events —
      trading batch latency for link efficiency; a healthy link opens
      the throttle back to the base window.
  (b) ``compact_wire`` — the uint16 partitioned wire (ADR 0108):
      2 B/event instead of 4 doubles the link-bound ceiling. ``True``
      *forces* compact on every eligible histogrammer during prestage
      (``EventHistogrammer.set_wire_format``); ``None`` — the healthy
      state — leaves each histogrammer's construction-time default
      untouched (ADR 0108 already picks compact wherever offsets fit;
      the policy must never silently revert that to the wide wire).
  (c) ``depth`` — in-flight window bound for the pipeline
      (``core/ingest_pipeline.py``): a degraded or high-RTT link wants
      more windows in flight to keep the transfer stage fed; a healthy
      link wants the shallow bound for latency.
  (d) ``publish_coalesce`` — the publish-tick width (ADR 0113, applied
      via ``JobManager.set_publish_coalesce``): when the EWMA publish
      RTT alone approaches the ingest->publish budget, finalize runs
      only every Nth window so the (combined, one-per-device) publish
      round trip amortizes over more accumulation; healthy-RTT days
      keep N = 1 for latency. Hysteresis-latched like the other axes.

  The degraded latch flips on below ``degraded_bandwidth_bps`` and off
  only above ``recover_factor`` times that — the dead zone prevents the
  policy from flapping across a noisy threshold, the same shape as
  ``LoadGovernor``'s escalate/relax bands.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass

#: Shared instrument (telemetry/instruments.py — defined there so a
#: serial service, which never imports this module, still exposes the
#: family). Recorded outside the monitor's lock: the instrument has its
#: own, and telemetry must never extend the estimator's critical
#: section.
from ..telemetry.instruments import PUBLISH_RTT_SECONDS as _RTT_SECONDS

__all__ = ["LinkMonitor", "LinkPolicy"]


@dataclass(frozen=True, slots=True)
class LinkPolicy:
    """One consistent adaptation decision (see module docstring)."""

    #: Multiplier on the batcher's base window (>= 1.0).
    window_scale: float
    #: True = force the uint16 compact partitioned wire (ADR 0108);
    #: None = leave each histogrammer's construction default untouched.
    compact_wire: bool | None
    #: In-flight window bound for the ingest pipeline.
    depth: int
    #: Publish-coalescing window (ADR 0113): finalize/publish only every
    #: Nth data window. 1 = publish every window (healthy RTT); a
    #: slow publish round trip widens the tick so it amortizes over
    #: more accumulation.
    publish_coalesce: int = 1
    #: Fan-out demand axis (ADR 0117): the serving tier's contribution
    #: to ``publish_coalesce``. > 1 when nobody has been watching the
    #: broadcast plane for the idle grace period (publish work nobody
    #: consumes is pure load) or when every attached consumer is
    #: drowning (pressure latch). 1 = live demand at normal pressure —
    #: publish cadence stays RTT-governed. Already folded into
    #: ``publish_coalesce``; exposed so stats/telemetry name the axis.
    fanout_coalesce: int = 1


class LinkMonitor:
    """Thread-safe EWMA link estimator + adaptation policy."""

    def __init__(
        self,
        *,
        target_bandwidth_bps: float = 4.0e8,
        degraded_bandwidth_bps: float = 1.5e8,
        recover_factor: float = 2.0,
        rtt_deep_s: float = 0.03,
        rtt_coalesce_s: float = 0.05,
        max_publish_coalesce: int = 8,
        fanout_idle_coalesce: int = 4,
        fanout_idle_grace_s: float = 10.0,
        fanout_pressure_high: float = 0.75,
        fanout_pressure_low: float = 0.25,
        alpha: float = 0.25,
        max_window_scale: float = 8.0,
        base_depth: int = 2,
        max_depth: int = 4,
    ) -> None:
        if not 0.0 < alpha <= 1.0:
            raise ValueError("alpha must be in (0, 1]")
        if recover_factor < 1.0:
            raise ValueError("recover_factor must be >= 1.0")
        #: 4e8 B/s is the bandwidth that sustains the 1e8 ev/s target at
        #: the 4 B/event flat wire (PERF.md) — at or above it there is
        #: nothing to adapt.
        self._target = float(target_bandwidth_bps)
        self._degraded = float(degraded_bandwidth_bps)
        self._recover = float(degraded_bandwidth_bps) * float(recover_factor)
        self._recover_factor = float(recover_factor)
        self._rtt_deep = float(rtt_deep_s)
        #: Publish-coalescing latch threshold (ADR 0113): above this
        #: publish RTT the round trip alone dominates a ~1 Hz tick, so
        #: the policy widens the publish window; the latch releases only
        #: below ``rtt_coalesce_s / recover_factor`` — the same dead-zone
        #: shape as the bandwidth latch, so a noisy RTT can't flap the
        #: publish cadence.
        self._rtt_coalesce = float(rtt_coalesce_s)
        self._max_coalesce = max(1, int(max_publish_coalesce))
        self._alpha = float(alpha)
        self._max_scale = float(max_window_scale)
        self._base_depth = int(base_depth)
        self._max_depth = max(int(max_depth), int(base_depth))
        self._lock = threading.Lock()
        self._bw_bps: float | None = None
        self._rtt_s: float | None = None
        #: Per-mesh-slice publish RTT EWMAs (ADR 0115): a multi-slice
        #: service publishes concurrently from several devices, and one
        #: congested slice must widen the publish tick even while the
        #: others look healthy — the policy reads the WORST slice.
        #: Entries carry their last-observation time and expire after
        #: ``_SLICE_TTL_S``: a slice whose jobs stopped must not pin
        #: the worst-slice RTT (and the coalesce latch) forever with
        #: its final congested estimate.
        self._rtt_by_slice: dict[str, tuple[float, float]] = {}
        self._degraded_latch = False
        self._coalesce_latch = False
        self._n_staging = 0
        self._n_publish = 0
        self._bytes_observed = 0
        #: Fan-out demand axis (ADR 0117), fed by the broadcast plane
        #: through ``observe_fanout``. ``None`` subscribers = no serving
        #: plane has ever reported — the axis stays neutral, so a
        #: deployment without a serve port behaves exactly as before.
        #: Idle entry is time-latched (``fanout_idle_grace_s`` of
        #: continuous zero-subscriber reports) so a dashboard reconnect
        #: blip cannot flap the publish cadence; attach releases
        #: INSTANTLY — a viewer must never wait out a hysteresis band
        #: for fresh data. Queue pressure uses a high/low dead zone like
        #: every other latch here.
        self._fanout_idle_coalesce = max(1, int(fanout_idle_coalesce))
        self._fanout_idle_grace_s = float(fanout_idle_grace_s)
        self._fanout_pressure_high = float(fanout_pressure_high)
        self._fanout_pressure_low = float(fanout_pressure_low)
        self._fanout_subscribers: int | None = None
        self._fanout_pressure = 0.0
        self._fanout_idle_since: float | None = None
        self._fanout_pressure_latch = False

    # -- observations ------------------------------------------------------
    def observe_staging(self, nbytes: int, seconds: float) -> None:
        """Fold one staging event (bytes moved over wall seconds) in."""
        if nbytes <= 0 or seconds <= 0.0:
            return
        sample = nbytes / seconds
        with self._lock:
            self._n_staging += 1
            self._bytes_observed += int(nbytes)
            self._bw_bps = (
                sample
                if self._bw_bps is None
                else self._alpha * sample + (1.0 - self._alpha) * self._bw_bps
            )

    def observe_publish(
        self,
        seconds: float,
        *,
        compiled: bool = False,
        slice_key: str | None = None,
    ) -> None:
        """Fold one publish round trip's wall time in.

        The observation is the wall time of one real execute+fetch pair
        — a combined publish (ADR 0113) or a whole tick program
        (ops/tick.py, ADR 0114: step AND publish in the one dispatch, so
        the sample is the full device round trip a steady-state tick
        pays). The groups of a tick are dispatched before the first is
        collected, and a group's sample is the host's time in its two
        halves, the dispatch plus the wait at the collect: the round
        trip as before for a group dispatched alone, and for one
        dispatched ahead what of it the tick still waited for, the
        programs queued before it included
        (``JobManager._run_tick_programs``). Compile rounds (``PublishCombiner.last_compiled`` / the tick
        handle's ``compiled``) are one-off XLA work worth
        hundreds of ms and must never reach the EWMA — a first-tick
        compile or a layout-swap/wire-flip recompile would otherwise
        latch the publish-coalescing policy on a healthy link. Two ways
        to exclude them, by caller kind: the JobManager SKIPS the call
        when the round compiled (the observer slot is duck-typed —
        a stub observer need not accept this kwarg), while direct
        LinkMonitor users pass ``compiled=True`` and this method drops
        the sample. Both are load-bearing; a timing that might include
        compilation must take one of them.

        ``slice_key`` (mesh serving, ADR 0115) attributes the sample to
        the mesh slice that executed the tick; per-slice EWMAs feed the
        policy's worst-slice RTT so one congested device widens the
        publish tick even while the others look healthy. Sliceless
        samples (single-device deployments) keep the single estimate.
        """
        if compiled or seconds <= 0.0:
            return
        _RTT_SECONDS.observe(
            seconds, slice="all" if slice_key is None else str(slice_key)
        )
        with self._lock:
            self._n_publish += 1
            self._rtt_s = (
                seconds
                if self._rtt_s is None
                else self._alpha * seconds + (1.0 - self._alpha) * self._rtt_s
            )
            if slice_key is not None:
                now = time.monotonic()
                entry = self._rtt_by_slice.get(slice_key)
                prev = None if entry is None else entry[0]
                self._rtt_by_slice[slice_key] = (
                    (
                        seconds
                        if prev is None
                        else self._alpha * seconds
                        + (1.0 - self._alpha) * prev
                    ),
                    now,
                )

    def observe_fanout(
        self, subscribers: int, queue_pressure: float
    ) -> None:
        """Fold one broadcast-plane QoS report in (ADR 0117).

        ``subscribers`` is the attached-consumer count,
        ``queue_pressure`` the worst per-subscriber send-queue fill in
        [0, 1] (``BroadcastServer.qos``). Zero subscribers starts the
        idle clock (publish coalescing backs off once it has run
        ``fanout_idle_grace_s``); any subscriber clears it immediately
        — cadence tightens the moment a viewer attaches.
        """
        now = time.monotonic()
        with self._lock:
            subscribers = max(0, int(subscribers))
            self._fanout_pressure = min(1.0, max(0.0, float(queue_pressure)))
            if subscribers == 0:
                if (
                    self._fanout_subscribers is None
                    or self._fanout_subscribers > 0
                ):
                    self._fanout_idle_since = now
            else:
                self._fanout_idle_since = None
            self._fanout_subscribers = subscribers

    # -- estimates ---------------------------------------------------------
    def bandwidth_bps(self) -> float | None:
        with self._lock:
            return self._bw_bps

    #: Per-slice RTT entries expire this long after their last sample:
    #: long against any publish cadence (ticks are ~1 Hz, coalesced at
    #: most 8x), short against a service lifetime — a retired slice
    #: stops gating the policy within a minute.
    _SLICE_TTL_S = 60.0

    def rtt_s(self, slice_key: str | None = None) -> float | None:
        with self._lock:
            if slice_key is not None:
                entry = self._rtt_by_slice.get(slice_key)
                return None if entry is None else entry[0]
            return self._rtt_s

    def _policy_rtt_locked(self) -> float | None:
        """The RTT the adaptation policy reacts to (caller holds the
        lock): the WORST live per-slice estimate when slices report —
        the publish tick must widen for the slowest slice, not the mean
        — else the single global estimate. Expired slices (no sample
        within the TTL: their jobs stopped or migrated) are pruned here
        so a dead slice's last congested estimate cannot latch the
        coalescing policy forever."""
        if self._rtt_by_slice:
            cutoff = time.monotonic() - self._SLICE_TTL_S
            for key in [
                k
                for k, (_, seen) in self._rtt_by_slice.items()
                if seen < cutoff
            ]:
                del self._rtt_by_slice[key]
        if self._rtt_by_slice:
            worst = max(rtt for rtt, _ in self._rtt_by_slice.values())
            if self._rtt_s is None:
                return worst
            return max(worst, self._rtt_s)
        return self._rtt_s

    # -- policy ------------------------------------------------------------
    def policy(self) -> LinkPolicy:
        """The current adaptation decision; neutral until the first
        staging observation converges the bandwidth estimate."""
        with self._lock:
            return self._policy_locked()

    def _policy_locked(self) -> LinkPolicy:
        """Policy computation under the caller's lock acquisition —
        shared by :meth:`policy` and :meth:`stats` so a stats snapshot
        is ONE coherent read (policy fields and raw estimates from the
        same critical section; see the stats docstring)."""
        bw = self._bw_bps
        rtt = self._policy_rtt_locked()
        fanout = self._fanout_coalesce_locked()
        coalesce = self._publish_coalesce_locked(rtt, fanout)
        if bw is None:
            return LinkPolicy(
                window_scale=1.0,
                compact_wire=None,
                depth=self._base_depth,
                publish_coalesce=coalesce,
                fanout_coalesce=fanout,
            )
        if self._degraded_latch:
            if bw >= self._recover:
                # graftlint: disable=JGL012 caller holds self._lock
                self._degraded_latch = False
        elif bw < self._degraded:
            # graftlint: disable=JGL012 caller holds self._lock
            self._degraded_latch = True
        degraded = self._degraded_latch
        # Continuous target quantized to sqrt(2) steps: the batcher
        # regates streams on every window change, so a smoothly
        # drifting estimate must not retarget every batch.
        raw = min(self._max_scale, max(1.0, self._target / bw))
        step = round(math.log(raw, math.sqrt(2.0)))
        scale = min(self._max_scale, max(1.0, math.sqrt(2.0) ** step))
        deep = degraded or (rtt is not None and rtt > self._rtt_deep)
        return LinkPolicy(
            window_scale=scale,
            compact_wire=True if degraded else None,
            depth=self._max_depth if deep else self._base_depth,
            publish_coalesce=coalesce,
            fanout_coalesce=fanout,
        )

    def _fanout_coalesce_locked(self) -> int:
        """The fan-out demand contribution to publish coalescing
        (caller holds the lock; ADR 0117). Neutral (1) until a serving
        plane reports. Zero subscribers for the idle grace period →
        ``fanout_idle_coalesce`` (publish ticks nobody consumes are
        pure load); an attach releases instantly. With live
        subscribers, sustained worst-queue pressure over the high
        watermark latches a mild widening (2) until pressure falls
        under the low watermark — publishing less often is the only
        lever that helps a consumer that cannot drain."""
        if self._fanout_subscribers is None:
            return 1
        if self._fanout_subscribers == 0:
            since = self._fanout_idle_since
            if (
                since is not None
                and time.monotonic() - since >= self._fanout_idle_grace_s
            ):
                return min(self._max_coalesce, self._fanout_idle_coalesce)
            return 1
        if self._fanout_pressure_latch:
            if self._fanout_pressure < self._fanout_pressure_low:
                # graftlint: disable=JGL012 caller holds self._lock
                self._fanout_pressure_latch = False
        elif self._fanout_pressure > self._fanout_pressure_high:
            # graftlint: disable=JGL012 caller holds self._lock
            self._fanout_pressure_latch = True
        return 2 if self._fanout_pressure_latch else 1

    def _publish_coalesce_locked(
        self, rtt: float | None, fanout: int = 1
    ) -> int:
        """The RTT-adaptive publish-coalescing window (caller holds the
        lock). Latched with a dead zone; while latched the window is the
        RTT over the latch threshold, doubled and quantized to the
        NEAREST power of two (floor 2) — a barely-over-threshold 51 ms
        RTT coalesces 2 windows, an 88 ms RTT 4, a 200 ms RTT 8
        (capped). ``fanout`` (ADR 0117) is the demand axis: the widest
        of the two wins, so an unwatched service backs off even at a
        healthy RTT and a slow round trip keeps its RTT width even with
        viewers attached."""
        # "_locked" contract: every caller (policy, and stats through
        # policy) already holds self._lock around this call.
        if rtt is not None:
            if self._coalesce_latch:
                if rtt <= self._rtt_coalesce / self._recover_factor:
                    # graftlint: disable=JGL012 caller holds self._lock
                    self._coalesce_latch = False
            elif rtt > self._rtt_coalesce:
                # graftlint: disable=JGL012 caller holds self._lock
                self._coalesce_latch = True
        rtt_width = 1
        if rtt is not None and self._coalesce_latch:
            raw = max(2.0, 2.0 * rtt / self._rtt_coalesce)
            rtt_width = min(self._max_coalesce, 1 << round(math.log2(raw)))
        return min(self._max_coalesce, max(rtt_width, fanout))

    def stats(self) -> dict[str, float | int | bool | None]:
        """Snapshot for the 30 s metrics line and the telemetry
        collector — ONE lock acquisition for the whole read. The old
        shape (``self.policy()`` then re-acquire for the raw fields)
        could interleave with observations between the two critical
        sections and report policy fields computed from DIFFERENT state
        than the latches/estimates next to them — e.g. ``degraded:
        True`` beside ``compact_wire: None``, an impossible pairing
        that sends an operator chasing a phantom policy bug. Pinned by
        the stats-coherence lock hammer in tests/core/link_monitor_test.
        """
        with self._lock:
            policy = self._policy_locked()
            return {
                "bandwidth_bps": self._bw_bps,
                "rtt_s": self._rtt_s,
                "rtt_by_slice": {
                    k: rtt for k, (rtt, _) in self._rtt_by_slice.items()
                },
                "n_staging": self._n_staging,
                "n_publish": self._n_publish,
                "bytes_observed": self._bytes_observed,
                "degraded": self._degraded_latch,
                "window_scale": policy.window_scale,
                "compact_wire": policy.compact_wire,
                "depth": policy.depth,
                "publish_coalesce": policy.publish_coalesce,
                "fanout_coalesce": policy.fanout_coalesce,
                "fanout_subscribers": self._fanout_subscribers,
                "fanout_pressure": self._fanout_pressure,
            }

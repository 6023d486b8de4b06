"""Behavioral tests for the rate-aware batcher, mirroring the reference's
test scenarios (rate estimation, slot gating, timeout, gap recovery,
eviction, hostile timestamps) without porting its tests."""

from __future__ import annotations

import pytest

from esslivedata_tpu.core.message import Message, StreamId, StreamKind
from esslivedata_tpu.core.rate_aware_batcher import (
    EVICT_AFTER_ABSENT,
    PeriodEstimator,
    RateAwareMessageBatcher,
)
from esslivedata_tpu.core.timestamp import Duration, Timestamp

DET = StreamId(kind=StreamKind.DETECTOR_EVENTS, name="det0")
MON = StreamId(kind=StreamKind.MONITOR_EVENTS, name="mon0")
LOG = StreamId(kind=StreamKind.LOG, name="temp")

NS = 1_000_000_000


def msg(stream: StreamId, t_ns: int) -> Message:
    return Message(timestamp=Timestamp.from_ns(t_ns), stream=stream, value=t_ns)


def pulses(stream: StreamId, start_ns: int, n: int, period_ns: int) -> list[Message]:
    return [msg(stream, start_ns + i * period_ns) for i in range(n)]


class TestPeriodEstimator:
    def test_unconverged_below_min_diffs(self):
        est = PeriodEstimator()
        for t in (0, NS, 2 * NS):
            est.observe(t)
        assert est.integer_rate_hz is None

    def test_snaps_to_integer_hz(self):
        est = PeriodEstimator()
        period = round(NS / 14)
        for i in range(10):
            est.observe(i * period)
        assert est.integer_rate_hz == 14

    def test_robust_to_missed_pulses(self):
        est = PeriodEstimator()
        period = round(NS / 14)
        # Every third pulse missing: diffs alternate 1x and 2x the period.
        ts, t = [], 0
        for i in range(20):
            t += period * (2 if i % 3 == 0 else 1)
            ts.append(t)
        for t in ts:
            est.observe(t)
        assert est.integer_rate_hz == 14

    def test_split_messages_zero_diffs_filtered(self):
        est = PeriodEstimator()
        for i in range(8):
            est.observe(i * NS)
            est.observe(i * NS)  # duplicate timestamp: split message
        assert est.integer_rate_hz == 1

    def test_non_integer_rate_rejected(self):
        est = PeriodEstimator()
        period = round(NS / 0.85)  # 0.85 Hz must not snap to 1 Hz
        for i in range(10):
            est.observe(i * period)
        assert est.integer_rate_hz is None


class TestSlotGating:
    def test_batch_closes_when_last_slot_filled(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        period = round(NS / 14)
        # Bootstrap flushes the backlog as batch 0 and opens the window.
        first = b.batch(pulses(DET, 0, 8, period))
        assert first is not None
        t0 = 7 * period  # window opens at the max bootstrap timestamp
        # Pulses that fill all but the last slot: no close.
        assert b.is_gating(DET)
        mid = b.batch(pulses(DET, t0 + period, 12, period))
        assert mid is None
        # A message in the last expected slot closes the batch.
        out = b.batch([msg(DET, t0 + 14 * period)])
        assert out is not None
        assert len(out.messages) >= 12

    def test_non_gated_streams_never_block(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        period = round(NS / 14)
        b.batch(pulses(DET, 0, 8, period))
        # Log stream flows opportunistically and is not tracked as gating.
        b.batch([msg(LOG, 8 * period)])
        assert not b.is_gating(LOG)

    def test_two_gated_streams_both_must_fill(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        det_p = round(NS / 14)
        mon_p = round(NS / 7)
        boot = pulses(DET, 0, 8, det_p) + pulses(MON, 0, 8, mon_p)
        b.batch(boot)
        t0 = max(m.timestamp.ns for m in boot)
        assert b.is_gating(DET) and b.is_gating(MON)
        # Fill detector's window fully but monitor only partially: no close
        # (timeout not reached since data time stays within 1.2 windows).
        out = b.batch(pulses(DET, t0 + det_p, 14, det_p))
        assert out is None
        out = b.batch(pulses(MON, t0 + mon_p, 7, mon_p))
        assert out is not None


class TestTimeoutPath:
    def test_hwm_timeout_closes_stalled_batch(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0), timeout_factor=1.2)
        period = round(NS / 14)
        b.batch(pulses(DET, 0, 8, period))
        t0 = 7 * period
        # Detector stalls; a non-gated stream's clock advances past the
        # timeout threshold and forces the close.
        assert b.batch([msg(LOG, t0 + NS)]) is None
        out = b.batch([msg(LOG, t0 + 2 * NS)])
        assert out is not None

    def test_far_future_timestamp_cannot_pin_hwm(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        period = round(NS / 14)
        b.batch(pulses(DET, 0, 8, period))
        # One insane timestamp (a year ahead) must not cause an unbounded
        # cascade of empty timeout closes: HWM is clamped near the window.
        year_ns = 365 * 24 * 3600 * NS
        b.batch([msg(LOG, year_ns)])
        closes = 0
        for _ in range(1000):
            if b.batch([]) is not None:
                closes += 1
        # The clamp bounds the cascade of timeout closes to a handful
        # (self-healing: each close advances the window toward the clamped
        # HWM) instead of one per window for a year's worth of windows.
        assert closes <= 3


class TestGapRecovery:
    def test_window_jumps_past_silence(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        period = round(NS / 14)
        b.batch(pulses(DET, 0, 8, period))
        t0 = 7 * period
        b.batch(pulses(DET, t0 + period, 14, period))  # may buffer
        # Long silence, then traffic 100 s later: the batcher must not emit
        # ~100 empty windows; it jumps.
        late_start = t0 + 100 * NS
        emitted = []
        for i in range(30):
            out = b.batch(pulses(DET, late_start + i * 14 * period, 14, period))
            if out is not None:
                emitted.append(out)
        assert emitted  # batches resumed
        # The jump must not manifest as a flood of *empty* windows covering
        # the 100 s of silence; nearly every emitted batch carries data.
        assert sum(1 for b_ in emitted if not b_.messages) <= 2


class TestEviction:
    def test_absent_stream_evicted(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        det_p = round(NS / 14)
        boot = pulses(DET, 0, 8, det_p) + pulses(MON, 0, 8, det_p)
        b.batch(boot)
        t0 = max(m.timestamp.ns for m in boot)
        assert MON in b.tracked_streams
        # Monitor goes silent; detector keeps closing batches via timeout
        # (monitor gate blocks slot-closes, HWM advances with det traffic).
        t = t0
        for _ in range(EVICT_AFTER_ABSENT + 6):
            t += 2 * NS
            b.batch(pulses(DET, t, 14, det_p))
        assert MON not in b.tracked_streams


class TestBootstrap:
    def test_first_call_flushes_backlog(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        backlog = pulses(DET, 0, 5, NS // 14) + [msg(LOG, 2 * NS)]
        out = b.batch(backlog)
        assert out is not None
        assert len(out.messages) == 6
        assert out.start.ns == 0

    def test_empty_poll_before_bootstrap(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        assert b.batch([]) is None


def overload(b: RateAwareMessageBatcher) -> None:
    """Two batches over the governor's high-load mark: it doubles the
    scale, and the batcher holds the wider window for its next batch."""
    for _ in range(2):
        b.report_processing_time(Duration.from_s(0.9))
    assert b.scale == 2.0


class TestGovernedWindow:
    def test_window_change_applies_at_next_batch(self):
        b = RateAwareMessageBatcher(Duration.from_s(1.0))
        overload(b)
        assert b.window == Duration.from_s(1.0)  # active batch unchanged
        period = round(NS / 14)
        b.batch(pulses(DET, 0, 8, period))
        t0 = 7 * period
        b.batch(pulses(DET, t0 + period, 15, period))  # close one batch
        assert b.window == Duration.from_s(2.0)


@pytest.mark.parametrize("kind", [StreamKind.LOG, StreamKind.DEVICE])
def test_only_event_kinds_gate(kind):
    b = RateAwareMessageBatcher(Duration.from_s(1.0))
    sid = StreamId(kind=kind, name="x")
    b.batch([msg(sid, i * NS // 14) for i in range(8)])
    assert not b.is_gating(sid)


def test_pending_window_does_not_resize_closing_batch():
    """A pending window change must not retroactively resize the batch
    being closed (its end stays start + the window it was opened with)."""
    b = RateAwareMessageBatcher(Duration.from_s(1.0))
    period = round(NS / 14)
    b.batch(pulses(DET, 0, 8, period))
    t0 = 7 * period
    overload(b)
    out = None
    t = t0 + period
    while out is None:
        out = b.batch(pulses(DET, t, 14, period))
        t += 14 * period
    assert (out.end - out.start).ns == NS  # closed with the 1 s window

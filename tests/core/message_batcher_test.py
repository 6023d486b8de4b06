import math

from esslivedata_tpu.core import Duration, Message, StreamId, StreamKind, Timestamp
from esslivedata_tpu.core.constants import PULSE_PERIOD_NS_DEN, PULSE_PERIOD_NS_NUM
from esslivedata_tpu.core.message_batcher import (
    AdaptiveMessageBatcher,
    BatchHold,
    LoadGovernor,
    NaiveMessageBatcher,
    SimpleMessageBatcher,
)
from esslivedata_tpu.telemetry import REGISTRY

STREAM = StreamId(kind=StreamKind.DETECTOR_EVENTS, name="bank0")


def msg(pulse: int, offset_ns: int = 0) -> Message:
    ts = Timestamp.from_pulse_index(pulse) + Duration.from_ns(offset_ns)
    return Message(timestamp=ts, stream=STREAM, value=pulse)


def pulses(window_s: float) -> int:
    return round(window_s * PULSE_PERIOD_NS_DEN * 1e9 / PULSE_PERIOD_NS_NUM)


class TestNaive:
    def test_empty_returns_none(self):
        assert NaiveMessageBatcher().batch([]) is None

    def test_batch_bounds_quantized(self):
        b = NaiveMessageBatcher().batch([msg(3, 5), msg(5, 2)])
        assert b is not None
        assert b.start == Timestamp.from_pulse_index(3)
        assert b.end == Timestamp.from_pulse_index(6)
        assert len(b) == 2

    def test_on_grid_message_contained(self):
        b = NaiveMessageBatcher().batch([msg(4)])
        assert b.start <= msg(4).timestamp < b.end


class TestSimple:
    def test_no_emission_until_window_passed(self):
        batcher = SimpleMessageBatcher(Duration.from_s(1.0))
        assert batcher.batch([msg(0), msg(5)]) is None
        assert batcher.batch([]) is None

    def test_window_closed_by_next_window_message(self):
        batcher = SimpleMessageBatcher(Duration.from_s(1.0))
        w = 14  # 1 s = 14 pulses
        assert batcher.batch([msg(0), msg(5)]) is None
        batch = batcher.batch([msg(w)])  # first message of next window
        assert batch is not None
        assert [m.value for m in batch.messages] == [0, 5]
        assert batch.start == Timestamp.from_pulse_index(0)
        assert batch.end == Timestamp.from_pulse_index(w)

    def test_trigger_message_stays_buffered(self):
        batcher = SimpleMessageBatcher(Duration.from_s(1.0))
        w = 14
        batcher.batch([msg(0)])
        batcher.batch([msg(w)])
        batch = batcher.batch([msg(2 * w)])
        assert [m.value for m in batch.messages] == [w]

    def test_late_message_folded_into_next_batch(self):
        batcher = SimpleMessageBatcher(Duration.from_s(1.0))
        w = 14
        batcher.batch([msg(0)])
        first = batcher.batch([msg(w)])
        assert [m.value for m in first.messages] == [0]
        # late message from the already-closed first window
        batcher.batch([msg(3)])
        second = batcher.batch([msg(2 * w)])
        assert sorted(m.value for m in second.messages) == [3, w]

    def test_windows_stay_aligned_after_gap(self):
        batcher = SimpleMessageBatcher(Duration.from_s(1.0))
        w = 14
        batcher.batch([msg(0)])
        batcher.batch([msg(10 * w + 3)])  # long gap; closes window 0
        batch = batcher.batch([msg(11 * w)])
        assert batch.start == Timestamp.from_pulse_index(10 * w)
        assert batch.end == Timestamp.from_pulse_index(11 * w)
        assert [m.value for m in batch.messages] == [10 * w + 3]


class TestAdaptive:
    def make(self, **kw):
        self.now = 0.0
        kw.setdefault("clock", lambda: self.now)
        return AdaptiveMessageBatcher(Duration.from_s(1.0), **kw)

    def drive_windows(self, batcher, start_pulse, n, step=14):
        """Feed one message per window to force closes; return batches."""
        out = []
        p = start_pulse
        for _ in range(n):
            p += step
            b = batcher.batch([msg(p)])
            if b:
                out.append(b)
        return out

    def test_escalates_after_two_overloaded(self):
        batcher = self.make()
        assert batcher.scale == 1.0
        batcher.report_processing_time(Duration.from_s(0.9))
        assert batcher.scale == 1.0
        batcher.report_processing_time(Duration.from_s(0.9))
        assert batcher.scale == 2.0

    def test_deescalates_after_three_underloaded(self):
        batcher = self.make()
        for _ in range(2):
            batcher.report_processing_time(Duration.from_s(0.9))
        assert batcher.scale == 2.0
        # Window doubling happens on the *next* opened window; emulate that
        # the wider window is now in effect before measuring load again.
        batcher.batch([msg(0)])
        self.drive_windows(batcher, 0, 3, step=28)
        for _ in range(3):
            batcher.report_processing_time(Duration.from_s(0.1))
        assert batcher.scale < 2.0

    def test_dead_zone_no_oscillation(self):
        batcher = self.make()
        for _ in range(2):
            batcher.report_processing_time(Duration.from_s(0.9))
        assert batcher.scale == 2.0
        batcher.batch([msg(0)])
        self.drive_windows(batcher, 0, 2, step=28)
        # After doubling, the same data rate gives half the load: inside the
        # dead zone, so the scale must hold.
        for _ in range(6):
            batcher.report_processing_time(Duration.from_s(0.9))
        assert batcher.scale == 2.0

    def test_max_scale_cap(self):
        batcher = self.make(max_scale=4.0)
        for _ in range(20):
            batcher.report_processing_time(Duration.from_s(100.0))
        assert batcher.scale <= 4.0

    def test_idle_deescalation_wall_clock(self):
        batcher = self.make(idle_timeout_s=5.0)
        for _ in range(4):
            batcher.report_processing_time(Duration.from_s(5.0))
        assert batcher.scale > 1.0
        before = batcher.scale
        self.now = 100.0
        batcher.batch([])  # idle poll past the timeout
        assert batcher.scale < before

    def test_floor_at_base(self):
        batcher = self.make()
        for _ in range(30):
            batcher.report_processing_time(Duration.from_ns(1))
        assert batcher.scale == 1.0

    def test_emitted_window_tracks_escalation(self):
        batcher = self.make()
        batcher.batch([msg(0)])
        b1 = batcher.batch([msg(14)])
        assert math.isclose(b1.window.seconds, 1.0, rel_tol=0.01)
        for _ in range(2):
            batcher.report_processing_time(Duration.from_s(2.0))
        b2 = batcher.batch([msg(3 * 14)])
        assert b2 is not None
        b3 = batcher.batch([msg(6 * 14)])
        assert b3 is not None
        assert math.isclose(b3.window.seconds, 2.0, rel_tol=0.01)


class TestMessagePreservationAcrossResize:
    """No message may be lost when the adaptive window resizes
    (reference message_batcher_test's escalation/deescalation
    preservation cluster): buffered active messages, future messages,
    and everything in flight must come out in SOME batch exactly once."""

    def make(self):
        return AdaptiveMessageBatcher(Duration.from_s(1.0))

    def _drain(self, batcher, feed, total_pulses):
        """Feed pulses one at a time; collect every emitted batch."""
        seen = []
        for p in range(total_pulses):
            out = batcher.batch([msg(p)] if p in feed else [])
            if out:
                seen.extend(m.value for m in out.messages)
        return seen

    def test_escalation_preserves_buffered_messages(self):
        batcher = self.make()
        feed = set(range(0, 70))
        collected = []
        for p in range(70):
            out = batcher.batch([msg(p)])
            if out:
                collected.extend(m.value for m in out.messages)
            if p == 20:
                # Overload mid-stream: the window doubles underneath
                # already-buffered messages.
                batcher.report_processing_time(Duration.from_s(0.9))
                batcher.report_processing_time(Duration.from_s(0.9))
        # Flush what remains with far-future pulses.
        for p in range(70, 140):
            out = batcher.batch([msg(p)])
            if out:
                collected.extend(m.value for m in out.messages)
        emitted = [v for v in collected if v < 70]
        assert sorted(emitted) == list(range(70)), (
            f"lost {set(range(70)) - set(emitted)} / "
            f"dup {[v for v in emitted if emitted.count(v) > 1]}"
        )

    def test_deescalation_preserves_buffered_messages(self):
        batcher = self.make()
        for _ in range(2):
            batcher.report_processing_time(Duration.from_s(0.9))
        assert batcher.scale == 2.0
        collected = []
        for p in range(90):
            out = batcher.batch([msg(p)])
            if out:
                collected.extend(m.value for m in out.messages)
            if p == 40:
                for _ in range(4):
                    batcher.report_processing_time(Duration.from_s(0.05))
        for p in range(90, 160):
            out = batcher.batch([msg(p)])
            if out:
                collected.extend(m.value for m in out.messages)
        emitted = [v for v in collected if v < 90]
        assert sorted(emitted) == list(range(90))

    def test_batches_never_overlap_and_stay_ordered(self):
        batcher = self.make()
        bounds = []
        for p in range(120):
            out = batcher.batch([msg(p)])
            if out:
                bounds.append((out.start.ns, out.end.ns))
            if p == 30:
                batcher.report_processing_time(Duration.from_s(0.9))
                batcher.report_processing_time(Duration.from_s(0.9))
            if p == 80:
                for _ in range(4):
                    batcher.report_processing_time(Duration.from_s(0.05))
        for (s0, e0), (s1, e1) in zip(bounds, bounds[1:], strict=False):
            assert e0 <= s1, f"windows overlap: {(s0, e0)} then {(s1, e1)}"
            assert s0 < e0 and s1 < e1


class TestBatchHold:
    """When a batch's newest message was delivered, from the polls the
    processor feeds: the fake clock is the poll's return time."""

    def feed(self, hold, batcher, polled_at, data):
        batch = batcher.batch(data)
        return batch, hold.arrival(polled_at, data, batch)

    def test_closing_poll_that_brings_only_the_next_window(self):
        """Two polls: the window's pulses, then the pulse that closes
        it. The batch's last message came with the FIRST poll."""
        hold, batcher = BatchHold(), SimpleMessageBatcher(Duration.from_s(1.0))
        batch, arrived = self.feed(
            hold, batcher, 10.000, [msg(p) for p in range(14)]
        )
        assert batch is None and arrived is None
        batch, arrived = self.feed(hold, batcher, 10.071, [msg(14)])
        assert len(batch) == 14
        assert arrived == 10.000

    def test_closing_poll_that_also_brings_the_windows_last_pulse(self):
        """A poll that falls between the sources of one pulse delivers
        the window's tail together with its closing message: the hold
        starts at that poll."""
        hold, batcher = BatchHold(), SimpleMessageBatcher(Duration.from_s(1.0))
        self.feed(hold, batcher, 10.000, [msg(p) for p in range(13)])
        batch, arrived = self.feed(hold, batcher, 10.050, [msg(13), msg(14)])
        assert len(batch) == 14
        assert arrived == 10.050
        # ... and the message it left buffered opens the next window
        # with this poll's time.
        self.feed(hold, batcher, 10.900, [])
        batch, arrived = self.feed(hold, batcher, 11.071, [msg(28)])
        assert [m.value for m in batch.messages] == [14]
        assert arrived == 10.050

    def test_backlog_drained_without_new_data(self):
        """Catch-up: one poll brought several windows; the later ones
        are emitted by polls that bring nothing."""
        hold, batcher = BatchHold(), SimpleMessageBatcher(Duration.from_s(1.0))
        batch, arrived = self.feed(
            hold, batcher, 5.0, [msg(0), msg(14), msg(28)]
        )
        assert [m.value for m in batch.messages] == [0] and arrived == 5.0
        batch, arrived = self.feed(hold, batcher, 6.0, [])
        assert [m.value for m in batch.messages] == [14] and arrived == 5.0

    def test_naive_batcher_holds_nothing(self):
        hold, batcher = BatchHold(), NaiveMessageBatcher()
        _, arrived = self.feed(hold, batcher, 1.0, [msg(0)])
        assert arrived == 1.0
        _, arrived = self.feed(hold, batcher, 2.0, [msg(1)])
        assert arrived == 2.0


class TestScaleChangeCounter:
    """``livedata_batcher_scale_changes_total`` follows the governor:
    one count per change of scale, none at the cap or the floor."""

    @staticmethod
    def changes() -> tuple[float, float]:
        family = REGISTRY.get("livedata_batcher_scale_changes_total")
        return family.value(direction="up"), family.value(direction="down")

    def test_counts_each_escalation_and_relaxation(self):
        governor = LoadGovernor(max_scale=4.0)
        up0, down0 = self.changes()
        assert governor.escalate() and governor.escalate()
        assert not governor.escalate()  # at the cap: no change, no count
        assert self.changes() == (up0 + 2, down0)
        while governor.relax():
            pass
        assert governor.scale == 1.0
        up, down = self.changes()
        assert up == up0 + 2 and down == down0 + 4  # 4 / sqrt(2)^4 = 1
        assert not governor.relax()  # at the floor
        assert self.changes() == (up, down)

    def test_adaptive_batcher_escalation_is_counted(self):
        batcher = AdaptiveMessageBatcher(Duration.from_s(1.0))
        up0, _ = self.changes()
        for _ in range(2):
            batcher.report_processing_time(Duration.from_s(0.9))
        assert batcher.scale == 2.0
        assert self.changes()[0] == up0 + 1

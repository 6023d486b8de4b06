"""Rate-aware batcher: per-stream pulse-slot completion instead of fixed windows.

Parity with reference ``core/rate_aware_batcher.py``: a batch closes when every
*gated* stream (detector/monitor/area kinds, reference :22-29) has seen a
message in the last pulse slot its estimated integer-Hz rate predicts for the
window — not when a fixed time has elapsed. A wall-of-data-time timeout
(high-water mark 1.2x the window past the batch start) closes batches when
gating streams stall, and extensive defensive bounds protect against insane
timestamps (reference :56-95): high-water-mark clamping, origin plausibility
checks, and future-message hold-back caps.

Behavioral contract reproduced from the reference's test scenarios:

- Rate estimation (``PeriodEstimator``) is median-of-diffs seeded, with each
  diff snapped to its nearest integer multiple of the seed and divided back,
  robust to missed pulses / split messages / jitter; the final rate snaps to
  integer Hz within max(10% relative, 0.1 Hz absolute) tolerance.
- A stream whose rate is below one pulse per window never gates (delivered
  opportunistically).
- Streams absent for 5 consecutive batches are evicted.
- Messages past the window's last slot overflow; if *only* overflow exists
  the window is lagging live traffic and jumps forward (gap recovery) instead
  of emitting a long run of empty windows.
"""

from __future__ import annotations

import statistics
from collections import defaultdict, deque
from dataclasses import dataclass, field

from .message import Message, StreamId, StreamKind
from .message_batcher import LoadGovernor, MessageBatch
from .timestamp import Duration, Timestamp

__all__ = ["PeriodEstimator", "RateAwareMessageBatcher", "SlotGrid"]

GATED_KINDS = frozenset(
    {
        StreamKind.DETECTOR_EVENTS,
        StreamKind.MONITOR_EVENTS,
        StreamKind.MONITOR_COUNTS,
        StreamKind.AREA_DETECTOR,
    }
)

#: Positive inter-arrival diffs needed before a rate estimate is trusted.
MIN_DIFFS = 4
#: Ring-buffer length of retained diffs.
DIFF_BUFFER = 32
#: Batches a stream may be silent before its state is dropped.
EVICT_AFTER_ABSENT = 5
#: Integer-Hz snap tolerance: relative and absolute-floor. Tight on
#: purpose — a genuinely non-integer rate (e.g. 14.5 Hz) must be REJECTED
#: rather than snapped, because a grid built on the wrong integer rate
#: drifts phase within a batch and turns every close into a timeout.
#: Jittered-but-integer rates land well inside 1% after the median.
_SNAP_REL = 0.01
_SNAP_ABS_HZ = 0.02
#: Allowed integer-Hz rounding drift when mapping timestamps to slots (ns).
_DRIFT_NS = 1_000_000
#: A grid origin further than this many windows from the batch start means the
#: stream's timestamps live in a disjoint epoch — drop the grid, don't gate.
_MAX_ORIGIN_OFFSET_WINDOWS = 1000
#: High-water mark may sit at most this many windows past the active start;
#: bounds the cascade of timeout-closed empty batches after one insane
#: far-future timestamp, and the same cap holds back plausible near-future
#: messages for later windows.
_MAX_HWM_WINDOWS = 3


class PeriodEstimator:
    """Infers a stream's pulse period from message inter-arrival times."""

    __slots__ = ("_diffs", "last_ns")

    def __init__(self) -> None:
        self._diffs: deque[int] = deque(maxlen=DIFF_BUFFER)
        self.last_ns: int | None = None

    def observe(self, ts_ns: int) -> None:
        if self.last_ns is not None and ts_ns > self.last_ns:
            self._diffs.append(ts_ns - self.last_ns)
        if self.last_ns is None or ts_ns > self.last_ns:
            self.last_ns = ts_ns

    @property
    def integer_rate_hz(self) -> int | None:
        """Estimated rate snapped to integer Hz, or None if unconverged."""
        if len(self._diffs) < MIN_DIFFS:
            return None
        seed = statistics.median(self._diffs)
        # Snap each diff to its nearest integer multiple of the seed: a diff
        # spanning k missed pulses contributes diff/k, an unbiased per-pulse
        # sample, instead of acting as an outlier.
        per_pulse = [d / k for d in self._diffs if (k := round(d / seed)) >= 1]
        period_ns = statistics.median(per_pulse) if per_pulse else seed
        raw_hz = 1e9 / period_ns
        rate = round(raw_hz)
        if rate < 1:
            return None
        if abs(raw_hz - rate) > max(_SNAP_REL * rate, _SNAP_ABS_HZ):
            return None
        return rate


@dataclass(frozen=True, slots=True)
class SlotGrid:
    """Fixed per-stream temporal grid mapping timestamps to pulse slots."""

    origin_ns: int
    period_ns: int
    slots_per_batch: int

    def slot(self, ts: Timestamp, window_start: Timestamp) -> int:
        """Slot of ``ts`` relative to the window's first expected pulse.

        The first pulse of a window is found by ceiling division with a small
        tolerance for integer-Hz rounding drift (a few ns/batch); a wide
        tolerance would misclassify genuine phase offsets (reference :162-183).
        """
        index = round((ts.ns - self.origin_ns) / self.period_ns)
        delta = window_start.ns - self.origin_ns
        base, rem = divmod(delta, self.period_ns)
        if rem > min(_DRIFT_NS, self.period_ns // 2):
            base += 1
        return index - base


@dataclass(slots=True)
class _StreamState:
    """Per-gated-stream estimator, grid, and per-window bucket."""

    estimator: PeriodEstimator = field(default_factory=PeriodEstimator)
    grid: SlotGrid | None = None
    absent: int = 0
    bucket: list[Message] = field(default_factory=list)
    max_slot: int = -1

    @property
    def is_gating(self) -> bool:
        return self.grid is not None

    def route(self, msg: Message, window_start: Timestamp) -> Message | None:
        """Bucket the message, or return it if it lies past the last slot.

        Overflow still bumps ``max_slot`` to the final slot so the gate
        observes that the window's last pulse was reached.
        """
        self.estimator.observe(msg.timestamp.ns)
        if self.grid is None:
            self.bucket.append(msg)
            return None
        slot = self.grid.slot(msg.timestamp, window_start)
        if slot >= self.grid.slots_per_batch:
            self.max_slot = max(self.max_slot, self.grid.slots_per_batch - 1)
            return msg
        self.bucket.append(msg)
        self.max_slot = max(self.max_slot, slot)
        return None

    def gate_satisfied(self) -> bool:
        if self.grid is None:
            return True
        return self.max_slot >= self.grid.slots_per_batch - 1

    def drain(self) -> list[Message]:
        out, self.bucket = self.bucket, []
        self.max_slot = -1
        return out

    def refresh_grid(self, window_start: Timestamp, window: Duration) -> None:
        """(Re)build the grid from the estimator; drop it for sub-rate or
        disjoint-epoch streams (they revert to opportunistic delivery)."""
        rate = self.estimator.integer_rate_hz
        if rate is None:
            return
        slots = round(rate * window.seconds)
        if slots < 1:
            self.grid = None
            return
        origin = self._origin_near(window_start, window)
        if origin is None:
            self.grid = None
            return
        self.grid = SlotGrid(
            origin_ns=origin,
            period_ns=round(1e9 / rate),
            slots_per_batch=slots,
        )

    def _origin_near(self, window_start: Timestamp, window: Duration) -> int | None:
        limit = _MAX_ORIGIN_OFFSET_WINDOWS * window.ns

        def plausible(ns: int) -> bool:
            return abs(ns - window_start.ns) <= limit

        if self.grid is not None and plausible(self.grid.origin_ns):
            return self.grid.origin_ns
        for m in self.bucket:
            if m.timestamp >= window_start:
                return m.timestamp.ns if plausible(m.timestamp.ns) else None
        if self.bucket:
            ns = self.bucket[0].timestamp.ns
            return ns if plausible(ns) else None
        last = self.estimator.last_ns
        return last if last is not None and plausible(last) else None


class RateAwareMessageBatcher:
    """Closes a batch when every gated stream's last expected slot is filled.

    Streams of non-gated kinds flow opportunistically into whatever window is
    active; near-future messages (within ``_MAX_HWM_WINDOWS`` windows past the
    active end) are held back for later windows so batch contents stay bounded
    by the batch's time range.
    """

    def __init__(self, window: Duration = Duration.from_s(1.0), *,
                 timeout_factor: float = 1.2) -> None:
        self._window = window
        self._base_window = window
        self.timeout_factor = timeout_factor
        self._streams: defaultdict[StreamId, _StreamState] = defaultdict(_StreamState)
        self._start: Timestamp | None = None
        self._hwm: Timestamp | None = None
        self._non_gated: list[Message] = []
        self._overflow: list[Message] = []
        self._future: list[Message] = []
        self._pending_window: Duration | None = None
        # Load-adaptive windows share the adaptive batcher's governor:
        # overload doubles the gated window (streams regate to the new
        # slot count at the next refresh), underload shrinks it back.
        # The governor locks its own counters; the rest of this batcher's
        # mutable state is deliberately unlocked — it is owned by the one
        # service worker thread that calls batch()/report_processing_time()
        # (unlike the protocol-level guarantee SimpleMessageBatcher makes).
        self._governor = LoadGovernor()
        self._last_emitted_window: Duration = window

    @property
    def window(self) -> Duration:
        return self._window

    @property
    def scale(self) -> float:
        """The governor's window scale (``livedata_batcher_window_scale``)."""
        return self._governor.scale

    @property
    def pending_messages(self) -> int:
        """Messages buffered toward not-yet-closed windows across every
        internal hold (non-gated flow, overflow, near-future, per-stream
        gated slots) — the durability plane's quiescence probe
        (ADR 0118): a checkpoint bookmark must not claim these as
        processed. Read from the owning service thread (like the rest
        of this batcher's unlocked state)."""
        pending = (
            len(self._non_gated) + len(self._overflow) + len(self._future)
        )
        for state in self._streams.values():
            pending += len(state.bucket)
        return pending

    def is_gating(self, stream: StreamId) -> bool:
        state = self._streams.get(stream)
        return state.is_gating if state is not None else False

    @property
    def tracked_streams(self) -> set[StreamId]:
        return set(self._streams)

    def report_processing_time(self, duration: Duration) -> None:
        load = duration.ns / max(self._last_emitted_window.ns, 1)
        if self._governor.observe(load):
            # Takes effect at the next batch start.
            self._pending_window = Duration(
                max(1, round(self._base_window.ns * self._governor.scale))
            )

    def batch(self, messages: list[Message]) -> MessageBatch | None:
        if messages:
            self._hwm = self._clamped_hwm(max(m.timestamp for m in messages))
        if self._start is None:
            if not messages:
                return None
            return self._bootstrap(messages)
        for msg in messages:
            self._route(msg)
        if self._window_is_lagging():
            self._jump_past_gap()
        if self._complete():
            return self._close()
        return None

    # -- internals ---------------------------------------------------------

    def _clamped_hwm(self, latest: Timestamp) -> Timestamp:
        """Cap HWM advance at a bounded distance past the active window so a
        single far-future timestamp cannot pin the timeout path; floor at the
        current HWM so it never regresses (reference :56-95)."""
        if self._start is None or self._hwm is None:
            return latest
        ceiling = self._start + self._window * _MAX_HWM_WINDOWS
        return max(self._hwm, min(latest, ceiling))

    def _bootstrap(self, messages: list[Message]) -> MessageBatch:
        """Flush the startup backlog as one batch; open the window after it."""
        lo = min(m.timestamp for m in messages)
        hi = max(m.timestamp for m in messages)
        for msg in messages:
            if msg.stream.kind in GATED_KINDS:
                self._streams[msg.stream].estimator.observe(msg.timestamp.ns)
        self._start = hi
        for state in self._streams.values():
            state.refresh_grid(hi, self._window)
        return MessageBatch(start=lo, end=hi, messages=list(messages))

    def _route(self, msg: Message) -> None:
        assert self._start is not None
        gated = msg.stream.kind in GATED_KINDS
        state = self._streams[msg.stream] if gated else None
        if (state is None or not state.is_gating) and self._is_near_future(msg):
            self._future.append(msg)
            return
        if state is None:
            self._non_gated.append(msg)
            return
        overflow = state.route(msg, self._start)
        if overflow is not None:
            self._overflow.append(overflow)

    def _is_near_future(self, msg: Message) -> bool:
        end = self._start + self._window  # type: ignore[operator]
        if not msg.timestamp > end:
            return False
        return (msg.timestamp - end).ns <= _MAX_HWM_WINDOWS * self._window.ns

    def _complete(self) -> bool:
        assert self._start is not None
        if self._hwm is not None:
            if self._hwm >= self._start + self._window * self.timeout_factor:
                return True
        has_gating = False
        for state in self._streams.values():
            if not state.is_gating:
                continue
            has_gating = True
            if not state.gate_satisfied():
                return False
        return has_gating

    def _window_is_lagging(self) -> bool:
        """Only overflow arrived: every gridded stream's traffic lies past the
        window — it is lagging live data and must jump, not crawl."""
        if not self._overflow:
            return False
        return not any(
            s.is_gating and s.bucket for s in self._streams.values()
        )

    def _jump_past_gap(self) -> None:
        assert self._start is not None
        stashed = self._drain_all()
        pending, self._overflow = self._overflow, []
        future, self._future = self._future, []
        earliest = min(m.timestamp for m in pending)
        steps = max((earliest - self._start).ns // self._window.ns, 0)
        if steps > 0:
            self._start = self._start + Duration.from_ns(steps * self._window.ns)
        for msg in stashed + pending + future:
            self._route(msg)

    def _drain_all(self) -> list[Message]:
        out, self._non_gated = self._non_gated, []
        for state in self._streams.values():
            out.extend(state.drain())
        return out

    def _close(self) -> MessageBatch:
        assert self._start is not None
        start = self._start
        # The closing batch's window length: captured before the stream
        # refresh, which may apply the governor's pending window — that
        # takes effect at the *next* batch start, not on this one.
        closing_window = self._window
        self._refresh_streams(start)
        messages = self._drain_all()
        if any(s.is_gating for s in self._streams.values()):
            end = start + closing_window
        else:
            # Timeout-closed with nothing gating: include all held-back
            # traffic and cover its real time range, mirroring
            # SimpleMessageBatcher semantics (reference :593-610).
            messages += self._future + self._overflow
            self._future, self._overflow = [], []
            end = max(
                (m.timestamp for m in messages), default=start + closing_window
            )
            end = max(end, start + closing_window)
        batch = MessageBatch(start=start, end=end, messages=messages)
        # Load feedback divides by the batch's REAL span: timeout-closed
        # batches can cover several windows of drained traffic, and
        # measuring that work against the nominal window would read ~3x
        # the true load and ratchet the governor to max scale.
        self._last_emitted_window = Duration(max(end.ns - start.ns, 1))
        self._start = end
        # Re-route held-back traffic into the new window; anything still past
        # its last slot lands back in overflow and waits for the next close.
        overflow, self._overflow = self._overflow, []
        future, self._future = self._future, []
        for msg in overflow + future:
            self._route(msg)
        return batch

    def _refresh_streams(self, window_start: Timestamp) -> None:
        for sid in list(self._streams):
            state = self._streams[sid]
            if state.bucket:
                state.absent = 0
                state.refresh_grid(window_start, self._window)
            else:
                state.absent += 1
                if state.absent >= EVICT_AFTER_ABSENT:
                    del self._streams[sid]
        if self._pending_window is not None:
            self._window = self._pending_window
            self._pending_window = None
            for state in self._streams.values():
                state.refresh_grid(window_start, self._window)

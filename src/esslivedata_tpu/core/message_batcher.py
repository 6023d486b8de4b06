"""Time-window batching with a data-derived clock.

Parity with reference ``core/message_batcher.py``: batch boundaries come from
*message timestamps*, never wall clock, and are quantized to the 14 Hz pulse
grid. Three batchers:

- ``NaiveMessageBatcher`` (reference :62): emit every poll immediately with
  pulse-quantized bounds — removes batching nondeterminism in tests.
- ``SimpleMessageBatcher`` (reference :93): fixed windows; a window closes
  when the first message of a later window arrives; late messages (older
  than the open window) are folded into the next emitted batch rather than
  dropped (reference :105-113).
- ``AdaptiveMessageBatcher`` (reference :230): window escalates x2 after 2
  consecutive overloaded batches and de-escalates x(1/sqrt 2) after 3
  consecutive underloaded ones, with a dead zone between the thresholds so
  the two rules cannot oscillate (reference :190-207); windows stay
  pulse-quantized (reference :210); a wall-clock idle timeout de-escalates
  when data stops flowing (reference :283-289).

All window arithmetic is exact-integer in pulse indices (see
``core/timestamp.py``), so boundaries are reproducible across hosts.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Protocol, runtime_checkable

from ..telemetry.instruments import BATCHER_SCALE_CHANGES
from .message import Message
from .timestamp import Duration, Timestamp

__all__ = [
    "AdaptiveMessageBatcher",
    "BatchHold",
    "LoadGovernor",
    "MessageBatch",
    "MessageBatcher",
    "NaiveMessageBatcher",
    "SimpleMessageBatcher",
]

from .constants import PULSE_PERIOD_NS_DEN, PULSE_PERIOD_NS_NUM


def _pulses_for(window: Duration) -> int:
    """Window length in whole pulses (>= 1)."""
    return max(1, round(window.ns * PULSE_PERIOD_NS_DEN / PULSE_PERIOD_NS_NUM))


@dataclass(slots=True)
class MessageBatch:
    """Messages plus the data-time window they were batched into."""

    start: Timestamp
    end: Timestamp
    messages: list[Message] = field(default_factory=list)

    def __len__(self) -> int:
        return len(self.messages)

    @property
    def window(self) -> Duration:
        return self.end - self.start


@runtime_checkable
class MessageBatcher(Protocol):
    def batch(self, messages: list[Message]) -> MessageBatch | None: ...

    def report_processing_time(self, duration: Duration) -> None: ...


class BatchHold:
    """When the newest message of each emitted batch was delivered.

    The processor feeds every poll (its return time on ``perf_counter``,
    the data messages it brought, what the batcher made of them) and
    gets back, for an emitted batch, the return time of the poll that
    delivered the batch's last message; the batch's hold is the start of
    its processing minus that. A window closes on the first message of a
    LATER window, so the closing poll often brings nothing the batch
    holds: its last message then came with an earlier poll, which is the
    one remembered here.
    """

    def __init__(self) -> None:
        #: Return time of the newest poll whose data is still buffered.
        self._buffered_at: float | None = None

    def arrival(
        self,
        polled_at: float,
        data: list[Message],
        batch: MessageBatch | None,
    ) -> float | None:
        """None while nothing is emitted; else the delivery time of the
        batch's newest message."""
        if batch is None:
            if data:
                self._buffered_at = polled_at
            return None
        end = batch.end
        arrived = self._buffered_at
        if arrived is None or any(m.timestamp < end for m in data):
            arrived = polled_at
        if any(m.timestamp >= end for m in data):
            self._buffered_at = polled_at
        return arrived


class NaiveMessageBatcher:
    """Emit every nonempty poll as one batch with pulse-quantized bounds."""

    #: Emits every poll's messages immediately — nothing ever pends
    #: (the durability plane's quiescence probe, ADR 0118).
    pending_messages = 0

    def batch(self, messages: list[Message]) -> MessageBatch | None:
        if not messages:
            return None
        lo = min(m.timestamp for m in messages).quantize()
        hi = max(m.timestamp for m in messages)
        end = hi.quantize_up()
        if end == hi:  # message exactly on grid: window must contain it
            end = Timestamp.from_pulse_index(hi.pulse_index() + 1)
        return MessageBatch(start=lo, end=end, messages=list(messages))

    def report_processing_time(self, duration: Duration) -> None:
        pass


class SimpleMessageBatcher:
    """Fixed data-time windows closed by the first message of a later window."""

    def __init__(self, window: Duration = Duration.from_s(1.0)) -> None:
        self._window_pulses = _pulses_for(window)
        self._buffer: list[Message] = []
        self._start_pulse: int | None = None
        # Width of the most recently *emitted* batch: load feedback must be
        # measured against the window the work actually covered, not a
        # freshly escalated width.
        self._last_emitted_pulses: int = self._window_pulses
        # Reentrant: the adaptive subclass wraps batch() and re-enters the
        # base implementation under the same lock. Today's in-repo callers
        # drive batch()/report_processing_time() from the one service
        # worker thread, so this is a defensive guarantee, not a fix for
        # an observed race: batchers are protocol objects handed to
        # multi-threaded transports, and an unguarded cross-thread
        # ``window`` read could observe a half-advanced (start_pulse,
        # window_pulses) pair mid-update. Uncontended RLock acquisition
        # is tens of ns against a >=71 ms batch window.
        self._lock = threading.RLock()

    @property
    def window(self) -> Duration:
        with self._lock:
            return Duration(
                self._window_pulses * PULSE_PERIOD_NS_NUM // PULSE_PERIOD_NS_DEN
            )

    def _window_pulses_next(self) -> int:
        """Hook for adaptive subclass: pulses for the next opened window."""
        return self._window_pulses

    @property
    def pending_messages(self) -> int:
        """Messages buffered toward a not-yet-closed window. The
        durability plane (ADR 0118) checkpoints only when this reads 0:
        a bookmark taken while a partial window sits here would claim
        data as processed that no job state yet contains — replay
        would then skip it."""
        with self._lock:
            return len(self._buffer)

    def batch(self, messages: list[Message]) -> MessageBatch | None:
        with self._lock:
            return self._batch_locked(messages)

    def _batch_locked(self, messages: list[Message]) -> MessageBatch | None:
        self._buffer.extend(messages)
        if not self._buffer:
            return None
        if self._start_pulse is None:
            first = min(m.timestamp for m in self._buffer)
            self._start_pulse = first.pulse_index()
        end_pulse = self._start_pulse + self._window_pulses
        end_ts = Timestamp.from_pulse_index(end_pulse)
        # The window closes only once data time has moved past it.
        if not any(m.timestamp >= end_ts for m in self._buffer):
            return None
        emitted = [m for m in self._buffer if m.timestamp < end_ts]
        self._buffer = [m for m in self._buffer if m.timestamp >= end_ts]
        self._last_emitted_pulses = self._window_pulses
        batch = MessageBatch(
            start=Timestamp.from_pulse_index(self._start_pulse),
            end=end_ts,
            messages=emitted,
        )
        # Advance to the aligned window containing the earliest remaining
        # message (skipping empty windows), using the possibly-updated width.
        self._window_pulses = self._window_pulses_next()
        next_pulse = min(m.timestamp for m in self._buffer).pulse_index()
        skipped = (next_pulse - end_pulse) // self._window_pulses
        self._start_pulse = end_pulse + max(0, skipped) * self._window_pulses
        return batch

    def report_processing_time(self, duration: Duration) -> None:
        pass


class LoadGovernor:
    """The load->window-scale state machine shared by the adaptive and
    rate-aware batchers: above ``high_load`` for ``escalate_after``
    consecutive batches the scale doubles (cap ``max_scale``); below
    ``high_load / (2*sqrt 2)`` for ``deescalate_after`` batches it
    shrinks by 1/sqrt 2 (floor 1). The gap between thresholds is the
    dead zone preventing oscillation after a doubling halves the load.
    """

    def __init__(
        self,
        *,
        max_scale: float = 8.0,
        high_load: float = 0.8,
        escalate_after: int = 2,
        deescalate_after: int = 3,
    ) -> None:
        self.scale = 1.0
        self._max_scale = max_scale
        self._high = high_load
        self._low = high_load / (2.0 * math.sqrt(2.0))
        self._escalate_after = escalate_after
        self._deescalate_after = deescalate_after
        self._over = 0
        self._under = 0
        # The consecutive-batch counters are read-modify-write sequences.
        # The governor is shared infrastructure (adaptive AND rate-aware
        # batchers); in-repo callers feed it from one worker thread, so —
        # as with the batcher lock above — this makes the class safe to
        # drive from any thread rather than fixing an observed race: a
        # lost increment would silently defer an escalation. RLock:
        # observe() re-enters escalate()/relax().
        self._lock = threading.RLock()

    def observe(self, load: float) -> bool:
        """Feed one batch's load; returns True when the scale changed."""
        with self._lock:
            if load > self._high:
                self._over += 1
                self._under = 0
            elif load < self._low:
                self._under += 1
                self._over = 0
            else:
                self._over = 0
                self._under = 0
            if self._over >= self._escalate_after:
                self._over = 0
                return self.escalate()
            if self._under >= self._deescalate_after:
                self._under = 0
                return self.relax()
            return False

    def escalate(self) -> bool:
        with self._lock:
            new = min(self._max_scale, self.scale * 2.0)
            changed = new != self.scale
            self.scale = new
        if changed:
            BATCHER_SCALE_CHANGES.inc(direction="up")
        return changed

    def relax(self) -> bool:
        with self._lock:
            new = max(1.0, self.scale / math.sqrt(2.0))
            changed = new != self.scale
            self.scale = new
        if changed:
            BATCHER_SCALE_CHANGES.inc(direction="down")
        return changed


class AdaptiveMessageBatcher(SimpleMessageBatcher):
    """Load-adaptive windows.

    ``report_processing_time`` feeds back the wall time the service spent on
    the last emitted batch. Load = processing_time / window. Above
    ``high_load`` for ``escalate_after`` consecutive batches the window
    doubles (cap ``max_scale`` x base); below ``high_load / (2*sqrt 2)`` for
    ``deescalate_after`` consecutive batches it shrinks by 1/sqrt 2 (floor at
    base). The gap between thresholds is the dead zone: after one doubling,
    load halves, landing between the thresholds — no oscillation.
    """

    def __init__(
        self,
        window: Duration = Duration.from_s(1.0),
        *,
        max_scale: float = 8.0,
        high_load: float = 0.8,
        escalate_after: int = 2,
        deescalate_after: int = 3,
        idle_timeout_s: float = 5.0,
        clock=time.monotonic,
    ) -> None:
        super().__init__(window)
        self._base_pulses = self._window_pulses
        self._governor = LoadGovernor(
            max_scale=max_scale,
            high_load=high_load,
            escalate_after=escalate_after,
            deescalate_after=deescalate_after,
        )
        self._pending_pulses = self._window_pulses
        self._idle_timeout_s = idle_timeout_s
        self._clock = clock
        self._last_activity = clock()

    @property
    def scale(self) -> float:
        with self._lock:
            return self._pending_pulses / self._base_pulses

    def _window_pulses_next(self) -> int:
        return self._pending_pulses

    def batch(self, messages: list[Message]) -> MessageBatch | None:
        with self._lock:
            now = self._clock()
            if messages:
                self._last_activity = now
            elif (
                now - self._last_activity > self._idle_timeout_s
                and self._pending_pulses > self._base_pulses
            ):
                # Data stopped: relax toward the base window so the next
                # burst is not stuck behind a huge escalated window.
                self._deescalate()
                self._last_activity = now
            return self._batch_locked(messages)

    def report_processing_time(self, duration: Duration) -> None:
        with self._lock:
            window_ns = (
                self._last_emitted_pulses
                * PULSE_PERIOD_NS_NUM
                / PULSE_PERIOD_NS_DEN
            )
            if self._governor.observe(duration.ns / window_ns):
                self._apply_scale()

    def _deescalate(self) -> None:
        """Idle relaxation path (wall-clock driven); caller holds the lock."""
        self._governor.relax()
        self._apply_scale()

    def _apply_scale(self) -> None:
        self._pending_pulses = max(
            1, round(self._base_pulses * self._governor.scale)
        )

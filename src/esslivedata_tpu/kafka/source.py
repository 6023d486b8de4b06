"""Message sources over a narrow consumer protocol.

Parity with reference ``kafka/source.py``: ``KafkaMessageSource`` (bounded
consume per poll, :28), ``BackgroundMessageSource`` (:80) — a daemon consume
thread overlapping broker I/O with compute, a bounded drop-oldest queue
(:199-213), a circuit breaker opening after consecutive errors (:225-240)
and health reporting (:295). The consumer protocol is deliberately tiny so
tests inject ``FakeConsumer`` without a broker (SURVEY.md section 4.2).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from collections.abc import Sequence
from dataclasses import dataclass
from enum import Enum

from .errors import is_fatal
from typing import Protocol, runtime_checkable

__all__ = [
    "BackgroundMessageSource",
    "ConsumerHealth",
    "FakeConsumer",
    "FakeKafkaMessage",
    "KafkaConsumer",
    "KafkaMessage",
    "KafkaMessageSource",
]

logger = logging.getLogger(__name__)


@runtime_checkable
class KafkaMessage(Protocol):
    def value(self) -> bytes: ...

    def topic(self) -> str: ...

    def error(self):  # None or error object
        ...


@runtime_checkable
class KafkaConsumer(Protocol):
    def consume(
        self, num_messages: int, timeout: float
    ) -> Sequence[KafkaMessage]: ...


@dataclass(frozen=True, slots=True)
class FakeKafkaMessage:
    _value: bytes
    _topic: str
    _error: object = None

    def value(self) -> bytes:
        return self._value

    def topic(self) -> str:
        return self._topic

    def error(self):
        return self._error


class FakeConsumer:
    """Replays scripted message batches; raising entries simulate failures."""

    def __init__(self, batches: Sequence[Sequence[KafkaMessage]] = ()) -> None:
        self._batches: deque = deque(list(b) for b in batches)
        self.consume_calls = 0

    def push(self, batch: Sequence[KafkaMessage]) -> None:
        self._batches.append(list(batch))

    def consume(self, num_messages: int, timeout: float) -> list[KafkaMessage]:
        self.consume_calls += 1
        if not self._batches:
            return []
        item = self._batches.popleft()
        if isinstance(item, Exception):
            raise item
        return list(item)[:num_messages]


class KafkaMessageSource:
    """Synchronous source: one bounded consume per poll, fatal-error filter."""

    def __init__(
        self,
        consumer: KafkaConsumer,
        *,
        max_messages: int = 100,
        timeout_s: float = 0.05,
    ) -> None:
        self._consumer = consumer
        self._max_messages = max_messages
        self._timeout_s = timeout_s

    def get_messages(self) -> list[KafkaMessage]:
        messages = self._consumer.consume(self._max_messages, self._timeout_s)
        good = []
        for msg in messages:
            err = msg.error()
            if err is not None:
                if is_fatal(err):
                    # Auth/misconfiguration: crash, don't spin (kafka/errors.py).
                    raise RuntimeError(f"Fatal Kafka error: {err}")
                logger.warning("Kafka message error: %s", err)
                continue
            good.append(msg)
        return good


class ConsumerHealth(Enum):
    OK = "ok"
    STALE = "stale"
    STOPPED = "stopped"


#: Full polls the worker waits through for the consumer to catch up
#: (never more than the queue holds: a full queue drops its oldest).
_HELD_BATCHES = 16


class BackgroundMessageSource:
    """Daemon consume thread feeding a bounded drop-oldest batch queue.

    Overlaps broker I/O with the worker's compute (thread boundary #1 in
    the reference call stack, SURVEY.md section 3.1). After
    ``max_consecutive_errors`` the circuit breaker opens: the thread stops
    and ``get_messages`` raises, killing the worker loop so the supervisor
    restarts the process with fresh connections.
    """

    def __init__(
        self,
        consumer: KafkaConsumer,
        *,
        max_messages: int = 100,
        timeout_s: float = 0.05,
        max_queued_batches: int = 1000,
        max_consecutive_errors: int = 10,
        health_timeout_s: float = 60.0,
    ) -> None:
        self._consumer = consumer
        self._max_messages = max_messages
        self._timeout_s = timeout_s
        self._queue: deque[list[KafkaMessage]] = deque(maxlen=max_queued_batches)
        self._held_batches = min(_HELD_BATCHES, max_queued_batches)
        self._lock = threading.Lock()
        self._running = threading.Event()
        self._thread: threading.Thread | None = None
        self._max_consecutive_errors = max_consecutive_errors
        self._consecutive_errors = 0
        self._broken = False
        self._health_timeout_s = health_timeout_s
        self._last_success = time.monotonic()
        self._dropped_batches = 0
        self._consumed_messages = 0
        #: The newest consume filled its budget: the consumer is in the
        #: middle of reading what the topics hold, and what is queued is
        #: a cut through them that may hold one topic's later messages
        #: without another's earlier ones (``get_messages`` waits).
        self._mid_read = False
        # Next-consume offset per topic of everything HANDED TO the
        # worker (not merely consumed into the queue): the durability
        # plane's bookmark surface (ADR 0118). Updated under the queue
        # lock on the worker side, so a checkpoint taken between
        # process cycles sees exactly the delivered frontier.
        # Bookmarks are PER TOPIC, which is only exact for topics with
        # one partition (the file broker always; per-instrument Kafka
        # topics typically): a topic observed on >= 2 partitions is
        # excluded from positions() — one merged number would seek
        # every partition to the max and silently skip the slower
        # partitions' gap. Excluded topics resume at the high
        # watermark, the documented pre-durability behavior.
        self._delivered_offsets: dict[str, int] = {}
        self._topic_partitions: dict[str, set] = {}
        self._multi_partition_logged: set[str] = set()

    # -- lifecycle --------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._running.set()
        self._thread = threading.Thread(
            target=self._consume_loop, name="kafka-consume", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._running.clear()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        with self._lock:
            self._mid_read = False  # what is queued is all there will be

    def __enter__(self) -> "BackgroundMessageSource":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- consume thread ---------------------------------------------------
    def _consume_loop(self) -> None:
        while self._running.is_set():
            try:
                batch = self._consumer.consume(self._max_messages, self._timeout_s)
            except Exception:
                with self._lock:
                    self._mid_read = False  # no read is under way: hand over
                self._consecutive_errors += 1
                logger.exception(
                    "Consume error (%d consecutive)", self._consecutive_errors
                )
                if self._consecutive_errors >= self._max_consecutive_errors:
                    logger.error("Circuit breaker open: stopping consume thread")
                    self._broken = True
                    self._running.clear()
                    return
                time.sleep(min(0.1 * self._consecutive_errors, 1.0))
                continue
            self._consecutive_errors = 0
            self._last_success = time.monotonic()
            fatal = next(
                (
                    m.error()
                    for m in batch
                    if m.error() is not None and is_fatal(m.error())
                ),
                None,
            )
            good = [m for m in batch if m.error() is None]
            with self._lock:
                self._mid_read = len(batch) >= self._max_messages
                if good:
                    # Enqueue before opening the circuit: good messages
                    # consumed alongside a fatal error event must still
                    # reach the worker.
                    if len(self._queue) == self._queue.maxlen:
                        self._dropped_batches += 1
                    self._queue.append(good)
                    self._consumed_messages += len(good)
            if fatal is not None:
                logger.error("Fatal Kafka error, opening circuit: %s", fatal)
                self._broken = True
                self._running.clear()
                return

    # -- worker side ------------------------------------------------------
    @staticmethod
    def _message_next_offset(message) -> int | None:
        """The resume offset AFTER ``message``: file-broker messages
        carry ``next_offset()`` (byte positions), confluent messages
        ``offset()`` (message index — resume at +1). None when the
        transport exposes neither (in-memory fakes): those deployments
        simply have no bookmarks, which is the pre-durability
        behavior."""
        probe = getattr(message, "next_offset", None)
        if probe is not None:
            try:
                value = probe()
                return None if value is None or value < 0 else int(value)
            except Exception:  # pragma: no cover - defensive
                return None
        probe = getattr(message, "offset", None)
        if probe is not None:
            try:
                value = probe()
                return (
                    None if value is None or value < 0 else int(value) + 1
                )
            except Exception:  # pragma: no cover - defensive
                return None
        return None

    def get_messages(self) -> list[KafkaMessage]:
        # Drain before checking the breaker: good messages enqueued alongside
        # the fatal error event must still reach the worker; only once the
        # queue is empty does the open circuit surface as an error.
        with self._lock:
            out: list[KafkaMessage] = []
            # A consume that filled its budget stopped in the middle of
            # the topics: the worker batches by data time across them,
            # and a window must not close on one topic's later message
            # while another's earlier ones are still unread. So what is
            # queued is handed over once the consumer has caught up (a
            # consume below its budget), or when it cannot (a backlog
            # of ``_HELD_BATCHES`` full polls: progress before order).
            if self._mid_read and len(self._queue) < self._held_batches and not self._broken:
                return out
            while self._queue:
                out.extend(self._queue.popleft())
            for message in out:
                next_offset = self._message_next_offset(message)
                if next_offset is not None:
                    topic = message.topic()
                    partition_probe = getattr(message, "partition", None)
                    if partition_probe is not None:
                        try:
                            self._topic_partitions.setdefault(
                                topic, set()
                            ).add(partition_probe())
                        except Exception:  # pragma: no cover
                            logger.debug(
                                "partition probe failed for %s",
                                topic,
                                exc_info=True,
                            )
                    if next_offset > self._delivered_offsets.get(topic, -1):
                        self._delivered_offsets[topic] = next_offset
        if not out and self._broken:
            raise RuntimeError(
                "Kafka consumer circuit breaker open (repeated consume errors)"
            )
        return out

    @property
    def health(self) -> ConsumerHealth:
        if self._broken or (
            self._thread is not None and not self._thread.is_alive()
            and self._running.is_set()
        ):
            return ConsumerHealth.STOPPED
        if time.monotonic() - self._last_success > self._health_timeout_s:
            return ConsumerHealth.STALE
        return ConsumerHealth.OK

    @property
    def is_healthy(self) -> bool:
        return self.health == ConsumerHealth.OK

    def positions(self) -> dict[str, int]:
        """Per-topic next-consume offsets of everything handed to the
        worker — the processor's checkpoint bookmarks (ADR 0118). The
        worker takes these only at quiescent window boundaries, where
        delivered == folded-into-state, so bookmark + state restore +
        replay is exactly-once. Topics observed on more than one
        partition are EXCLUDED (logged once): a single merged offset
        cannot bookmark several partitions without skipping the slower
        ones' gap on restore — those topics resume at live instead."""
        with self._lock:
            out = {}
            for topic, offset in self._delivered_offsets.items():
                if len(self._topic_partitions.get(topic, ())) > 1:
                    if topic not in self._multi_partition_logged:
                        self._multi_partition_logged.add(topic)
                        logger.warning(
                            "topic %s spans multiple partitions: "
                            "excluded from checkpoint bookmarks "
                            "(restart resumes it at the high "
                            "watermark)",
                            topic,
                        )
                    continue
                out[topic] = offset
            return out

    @property
    def metrics(self) -> dict[str, int]:
        with self._lock:
            return {
                "queued_batches": len(self._queue),
                "dropped_batches": self._dropped_batches,
                "consumed_messages": self._consumed_messages,
            }

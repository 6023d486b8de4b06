"""Sinks + per-type serializers.

Parity with reference ``kafka/sink.py`` (KafkaSink:53, MessageSerializer:40,
drop-on-BufferError backpressure :110-118, UnrollingSinkAdapter:179) and
``kafka/sink_serializers.py`` (results->da00:78, logs->f144:95,
status->x5f2:108, commands/acks->JSON:160-182). Serialization errors are
contained per message; producer buffer-full drops the message rather than
blocking the hot loop.
"""

from __future__ import annotations

import json
import logging
import os
import socket
import threading
import time
from collections.abc import Sequence
from dataclasses import dataclass
from typing import Protocol, runtime_checkable

import numpy as np
from pydantic import BaseModel

from ..core.message import Message, StreamKind
from ..preprocessors.to_nxlog import LogData
from ..telemetry.instruments import (
    SINK_BYTES,
    SINK_SECONDS,
    SINK_SERIALIZE_SECONDS,
)
from ..utils.labeled import DataArray
from . import wire
from .da00_compat import dataarray_to_da00
from .stream_mapping import LivedataTopics

__all__ = [
    "FakeProducer",
    "KafkaProducer",
    "KafkaSink",
    "MessageSerializer",
    "SerializedMessage",
    "UnrollingSinkAdapter",
    "make_default_serializer",
]

logger = logging.getLogger(__name__)

_SERIALIZE_S = SINK_SECONDS.labels(phase="serialize")
_PRODUCE_S = SINK_SECONDS.labels(phase="produce")
_FLUSH_S = SINK_SECONDS.labels(phase="flush")
_DA00_S = SINK_SERIALIZE_SECONDS.labels(step="da00")
_WIRE_S = SINK_SERIALIZE_SECONDS.labels(step="wire")


@dataclass(frozen=True, slots=True)
class SerializedMessage:
    topic: str
    value: bytes
    key: bytes | None = None


@runtime_checkable
class MessageSerializer(Protocol):
    def serialize(self, message: Message) -> SerializedMessage: ...


@runtime_checkable
class KafkaProducer(Protocol):
    def produce(self, topic: str, value: bytes, key: bytes | None = None) -> None: ...

    def flush(self, timeout: float = 0.0) -> None: ...


class FakeProducer:
    """In-memory producer double; can simulate a full buffer."""

    def __init__(self, *, buffer_errors: int = 0) -> None:
        self.messages: list[SerializedMessage] = []
        self._buffer_errors = buffer_errors

    def produce(self, topic: str, value: bytes, key: bytes | None = None) -> None:
        if self._buffer_errors > 0:
            self._buffer_errors -= 1
            raise BufferError("queue full")
        self.messages.append(SerializedMessage(topic=topic, value=value, key=key))

    def flush(self, timeout: float = 0.0) -> None:
        pass


class DefaultSerializer:
    """Routes by StreamKind + payload type to the right wire format."""

    def __init__(self, topics: LivedataTopics, service_id: str = "") -> None:
        self._topics = topics
        self._service_id = service_id
        # Both steps on the scrape from the start: a reader of one
        # never finds the family without it.
        _DA00_S.inc(0)
        _WIRE_S.inc(0)

    @staticmethod
    def _encode_da00(name: str, ts: int, value: DataArray) -> bytes:
        """A result as da00 bytes, its two steps timed apart: building
        the message's variables, then the wire encode."""
        start = time.perf_counter()
        variables = dataarray_to_da00(value)
        built = time.perf_counter()
        payload = wire.encode_da00(name, ts, variables)
        _DA00_S.inc(built - start)
        _WIRE_S.inc(time.perf_counter() - built)
        return payload

    def serialize(self, message: Message) -> SerializedMessage:
        kind = message.stream.kind
        value = message.value
        ts = message.timestamp.ns
        name = message.stream.name
        if kind in (StreamKind.LIVEDATA_DATA,) and isinstance(value, DataArray):
            return SerializedMessage(
                topic=self._topics.data,
                value=self._encode_da00(name, ts, value),
                key=name.encode(),
            )
        if kind == StreamKind.LIVEDATA_NICOS_DATA:
            if isinstance(value, LogData):
                return SerializedMessage(
                    topic=self._topics.nicos,
                    value=wire.encode_f144(name, value.value, int(value.time[-1])),
                    key=name.encode(),
                )
            if isinstance(value, DataArray):
                # Contracted device outputs (core/nicos_devices.py): da00
                # keyed by stable device name; the start_time coord rides
                # along as the generation change-detector.
                return SerializedMessage(
                    topic=self._topics.nicos,
                    value=self._encode_da00(name, ts, value),
                    key=name.encode(),
                )
            return SerializedMessage(
                topic=self._topics.nicos,
                value=wire.encode_f144(name, np.asarray(value), ts),
                key=name.encode(),
            )
        if kind == StreamKind.LIVEDATA_STATUS and isinstance(value, BaseModel):
            # NICOS wire contract (kafka/nicos_status.py): service and
            # per-job heartbeats carry a NICOS status code + typed payload
            # in status_json, addressed by the NICOS identity conventions.
            from ..core.job import JobStatus, ServiceStatus
            from .nicos_status import (
                job_status_to_x5f2,
                service_status_to_x5f2,
            )

            if isinstance(value, ServiceStatus):
                payload = service_status_to_x5f2(
                    value,
                    worker=self._service_id,
                    host_name=socket.gethostname(),
                    process_id=os.getpid(),
                )
            elif isinstance(value, JobStatus):
                payload = job_status_to_x5f2(
                    value,
                    host_name=socket.gethostname(),
                    process_id=os.getpid(),
                )
            else:
                payload = wire.encode_x5f2(
                    wire.X5f2Status(
                        software_name="esslivedata-tpu",
                        software_version="0.1.0",
                        service_id=self._service_id,
                        host_name=socket.gethostname(),
                        process_id=os.getpid(),
                        update_interval_ms=2000,
                        status_json=value.model_dump_json(),
                    )
                )
            return SerializedMessage(
                topic=self._topics.status, value=payload
            )
        if kind == StreamKind.LIVEDATA_RESPONSES:
            payload = (
                value.model_dump(mode="json")
                if isinstance(value, BaseModel)
                else value
            )
            return SerializedMessage(
                topic=self._topics.responses,
                value=json.dumps(payload).encode(),
            )
        if kind == StreamKind.LIVEDATA_COMMANDS:
            payload = (
                value.model_dump(mode="json")
                if isinstance(value, BaseModel)
                else value
            )
            return SerializedMessage(
                topic=self._topics.commands,
                value=json.dumps(payload).encode(),
            )
        raise ValueError(
            f"No serializer for kind={kind} value type {type(value).__name__}"
        )


def make_default_serializer(
    topics: LivedataTopics, service_id: str = ""
) -> DefaultSerializer:
    return DefaultSerializer(topics, service_id)


class KafkaSink:
    """MessageSink publishing through a producer with drop-on-full.

    Error policy mirrors the consume side's circuit breaker: transient
    produce/flush exceptions are contained (counted, logged) — a broker
    hiccup must not crash the service worker per message — but after
    ``MAX_CONSECUTIVE_ERRORS`` in a row the breaker opens and the error
    propagates, handing the supervisor a restart instead of a silent
    black hole.
    """

    #: Consecutive produce failures before the breaker opens.
    MAX_CONSECUTIVE_ERRORS = 10

    def __init__(self, producer: KafkaProducer, serializer: MessageSerializer):
        self._producer = producer
        self._serializer = serializer
        self.dropped = 0
        self.serialize_errors = 0
        self.produce_errors = 0
        self.flush_errors = 0
        # Per-path failure continuity: a healthy flush must not mask a
        # persistently failing produce (and vice versa), so each path
        # trips its own breaker.
        self._consecutive_produce = 0
        self._consecutive_flush = 0
        # Pipelined ingest publishes results from the step worker while
        # the service thread publishes heartbeats/acks (ADR 0111): the
        # error counters above are read-modify-writes, and interleaved
        # streaks must not lose increments (a delayed breaker trip
        # black-holes messages for longer). librdkafka's produce() is
        # itself thread-safe; the lock covers this sink's accounting.
        self._lock = threading.Lock()

    def metrics(self) -> dict[str, int]:
        """Coherent snapshot of the sink/breaker counters — the
        telemetry collector's read (ADR 0116); one lock acquisition so
        a streak's dropped/consecutive pair can never tear."""
        with self._lock:
            return {
                "dropped": self.dropped,
                "serialize_errors": self.serialize_errors,
                "produce_errors": self.produce_errors,
                "flush_errors": self.flush_errors,
                "consecutive_produce_failures": self._consecutive_produce,
                "consecutive_flush_failures": self._consecutive_flush,
            }

    def _trip_or_warn(
        self, consecutive: int, what: str, exc: BaseException
    ) -> None:
        if consecutive >= self.MAX_CONSECUTIVE_ERRORS:
            logger.error(
                "Producer circuit breaker open after %d consecutive "
                "%s failures",
                consecutive,
                what,
            )
            raise exc
        # (Only a produce failure drops a message; a failed flush(0)
        # leaves the batch queued in the producer.)
        logger.warning("%s failed (%d consecutive)", what, consecutive)

    def publish_messages(self, messages: Sequence[Message]) -> None:
        # Phase seconds (ADR 0116): encode and broker write interleave
        # per message, so they are summed over the loop from two clock
        # reads a message (each read ends one phase and starts the
        # next), not recorded as a span each: the ``sink`` span around
        # this call stays one ring entry. ``serialize`` holds the
        # serializer's ``da00`` + ``wire`` steps
        # (``livedata_sink_serialize_seconds_total``) plus this loop's
        # own overhead and the routing in ``serialize()``.
        serialize_s = produce_s = 0.0
        nbytes = 0
        mark = time.perf_counter()
        try:
            for msg in messages:
                try:
                    sm = self._serializer.serialize(msg)
                except Exception:
                    with self._lock:
                        self.serialize_errors += 1
                    logger.exception("Failed to serialize %s", msg.stream)
                    continue
                now = time.perf_counter()
                serialize_s += now - mark
                mark = now
                nbytes += len(sm.value)
                try:
                    self._produce(sm)
                finally:
                    now = time.perf_counter()
                    produce_s += now - mark
                    mark = now
        finally:
            _SERIALIZE_S.inc(serialize_s)
            _PRODUCE_S.inc(produce_s)
            SINK_BYTES.inc(nbytes)
        try:
            self._producer.flush(0)
        except Exception as err:
            with self._lock:
                self.flush_errors += 1
                self._consecutive_flush += 1
                consecutive = self._consecutive_flush
            self._trip_or_warn(consecutive, "flush", err)
        else:
            with self._lock:
                self._consecutive_flush = 0
        finally:
            _FLUSH_S.inc(time.perf_counter() - mark)

    def _produce(self, sm: SerializedMessage) -> None:
        try:
            self._producer.produce(sm.topic, sm.value, sm.key)
        except BufferError as err:
            # Producer queue full: drop rather than stall the hot
            # loop (reference sink.py:110-118) — but during an
            # extended broker outage an async producer fails
            # EXACTLY this way (the local queue never drains), so
            # sustained drops must trip the breaker too instead of
            # black-holing every message behind per-drop warnings.
            with self._lock:
                self.dropped += 1
                self._consecutive_produce += 1
                consecutive = self._consecutive_produce
            self._trip_or_warn(consecutive, "produce (queue full)", err)
        except Exception as err:
            with self._lock:
                self.produce_errors += 1
                self._consecutive_produce += 1
                consecutive = self._consecutive_produce
            self._trip_or_warn(consecutive, "produce", err)
        else:
            with self._lock:
                self._consecutive_produce = 0


class UnrollingSinkAdapter:
    """Unpacks Message[dict[str, DataArray]] (a job's result group) into one
    message per output (reference sink.py:179)."""

    def __init__(self, sink) -> None:
        self._sink = sink

    def metrics(self) -> dict[str, int]:
        """Pass through the wrapped sink's counters (duck-typed; the
        telemetry collector walks one adapter layer this way)."""
        inner = getattr(self._sink, "metrics", None)
        return inner() if callable(inner) else {}

    def publish_messages(self, messages: Sequence[Message]) -> None:
        flat: list[Message] = []
        for msg in messages:
            if isinstance(msg.value, dict):
                for out_name, da in msg.value.items():
                    flat.append(
                        Message(
                            timestamp=msg.timestamp,
                            stream=msg.stream.__class__(
                                kind=msg.stream.kind,
                                name=f"{msg.stream.name}/{out_name}",
                            ),
                            value=da,
                        )
                    )
            else:
                flat.append(msg)
        self._sink.publish_messages(flat)

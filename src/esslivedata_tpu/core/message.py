"""Stream identities and the timestamped message envelope.

Everything that flows through a service — raw facility data, synthesized
streams, commands, acks, statuses, results — is a ``Message`` carrying a
``StreamId``. The envelope is deliberately tiny: routing decisions read
only ``stream``, batching decisions read only ``timestamp``, and the
payload type is opaque to both.

Behavioral parity with reference ``core/message.py`` (the 13 wire stream
kinds, nameless control-plane stream ids, data-time message ordering);
expression is this codebase's own.
"""

from __future__ import annotations

from collections.abc import Sequence
from dataclasses import dataclass, field
from enum import StrEnum
from typing import Generic, Protocol, TypeVar, runtime_checkable

from .timestamp import Timestamp

PayloadT = TypeVar("PayloadT")
ItemT = TypeVar("ItemT")
OutT = TypeVar("OutT")

__all__ = [
    "COMMAND_STREAM",
    "Message",
    "MessageSink",
    "MessageSource",
    "RESPONSE_STREAM",
    "RUN_CONTROL_STREAM",
    "RunStart",
    "RunStop",
    "STATUS_STREAM",
    "StreamId",
    "StreamKind",
]


class StreamKind(StrEnum):
    """The kinds of streams a service consumes or produces.

    The string values are wire-contract: they appear in routing tables and
    serialized stream names, and match the reference's vocabulary so that
    deployments can mix both implementations on the same topics.
    """

    UNKNOWN = "unknown"

    # Raw facility streams (consumed).
    MONITOR_COUNTS = "monitor_counts"
    MONITOR_EVENTS = "monitor_events"
    DETECTOR_EVENTS = "detector_events"
    AREA_DETECTOR = "area_detector"
    LOG = "log"
    RUN_CONTROL = "run_control"

    # Synthesized in-process (ADR 0001).
    DEVICE = "device"

    # Livedata control plane and outputs (produced, and consumed by the
    # dashboard).
    LIVEDATA_COMMANDS = "livedata_commands"
    LIVEDATA_RESPONSES = "livedata_responses"
    LIVEDATA_DATA = "livedata_data"
    LIVEDATA_NICOS_DATA = "livedata_nicos_data"
    LIVEDATA_ROI = "livedata_roi"
    LIVEDATA_STATUS = "livedata_status"

    @property
    def is_command(self) -> bool:
        """Dispatched to the command handler, never batched as data."""
        return self is StreamKind.LIVEDATA_COMMANDS

    @property
    def is_run_control(self) -> bool:
        """Run start/stop transitions; handled before data batching."""
        return self is StreamKind.RUN_CONTROL

    @property
    def is_data(self) -> bool:
        """Everything the batcher and preprocessors may see."""
        return not (self.is_command or self.is_run_control)


@dataclass(frozen=True, slots=True, kw_only=True)
class StreamId:
    """Identity of one stream: its kind plus a source name.

    Control-plane streams are singletons per kind and carry no name; use
    :meth:`nameless` (or the module-level constants) for those.
    """

    kind: StreamKind = StreamKind.UNKNOWN
    name: str

    @classmethod
    def nameless(cls, kind: StreamKind) -> StreamId:
        return cls(kind=kind, name="")


COMMAND_STREAM = StreamId.nameless(StreamKind.LIVEDATA_COMMANDS)
RESPONSE_STREAM = StreamId.nameless(StreamKind.LIVEDATA_RESPONSES)
STATUS_STREAM = StreamId.nameless(StreamKind.LIVEDATA_STATUS)
RUN_CONTROL_STREAM = StreamId.nameless(StreamKind.RUN_CONTROL)


@dataclass(frozen=True, slots=True, kw_only=True)
class Message(Generic[PayloadT]):
    """A payload on a stream, stamped with data time.

    ``timestamp`` is the *data clock*: for data-plane messages it is when
    the payload was produced at its source (decoded from the wire), and all
    batching/windowing math runs on it — never on wall clock. The wall-clock
    default exists only for control-plane messages created in-process.

    Messages order by timestamp so heterogeneous streams can be merged with
    a plain sort.
    """

    stream: StreamId
    value: PayloadT
    timestamp: Timestamp = field(default_factory=Timestamp.now)

    def __lt__(self, other: Message[PayloadT]) -> bool:
        return self.timestamp < other.timestamp


@dataclass(frozen=True, slots=True)
class RunStart:
    """Run start announced by the facility control system (pl72 schema)."""

    run_name: str
    start_time: Timestamp
    stop_time: Timestamp | None = None


@dataclass(frozen=True, slots=True)
class RunStop:
    """Run stop announced by the facility control system (6s4t schema)."""

    run_name: str
    stop_time: Timestamp


@runtime_checkable
class MessageSource(Protocol, Generic[ItemT]):
    """Anything messages can be pulled from (Kafka, fakes, adapters)."""

    def get_messages(self) -> Sequence[ItemT]: ...


@runtime_checkable
class MessageSink(Protocol, Generic[OutT]):
    """Anything finished messages can be pushed into (Kafka, fakes)."""

    def publish_messages(self, messages: Sequence[Message[OutT]]) -> None: ...

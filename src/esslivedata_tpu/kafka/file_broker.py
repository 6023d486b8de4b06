"""File-backed broker: multi-process pub/sub without a Kafka deployment.

The integration test layer (tests/integration/, reference
tests/integration/backend.py) spawns real service subprocesses and a real
dashboard process and needs a broker they can all reach. Docker is not
available in every environment this runs in, so topics are append-only
files in a shared directory:

    <root>/<topic>.log     frames of [key_len u32][value_len u32][key][value]

Appends happen under an exclusive ``flock`` and as a single ``write`` so
concurrent producers interleave only at frame boundaries; consumers track
a *byte* offset per topic and only surface complete frames, so a reader
racing a writer sees the prefix. Offsets double as Kafka watermarks
(low = 0, high = file size), which lets ``assign_all_partitions`` pin a
restarted service at live data exactly as it does against a real broker.

This is a test/dev transport: single partition per topic, no retention,
no replication. The point is that every byte still crosses a process
boundary through the same consumer/producer protocols the confluent
client implements, so crash/restart/adoption scenarios exercise the real
code paths.
"""

from __future__ import annotations

import fcntl
import os
import logging
import struct
import time
from pathlib import Path

__all__ = [
    "FileBrokerConsumer",
    "FileBrokerProducer",
    "ensure_topics",
]

logger = logging.getLogger(__name__)

_HEADER = struct.Struct("<II")


def _topic_path(root: Path, topic: str) -> Path:
    if "/" in topic or topic.startswith("."):
        raise ValueError(f"Invalid topic name {topic!r}")
    return root / f"{topic}.log"


def ensure_topics(root: str | Path, topics) -> None:
    """Create empty topic files (the broker-side 'create topics' admin op;
    consumers validate topic existence at startup)."""
    root = Path(root)
    root.mkdir(parents=True, exist_ok=True)
    for topic in topics:
        _topic_path(root, topic).touch()


class FileMessage:
    """confluent_kafka.Message-shaped record."""

    __slots__ = ("_topic", "_value", "_key", "_next_offset")

    def __init__(
        self,
        topic: str,
        value: bytes,
        key: bytes | None,
        next_offset: int = -1,
    ) -> None:
        self._topic = topic
        self._value = value
        self._key = key
        self._next_offset = next_offset

    def topic(self) -> str:
        return self._topic

    def value(self) -> bytes:
        return self._value

    def key(self) -> bytes | None:
        return self._key

    def next_offset(self) -> int:
        """The byte offset a consumer resuming AFTER this message
        should seek to (the durability plane's bookmark unit on this
        broker, ADR 0118). File-broker offsets are byte positions —
        the confluent path uses message ``offset() + 1`` instead; the
        transport layer (kafka/source.py) probes for whichever the
        message carries."""
        return self._next_offset

    def error(self):
        return None


class FileBrokerProducer:
    def __init__(self, root: str | Path) -> None:
        self._root = Path(root)
        self._root.mkdir(parents=True, exist_ok=True)

    def produce(self, topic: str, value: bytes, key=None) -> None:
        if isinstance(key, str):
            key = key.encode()
        frame = (
            _HEADER.pack(len(key or b""), len(value))
            + (key or b"")
            + value
        )
        path = _topic_path(self._root, topic)
        with open(path, "ab") as f:
            fcntl.flock(f.fileno(), fcntl.LOCK_EX)
            try:
                f.write(frame)
                f.flush()
            finally:
                fcntl.flock(f.fileno(), fcntl.LOCK_UN)

    def poll(self, timeout: float = 0.0) -> int:
        return 0

    def flush(self, timeout: float = 0.0) -> int:
        return 0


class _TopicMeta:
    def __init__(self) -> None:
        self.partitions = {0: object()}


class _Metadata:
    def __init__(self, names) -> None:
        self.topics = {name: _TopicMeta() for name in names}


class FileBrokerConsumer:
    """Both halves of the consumer surface: the assignment handshake
    (list_topics/get_watermark_offsets/assign) and the consume loop."""

    def __init__(self, root: str | Path) -> None:
        self._root = Path(root)
        # topic -> next byte offset to read
        self._offsets: dict[str, int] = {}
        # round-robin cursor over topics (see _consume_once)
        self._rr = 0

    # -- assignment surface ------------------------------------------------
    def list_topics(self, timeout: float = 0.0) -> _Metadata:
        return _Metadata(
            p.stem for p in sorted(self._root.glob("*.log"))
        )

    def get_watermark_offsets(
        self, partition, timeout: float = 0.0
    ) -> tuple[int, int]:
        path = _topic_path(self._root, partition.topic)
        return (0, path.stat().st_size if path.exists() else 0)

    def assign(self, partitions) -> None:
        for tp in partitions:
            offset = getattr(tp, "offset", -1)
            if offset is None or offset < 0:
                offset = 0
            self._offsets[tp.topic] = offset

    def subscribe(self, topics) -> None:
        """Subscribe-at-end (the dashboard's live-data semantics)."""
        for topic in topics:
            path = _topic_path(self._root, topic)
            self._offsets[topic] = (
                path.stat().st_size if path.exists() else 0
            )

    # -- consume loop ------------------------------------------------------
    def consume(self, num_messages: int, timeout: float = 0.0):
        out = self._consume_once(num_messages)
        if not out and timeout > 0:
            # Honor the blocking contract the confluent client has: the
            # service consume thread loops on consume() with no sleep of
            # its own, so returning instantly on empty would busy-spin a
            # core per service doing stat() calls.
            time.sleep(timeout)
            out = self._consume_once(num_messages)
        return out

    def _consume_once(self, num_messages: int) -> list[FileMessage]:
        out: list[FileMessage] = []
        # Rotate the starting topic across calls: with a fixed order, a
        # sustained high-volume first topic (detector data) would fill the
        # whole budget every call and starve status/command topics.
        topics = list(self._offsets)
        if not topics:
            return out
        self._rr %= len(topics)
        order = topics[self._rr:] + topics[: self._rr]
        self._rr = (self._rr + 1) % len(topics)
        # One cut through all topics, taken at one instant, and no topic
        # is read past it. Reading takes time (a burst of detector data
        # is hundreds of MB): a topic read later would otherwise run
        # ahead of one read earlier, and a window would close on its
        # later pulse while the earlier topic's last pulse was still
        # unread. A producer appends a pulse's messages in time order,
        # so such a cut never holds a message without those before it.
        sizes = self._cut(order)
        for topic in order:
            if len(out) >= num_messages:
                break
            out.extend(
                self._read_topic(
                    topic, num_messages - len(out), sizes[topic]
                )
            )
        return out

    def _cut(self, topics: list[str]) -> dict[str, int]:
        """Every topic's size at one instant. An append holds its
        topic's exclusive ``flock`` (both producers), so while a shared
        lock is held on every topic none of them grows: sizes read one
        after another with no lock are a millisecond apart (more when
        the thread loses the GIL between them), which in a burst is a
        monitor message and the next pulse's first detector message."""
        held: dict[str, int] = {}
        try:
            for topic in topics:
                try:
                    fd = os.open(_topic_path(self._root, topic), os.O_RDONLY)
                except FileNotFoundError:
                    continue  # no such topic yet: nothing to read
                held[topic] = fd
                fcntl.flock(fd, fcntl.LOCK_SH)
            return {
                topic: os.fstat(held[topic]).st_size if topic in held else 0
                for topic in topics
            }
        finally:
            for fd in held.values():
                os.close(fd)  # and with it the lock

    def _read_topic(
        self, topic: str, limit: int, size: int
    ) -> list[FileMessage]:
        """Up to ``limit`` whole frames that end at or before ``size``."""
        offset = self._offsets.get(topic, 0)
        if size <= offset:
            return []
        out: list[FileMessage] = []
        with open(_topic_path(self._root, topic), "rb") as f:
            f.seek(offset)
            while len(out) < limit and offset + _HEADER.size <= size:
                header = f.read(_HEADER.size)
                if len(header) < _HEADER.size:
                    break
                key_len, value_len = _HEADER.unpack(header)
                if offset + _HEADER.size + key_len + value_len > size:
                    break  # written after the cut: the next poll's
                payload = f.read(key_len + value_len)
                if len(payload) < key_len + value_len:
                    # Partial frame: a writer is mid-append; retry later.
                    break
                offset = f.tell()
                out.append(
                    FileMessage(
                        topic,
                        payload[key_len:],
                        payload[:key_len] or None,
                        next_offset=offset,
                    )
                )
        self._offsets[topic] = offset
        return out

    def positions(self) -> dict[str, int]:
        """Next-read byte offset per assigned topic — the consumer-side
        bookmark surface (durability plane, ADR 0118)."""
        return dict(self._offsets)

    def close(self) -> None:
        self._offsets.clear()

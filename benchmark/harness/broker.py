"""Client side of the program's file broker (``--broker-dir``).

The format is the program's: ``<root>/<topic>.log`` holds frames of
``[key_len u32][value_len u32][key][value]``, appended under an
exclusive ``flock`` in one write; readers keep a byte offset and only
surface complete frames.
"""

from __future__ import annotations

import fcntl
import os
import struct
from pathlib import Path

_HEADER = struct.Struct("<II")


def ensure_topics(root: Path, topics) -> None:
    root.mkdir(parents=True, exist_ok=True)
    for topic in topics:
        (root / f"{topic}.log").touch()


class Producer:
    """Appends frames to topics; one open descriptor per topic."""

    def __init__(self, root: Path) -> None:
        self._root = Path(root)
        self._fds: dict[str, int] = {}
        self.bytes_written = 0

    def produce(self, topic: str, value) -> None:
        fd = self._fds.get(topic)
        if fd is None:
            fd = self._fds[topic] = os.open(
                self._root / f"{topic}.log", os.O_WRONLY | os.O_APPEND | os.O_CREAT
            )
        frame = _HEADER.pack(0, len(value)) + value
        fcntl.flock(fd, fcntl.LOCK_EX)
        try:
            written = os.write(fd, frame)
        finally:
            fcntl.flock(fd, fcntl.LOCK_UN)
        if written != len(frame):
            raise OSError(f"short write on {topic}: {written} of {len(frame)}")
        self.bytes_written += written

    def close(self) -> None:
        for fd in self._fds.values():
            os.close(fd)
        self._fds.clear()


class Consumer:
    """Follows one topic from a byte offset."""

    def __init__(self, root: Path, topic: str) -> None:
        self._path = Path(root) / f"{topic}.log"
        self._offset = 0

    def poll(self, limit: int = 64) -> list[bytes]:
        try:
            size = self._path.stat().st_size
        except FileNotFoundError:
            return []
        if size <= self._offset:
            return []
        out = []
        with open(self._path, "rb") as f:
            f.seek(self._offset)
            while len(out) < limit:
                header = f.read(_HEADER.size)
                if len(header) < _HEADER.size:
                    break
                key_len, value_len = _HEADER.unpack(header)
                payload = f.read(key_len + value_len)
                if len(payload) < key_len + value_len:
                    break  # a writer is mid-append
                self._offset = f.tell()
                out.append(payload[key_len:])
        return out

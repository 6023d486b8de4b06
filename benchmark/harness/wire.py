"""The two schemas the client side speaks: ev44 out, da00 in.

Written against the ESS streaming-data-types schemas with the
``flatbuffers`` runtime only, so that a change to the program's own
codec cannot move the yardstick with it.
"""

from __future__ import annotations

import struct

import flatbuffers
import numpy as np
from flatbuffers import number_types as N
from flatbuffers.table import Table

#: da00_dtype (da00_dataarray.fbs): none=0, int8..float64, c_string=11.
_DA00_DTYPES = (
    None, np.int8, np.uint8, np.int16, np.uint16, np.int32, np.uint32,
    np.int64, np.uint64, np.float32, np.float64, None,
)


def encode_ev44(
    source_name: str,
    message_id: int,
    reference_time_ns: int,
    time_of_flight: np.ndarray,
    pixel_id: np.ndarray,
) -> bytes:
    """One ev44 message carrying one pulse (one reference time)."""
    b = flatbuffers.Builder(1024)
    pid = b.CreateNumpyVector(np.ascontiguousarray(pixel_id, np.int32))
    tof = b.CreateNumpyVector(np.ascontiguousarray(time_of_flight, np.int32))
    rti = b.CreateNumpyVector(np.zeros(1, np.int32))
    rt = b.CreateNumpyVector(np.array([reference_time_ns], np.int64))
    src = b.CreateString(source_name)
    b.StartObject(6)
    b.PrependUOffsetTRelativeSlot(0, src, 0)
    b.PrependInt64Slot(1, message_id, 0)
    b.PrependUOffsetTRelativeSlot(2, rt, 0)
    b.PrependUOffsetTRelativeSlot(3, rti, 0)
    b.PrependUOffsetTRelativeSlot(4, tof, 0)
    b.PrependUOffsetTRelativeSlot(5, pid, 0)
    b.Finish(b.EndObject(), file_identifier=b"ev44")
    return bytes(b.Output())


class Ev44Template:
    """A pre-encoded ev44 message whose id and reference time are
    patched in place: the pool is encoded once in set-up and cycled
    with fresh timestamps, so sending a pulse costs one write."""

    _ID_MARK = 0x1122334455667788
    _TIME_MARK = 0x0A0B0C0D0E0F1011

    def __init__(self, source_name: str, toa: np.ndarray, ids: np.ndarray) -> None:
        self.buf = bytearray(
            encode_ev44(source_name, self._ID_MARK, self._TIME_MARK, toa, ids)
        )
        self._id_at = self._find(self._ID_MARK)
        self._time_at = self._find(self._TIME_MARK)

    def _find(self, mark: int) -> int:
        raw = struct.pack("<q", mark)
        at = self.buf.find(raw)
        if at < 0 or self.buf.find(raw, at + 1) >= 0:
            raise ValueError("ev44 template: patch point is not unique")
        return at

    def stamp(self, message_id: int, reference_time_ns: int) -> bytearray:
        struct.pack_into("<q", self.buf, self._id_at, message_id)
        struct.pack_into("<q", self.buf, self._time_at, reference_time_ns)
        return self.buf


def _string(tab: Table, slot: int) -> str:
    off = tab.Offset(4 + 2 * slot)
    return tab.String(off + tab.Pos).decode() if off else ""


def _vector(tab: Table, slot: int, dtype) -> np.ndarray:
    off = tab.Offset(4 + 2 * slot)
    if not off:
        return np.empty(0, dtype)
    return np.frombuffer(
        tab.Bytes, dtype, count=tab.VectorLen(off), offset=tab.Vector(off)
    )


def decode_da00(buf) -> tuple[str, int, dict[str, np.ndarray]]:
    """(source_name, timestamp_ns, {variable name: shaped array}); the
    arrays are views into ``buf``."""
    if bytes(buf[4:8]) != b"da00":
        raise ValueError(f"not a da00 message: {bytes(buf[4:8])!r}")
    root = Table(buf, flatbuffers.encode.Get(N.UOffsetTFlags.packer_type, buf, 0))
    off = root.Offset(4 + 2 * 1)
    timestamp = root.Get(N.Int64Flags, off + root.Pos) if off else 0
    variables = {}
    off = root.Offset(4 + 2 * 2)
    for i in range(root.VectorLen(off) if off else 0):
        var = Table(buf, root.Indirect(root.Vector(off) + 4 * i))
        code_at = var.Offset(4 + 2 * 4)
        code = var.Get(N.Int8Flags, code_at + var.Pos) if code_at else 0
        dtype = _DA00_DTYPES[code] if 0 <= code < len(_DA00_DTYPES) else None
        if dtype is None:
            raise ValueError(f"da00 variable with dtype code {code}")
        shape = tuple(int(s) for s in _vector(var, 6, np.int64))
        data = _vector(var, 7, np.uint8).view(dtype)
        variables[_string(var, 0)] = data.reshape(shape) if shape else data.reshape(())
    return _string(root, 0), timestamp, variables

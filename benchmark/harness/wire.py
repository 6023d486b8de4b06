"""The schemas the client side speaks: ev44 and ad00 out, da00 in.

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

from .traffic import AD00_DTYPES

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


def encode_ad00(
    source_name: str, frame_id: int, timestamp_ns: int, frame: np.ndarray
) -> bytes:
    """One ad00 message carrying one frame
    (``schemas/ad00_area_detector_array.fbs``: source_name, id,
    timestamp, data_type, dimensions, data). ``data_type`` is ad00's own
    ``DType`` code (uint16 = 3), not da00's."""
    frame = np.ascontiguousarray(frame)
    b = flatbuffers.Builder(frame.nbytes + 1024)
    data = b.CreateNumpyVector(frame.reshape(-1).view(np.uint8))
    dims = b.CreateNumpyVector(np.asarray(frame.shape, np.int64))
    src = b.CreateString(source_name)
    b.StartObject(6)
    b.PrependUOffsetTRelativeSlot(0, src, 0)
    b.PrependInt64Slot(1, frame_id, 0)
    b.PrependInt64Slot(2, timestamp_ns, 0)
    b.PrependInt8Slot(3, AD00_DTYPES.index(frame.dtype.name), 0)
    b.PrependUOffsetTRelativeSlot(4, dims, 0)
    b.PrependUOffsetTRelativeSlot(5, data, 0)
    b.Finish(b.EndObject(), file_identifier=b"ad00")
    return bytes(b.Output())


class _Template:
    """A pre-encoded message whose id and time are patched in place: the
    pool is encoded once in set-up and cycled with fresh timestamps, so
    sending a message costs one write."""

    _ID_MARK = 0x1122334455667788
    _TIME_MARK = 0x0A0B0C0D0E0F1011

    def __init__(self, encoded: bytes) -> None:
        self.buf = bytearray(encoded)
        self._id_at = self._find(self._ID_MARK)
        self._time_at = self._find(self._TIME_MARK)

    def _find(self, mark: int) -> int:
        raw = struct.pack("<q", mark)
        at = self.buf.find(raw)
        if at < 0 or self.buf.find(raw, at + 1) >= 0:
            raise ValueError(f"{type(self).__name__}: patch point is not unique")
        return at

    def stamp(self, message_id: int, time_ns: int) -> bytearray:
        struct.pack_into("<q", self.buf, self._id_at, message_id)
        struct.pack_into("<q", self.buf, self._time_at, time_ns)
        return self.buf


class Ev44Template(_Template):
    """An ev44 message of one pulse; ``stamp`` patches its message id and
    reference time."""

    def __init__(self, source_name: str, toa: np.ndarray, ids: np.ndarray) -> None:
        super().__init__(encode_ev44(source_name, self._ID_MARK, self._TIME_MARK, toa, ids))


class Ad00Template(_Template):
    """An ad00 message of one frame; ``stamp`` patches its id and
    timestamp."""

    def __init__(self, source_name: str, frame: np.ndarray) -> None:
        super().__init__(encode_ad00(source_name, self._ID_MARK, self._TIME_MARK, frame))


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

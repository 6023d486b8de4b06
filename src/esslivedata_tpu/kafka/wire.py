"""Clean-room FlatBuffers codecs for the ESS streaming schema family.

The reference consumes/produces these schemas through the generated
``ess-streaming-data-types`` package (reference: kafka/message_adapter.py:
13-21); that package is not available here, so the same logical payloads are
implemented directly on the flatbuffers runtime: a generic vtable reader for
decode (zero-copy numpy views into the message buffer — the moral
equivalent of the reference's fast-path partial decode,
message_adapter.py:360) and low-level Builder slots for encode.

Schemas carry the standard 4-byte file identifiers (ev44, f144, da00, ad00,
x5f2, pl72, 6s4t). Field layouts (vtable slot ids, scalar widths, union
tags, enum orderings) follow the vendored schema contract in
``schemas/*.fbs`` and are VERIFIED against it by
``tests/kafka/golden_wire_test.py``: an independent mini-.fbs parser +
generic buffer walker checks every encoder's bytes field by field, and
golden byte fixtures pin the exact serialization against drift. The
schemas themselves are reconstructions of the public ECDC family (see
schemas/README.md for the provenance caveat).

Payload field conventions (wire layout per schemas/*.fbs; the Python
dataclasses normalize where noted):
- ev44: source_name, message_id, reference_time[] (ns epoch pulse times),
  reference_time_index[], time_of_flight[] (ns within pulse, int32),
  pixel_id[] (int32; zero-length vector for monitors).
- f144: source_name, value as a 20-member typed union (scalar and array
  forms of i8..u64/f32/f64 with a hidden value_type tag), timestamp (ns
  epoch). Decode normalizes every member to a float64 vector.
- da00: source_name, timestamp (ns), variables[] each with name, unit,
  label, source, dtype enum (none..c_string), axes[], shape[] (int64),
  raw data bytes.
- ad00: source_name, frame id, timestamp (ns), dtype enum,
  dimensions[] (int64), raw data.
- x5f2: software_name/version, service_id, host_name, process_id (u32),
  update_interval (ms, u32), status_json.
- pl72: start/stop times (u64 ns), run_name, instrument_name, plus
  nexus_structure/job_id/service_id when set. 6s4t: stop_time (u64 ns),
  run_name, job_id/service_id/command_id when set.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Any

import flatbuffers
import numpy as np

__all__ = [
    "Ad00Image",
    "Da00Variable",
    "Ev44Batch",
    "Ev44Message",
    "Ev44View",
    "F144Message",
    "RunStartMessage",
    "RunStopMessage",
    "X5f2Status",
    "decode_6s4t",
    "decode_ad00",
    "decode_da00",
    "decode_ev44",
    "decode_ev44_batch",
    "decode_f144",
    "decode_pl72",
    "decode_x5f2",
    "encode_6s4t",
    "encode_ad00",
    "encode_da00",
    "encode_ev44",
    "encode_f144",
    "encode_pl72",
    "encode_x5f2",
    "get_schema",
    "walk_ev44",
]


class WireError(ValueError):
    """Malformed or wrong-schema buffer."""


def _np_vector(b: flatbuffers.Builder, arr: np.ndarray) -> int | None:
    """CreateNumpyVector that is safe for empty arrays.

    This flatbuffers runtime corrupts empty vectors written near
    differently-aligned neighbors (the stored offset lands on adjacent
    data), so empty arrays are not written at all — ``None`` means "omit
    the slot"; an absent vector decodes as empty, which is semantically
    identical in flatbuffers."""
    arr = np.ascontiguousarray(arr)
    if arr.size == 0:
        return None
    return b.CreateNumpyVector(arr)


def _np_vector_required(b: flatbuffers.Builder, arr: np.ndarray) -> int:
    """Vector for a schema slot marked ``(required)``: an empty input
    writes an explicit zero-length vector (StartVector/EndVector — safe,
    unlike this runtime's CreateNumpyVector on empty arrays) so the slot
    is always present, as generated readers/verifiers expect."""
    arr = np.ascontiguousarray(arr)
    if arr.size == 0:
        itemsize = max(arr.dtype.itemsize, 1)
        b.StartVector(itemsize, 0, itemsize)
        return b.EndVector()
    return b.CreateNumpyVector(arr)


def _prepend_vec_slot(b: flatbuffers.Builder, slot: int, off: int | None) -> None:
    if off is not None:
        b.PrependUOffsetTRelativeSlot(slot, off, 0)


def get_schema(buf: bytes) -> str:
    """4-char file identifier of a serialized message ('ev44', ...)."""
    if len(buf) < 8:
        raise WireError(f"Buffer too short for flatbuffer: {len(buf)} bytes")
    try:
        return buf[4:8].decode("ascii")
    except UnicodeDecodeError as err:
        raise WireError("Invalid file identifier") from err


# ---------------------------------------------------------------------------
# Generic vtable reader
# ---------------------------------------------------------------------------


#: Precompiled struct formats for the decode hot path (populated lazily;
#: the working set is the handful of scalar formats the schemas use).
_STRUCTS: dict[str, struct.Struct] = {}


class _Tbl:
    """Minimal flatbuffers table reader (decode side only).

    Every offset read is bounds-checked through :meth:`_read`: a hostile
    buffer steering an offset out of range raises :class:`WireError`
    (the per-message containment contract), never ``struct.error`` or a
    wild slice.
    """

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int) -> None:
        if pos < 0 or pos + 4 > len(buf):
            raise WireError("Table position out of range")
        self.buf = buf
        self.pos = pos

    def _read(self, fmt: str, offset: int):
        """Bounds-checked struct read; corrupt offsets become WireError.
        Hot path: format structs are precompiled (size lookup is free)."""
        st = _STRUCTS.get(fmt)
        if st is None:
            st = _STRUCTS[fmt] = struct.Struct(fmt)
        if offset < 0 or offset + st.size > len(self.buf):
            raise WireError("Offset out of range")
        return st.unpack_from(self.buf, offset)[0]

    @classmethod
    def root(cls, buf: bytes, expected_id: str | None = None) -> "_Tbl":
        if len(buf) < 8:
            raise WireError("Buffer too short")
        if expected_id is not None and get_schema(buf) != expected_id:
            raise WireError(
                f"Expected schema {expected_id!r}, got {get_schema(buf)!r}"
            )
        (off,) = struct.unpack_from("<I", buf, 0)
        return cls(buf, off)

    def _slot(self, slot: int) -> int | None:
        soff = self._read("<i", self.pos)
        vt = self.pos - soff
        if vt < 0 or vt + 4 > len(self.buf):
            raise WireError("Corrupt vtable offset")
        vt_len = self._read("<H", vt)
        entry = 4 + slot * 2
        if entry + 2 > vt_len:
            return None
        foff = self._read("<H", vt + entry)
        if foff == 0:
            return None
        return self.pos + foff

    def scalar(self, slot: int, fmt: str, default=0):
        p = self._slot(slot)
        if p is None:
            return default
        return self._read(fmt, p)

    def _indirect(self, p: int) -> int:
        off = self._read("<I", p)
        target = p + off
        if target < 0 or target + 4 > len(self.buf):
            raise WireError("Indirect offset out of range")
        return target

    def _string_at(self, sp: int) -> str:
        n = self._read("<I", sp)
        if sp + 4 + n > len(self.buf):
            raise WireError("String extends past buffer end")
        try:
            return bytes(self.buf[sp + 4 : sp + 4 + n]).decode("utf-8")
        except UnicodeDecodeError as err:
            raise WireError(f"Invalid UTF-8 string: {err}") from err

    def string(self, slot: int, default: str = "") -> str:
        p = self._slot(slot)
        if p is None:
            return default
        return self._string_at(self._indirect(p))

    def vector_np(self, slot: int, dtype) -> np.ndarray:
        p = self._slot(slot)
        if p is None:
            return np.empty(0, dtype=dtype)
        vp = self._indirect(p)
        n = self._read("<I", vp)
        itemsize = np.dtype(dtype).itemsize
        end = vp + 4 + n * itemsize
        if end > len(self.buf):
            raise WireError("Vector extends past buffer end")
        return np.frombuffer(self.buf, dtype=dtype, count=n, offset=vp + 4)

    def table(self, slot: int) -> "_Tbl | None":
        p = self._slot(slot)
        if p is None:
            return None
        return _Tbl(self.buf, self._indirect(p))

    def tables(self, slot: int) -> list["_Tbl"]:
        p = self._slot(slot)
        if p is None:
            return []
        vp = self._indirect(p)
        n = self._read("<I", vp)
        if vp + 4 + n * 4 > len(self.buf):
            raise WireError("Table vector extends past buffer end")
        out = []
        for i in range(n):
            ep = vp + 4 + i * 4
            out.append(_Tbl(self.buf, self._indirect(ep)))
        return out

    def strings(self, slot: int) -> list[str]:
        p = self._slot(slot)
        if p is None:
            return []
        vp = self._indirect(p)
        n = self._read("<I", vp)
        if vp + 4 + n * 4 > len(self.buf):
            raise WireError("String vector extends past buffer end")
        out = []
        for i in range(n):
            ep = vp + 4 + i * 4
            out.append(self._string_at(self._indirect(ep)))
        return out


# ---------------------------------------------------------------------------
# dtype enums (per schema: da00 and ad00 declare DIFFERENT orderings)
# ---------------------------------------------------------------------------

#: da00_dtype (schemas/da00_dataarray.fbs): none=0, then int8..float64,
#: c_string=11. Index 0 and 11 have no numpy dtype (None sentinels).
_DA00_DTYPES: list[np.dtype | None] = [
    None,
    np.dtype(np.int8),
    np.dtype(np.uint8),
    np.dtype(np.int16),
    np.dtype(np.uint16),
    np.dtype(np.int32),
    np.dtype(np.uint32),
    np.dtype(np.int64),
    np.dtype(np.uint64),
    np.dtype(np.float32),
    np.dtype(np.float64),
    None,  # c_string
]
_DA00_CODE = {dt: i for i, dt in enumerate(_DA00_DTYPES) if dt is not None}

#: ad00 DType (schemas/ad00_area_detector_array.fbs): int8=0..float64=9.
_AD00_DTYPES: list[np.dtype] = [
    np.dtype(np.int8),
    np.dtype(np.uint8),
    np.dtype(np.int16),
    np.dtype(np.uint16),
    np.dtype(np.int32),
    np.dtype(np.uint32),
    np.dtype(np.int64),
    np.dtype(np.uint64),
    np.dtype(np.float32),
    np.dtype(np.float64),
]
_AD00_CODE = {dt: i for i, dt in enumerate(_AD00_DTYPES)}


def da00_encodable(dtype) -> bool:
    """True when ``dtype`` maps into the da00 dtype enum above — i.e.
    the wire serializer (and the delta codec downstream of it) can
    carry an array of it. The trace pass (JGL105) proves every tick
    publish output against this, so a program edit cannot route an
    unencodable dtype at the wire only to fail at runtime."""
    try:
        return np.dtype(dtype) in _DA00_CODE
    except TypeError:
        return False


def _dtype_code(arr: np.ndarray, table: dict) -> int:
    try:
        return table[arr.dtype]
    except KeyError as err:
        raise WireError(f"Unsupported wire dtype {arr.dtype}") from err


# ---------------------------------------------------------------------------
# ev44 — event data
# ---------------------------------------------------------------------------


@dataclass(frozen=True, slots=True)
class Ev44Message:
    source_name: str
    message_id: int
    reference_time: np.ndarray  # int64 ns epoch
    reference_time_index: np.ndarray  # int32
    time_of_flight: np.ndarray  # int32 ns within pulse
    pixel_id: np.ndarray  # int32; empty for monitor events


def encode_ev44(
    source_name: str,
    message_id: int,
    reference_time: np.ndarray,
    reference_time_index: np.ndarray,
    time_of_flight: np.ndarray,
    pixel_id: np.ndarray | None = None,
) -> bytes:
    b = flatbuffers.Builder(1024)
    # All four vectors are (required) in the schema: empty inputs (e.g.
    # pixel_id for monitor events) still write a zero-length vector.
    if pixel_id is None:
        pixel_id = np.empty(0, np.int32)
    pid_off = _np_vector_required(
        b, np.ascontiguousarray(pixel_id, np.int32)
    )
    tof_off = _np_vector_required(
        b, np.ascontiguousarray(time_of_flight, np.int32)
    )
    rti_off = _np_vector_required(
        b, np.ascontiguousarray(reference_time_index, np.int32)
    )
    rt_off = _np_vector_required(
        b, np.ascontiguousarray(reference_time, np.int64)
    )
    src_off = b.CreateString(source_name)
    b.StartObject(6)
    b.PrependUOffsetTRelativeSlot(0, src_off, 0)
    b.PrependInt64Slot(1, message_id, 0)
    b.PrependUOffsetTRelativeSlot(2, rt_off, 0)
    b.PrependUOffsetTRelativeSlot(3, rti_off, 0)
    b.PrependUOffsetTRelativeSlot(4, tof_off, 0)
    b.PrependUOffsetTRelativeSlot(5, pid_off, 0)
    b.Finish(b.EndObject(), file_identifier=b"ev44")
    return bytes(b.Output())


def decode_ev44(buf: bytes) -> Ev44Message:
    t = _Tbl.root(buf, "ev44")
    return Ev44Message(
        source_name=t.string(0),
        message_id=t.scalar(1, "<q"),
        reference_time=t.vector_np(2, np.int64),
        reference_time_index=t.vector_np(3, np.int32),
        time_of_flight=t.vector_np(4, np.int32),
        pixel_id=t.vector_np(5, np.int32),
    )


# ---------------------------------------------------------------------------
# ev44 batch decode plane (ADR 0125)
# ---------------------------------------------------------------------------

#: Module-level precompiled structs for the header walk: ``walk_ev44``
#: is the per-message cost of a whole poll's decode, so even the
#: ``_STRUCTS`` dict lookup is off its path.
_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")
_I32 = struct.Struct("<i")
_I64 = struct.Struct("<q")

_INT32_SIZE = 4
_INT64_SIZE = 8


@dataclass(slots=True)
class Ev44View:
    """Header-only view of one ev44 message: the routing fields plus the
    (offset, count) coordinates of the payload vectors — NO payload
    ndarrays are materialized. ``walk_ev44`` builds one per message with
    a single vtable walk; payloads land later, straight into a batch
    arena via :meth:`fill_into` (or lazily via the ``time_of_flight`` /
    ``pixel_id`` properties for per-message consumers). Treat as
    immutable; not ``frozen`` because the per-field
    ``object.__setattr__`` would double construction cost on the
    per-message hot path.

    ``reference_time_ns`` is the LAST pulse time (what the adapters
    timestamp messages with), or ``None`` when the vector is empty.
    """

    buf: bytes  # the whole wire buffer (any buffer protocol object)
    source_name: str
    message_id: int
    reference_time_ns: int | None
    tof_off: int  # byte offset of time_of_flight data (int32)
    n_tof: int
    pid_off: int  # byte offset of pixel_id data (int32)
    n_pid: int  # 0 for monitor events

    @property
    def n_events(self) -> int:
        return self.n_tof

    @property
    def nbytes(self) -> int:
        return len(self.buf)

    @property
    def time_of_flight(self) -> np.ndarray:
        """Zero-copy int32 view into the wire buffer."""
        return np.frombuffer(
            self.buf, dtype=np.int32, count=self.n_tof, offset=self.tof_off
        )

    @property
    def pixel_id(self) -> np.ndarray:
        """Zero-copy int32 view into the wire buffer (empty: monitor)."""
        return np.frombuffer(
            self.buf, dtype=np.int32, count=self.n_pid, offset=self.pid_off
        )

    def fill_into(self, pid_dst: np.ndarray, toa_dst: np.ndarray) -> None:
        """Land this message's payload into arena slices of length
        ``n_events``: pixel ids copy int32→int32, times of flight cast
        int32→float32 fused into the assignment (no intermediate array).
        Monitor messages (``n_pid == 0``) zero-fill the pixel slice —
        the same pixel-0 convention ``ToEventBatch`` applies to
        ``MonitorEvents``. A populated ``pixel_id`` whose length
        disagrees with ``time_of_flight`` raises :class:`WireError`
        (never a numpy broadcast error)."""
        toa_dst[:] = self.time_of_flight
        if not self.n_pid:
            pid_dst[:] = 0
        elif self.n_pid == self.n_tof:
            pid_dst[:] = self.pixel_id
        else:
            raise WireError(
                f"ev44 pixel_id length {self.n_pid} != "
                f"time_of_flight length {self.n_tof}"
            )


def walk_ev44(buf) -> Ev44View:
    """One bounds-checked vtable walk over an ev44 header.

    Reads every field the ingress path needs (source name, message id,
    last pulse time, payload vector coordinates) in a single pass with
    module-level precompiled structs — no :class:`_Tbl` object, no
    per-vector ndarray. Raises :class:`WireError` for every malformed
    input (the per-message containment contract). A ``pixel_id`` length
    disagreeing with ``time_of_flight`` is NOT rejected here — the
    monitor adapters accept such messages as pixel-less (reference
    behavior), so length policy belongs to the consumer
    (:meth:`Ev44View.fill_into` / ``decode_ev44_batch`` quarantine).
    """
    n = len(buf)
    if n < 8:
        raise WireError(f"Buffer too short for flatbuffer: {n} bytes")
    if bytes(buf[4:8]) != b"ev44":
        raise WireError(f"Expected schema 'ev44', got {get_schema(buf)!r}")
    # Straight-line walk, ~16 precompiled struct reads, ONE containment
    # boundary: every corrupt-offset shape either trips an explicit
    # range check below or runs ``unpack_from`` past the buffer end,
    # which raises ``struct.error`` — converted to :class:`WireError` in
    # the except arm. Negative read offsets cannot occur (all offsets
    # are u16/u32 reads; the one subtraction, ``vt``, is checked), so
    # ``unpack_from``'s from-the-end negative indexing is unreachable.
    u16 = _U16.unpack_from
    u32 = _U32.unpack_from
    i64 = _I64.unpack_from
    try:
        pos = u32(buf, 0)[0]
        vt = pos - _I32.unpack_from(buf, pos)[0]
        if vt < 0:
            raise WireError("Corrupt vtable offset")
        vt_len = u16(buf, vt)[0]

        # source_name (slot 0): string = u32 length + utf-8 bytes.
        source_name = ""
        foff = u16(buf, vt + 4)[0] if vt_len >= 6 else 0
        if foff:
            p = pos + foff
            sp = p + u32(buf, p)[0]
            slen = u32(buf, sp)[0]
            if sp + 4 + slen > n:
                raise WireError("String extends past buffer end")
            try:
                source_name = bytes(buf[sp + 4 : sp + 4 + slen]).decode(
                    "utf-8"
                )
            except UnicodeDecodeError as err:
                raise WireError(f"Invalid UTF-8 string: {err}") from err

        foff = u16(buf, vt + 6)[0] if vt_len >= 8 else 0
        message_id = i64(buf, pos + foff)[0] if foff else 0

        # reference_time (slot 2, int64): only the LAST element is read
        # — the adapters' message timestamp — not the whole vector.
        reference_time_ns = None
        foff = u16(buf, vt + 8)[0] if vt_len >= 10 else 0
        if foff:
            p = pos + foff
            vp = p + u32(buf, p)[0]
            n_rt = u32(buf, vp)[0]
            if vp + 4 + n_rt * _INT64_SIZE > n:
                raise WireError("Vector extends past buffer end")
            if n_rt:
                reference_time_ns = i64(
                    buf, vp + 4 + (n_rt - 1) * _INT64_SIZE
                )[0]

        # time_of_flight (slot 4) / pixel_id (slot 5), int32 vectors.
        tof_off = n_tof = 0
        foff = u16(buf, vt + 12)[0] if vt_len >= 14 else 0
        if foff:
            p = pos + foff
            vp = p + u32(buf, p)[0]
            n_tof = u32(buf, vp)[0]
            if vp + 4 + n_tof * _INT32_SIZE > n:
                raise WireError("Vector extends past buffer end")
            tof_off = vp + 4

        pid_off = n_pid = 0
        foff = u16(buf, vt + 14)[0] if vt_len >= 16 else 0
        if foff:
            p = pos + foff
            vp = p + u32(buf, p)[0]
            n_pid = u32(buf, vp)[0]
            if vp + 4 + n_pid * _INT32_SIZE > n:
                raise WireError("Vector extends past buffer end")
            pid_off = vp + 4
    except struct.error as err:
        raise WireError(f"Offset out of range: {err}") from err
    return Ev44View(
        buf=buf,
        source_name=source_name,
        message_id=message_id,
        reference_time_ns=reference_time_ns,
        tof_off=tof_off,
        n_tof=n_tof,
        pid_off=pid_off,
        n_pid=n_pid,
    )


@dataclass(slots=True)
class Ev44Batch:
    """One poll's worth of ev44 payloads as a single contiguous triple.

    ``pixel_id``/``toa`` are views over a reusable decode arena
    (``core.device_event_cache.DecodeArenaPool``) of exactly
    ``n_events`` elements; ``offsets`` is the int64 prefix-sum such that
    message ``i``'s events live at ``[offsets[i]:offsets[i+1])``.
    ``views`` holds the per-message headers (routing metadata only);
    ``errors`` the quarantined ``(input index, WireError)`` pairs.
    ``lease`` owns the arena — the arrays stay valid (and the arena out
    of the pool) for exactly as long as the batch/lease is referenced.
    """

    pixel_id: np.ndarray  # int32 [n_events]
    toa: np.ndarray  # float32 [n_events]
    offsets: np.ndarray  # int64 [len(views) + 1]
    views: list[Ev44View]
    errors: list[tuple[int, WireError]]
    n_messages: int  # input buffers, including quarantined ones
    nbytes: int  # wire bytes of the decoded (non-quarantined) messages
    lease: Any = None

    @property
    def n_events(self) -> int:
        return int(self.offsets[-1])


def decode_ev44_batch(buffers, *, arena=None) -> Ev44Batch:
    """Vectorized decode of a whole poll of ev44 buffers.

    Pass 1 walks each header once (:func:`walk_ev44`); a malformed
    message is quarantined into ``errors`` (and counted on
    ``livedata_decode_errors_total{schema="ev44"}``) WITHOUT poisoning
    the rest of the batch. Pass 2 leases a pinned staging arena sized to
    the total event count and lands every payload zero-copy-from-wire
    into it — one contiguous (toa, pixel, offsets) triple, no
    per-message ndarray or :class:`Ev44Message` allocation.

    ``arena`` overrides the arena lease (object with ``pixel``/``toa``
    ndarrays of at least ``n_events`` elements) for callers that manage
    their own reuse; by default one is leased from
    ``core.device_event_cache.default_decode_pool()``.
    """
    views: list[Ev44View] = []
    errors: list[tuple[int, WireError]] = []
    nbytes = 0
    n_in = 0
    for i, buf in enumerate(buffers):
        n_in += 1
        try:
            v = walk_ev44(buf)
            if v.n_pid and v.n_pid != v.n_tof:
                raise WireError(
                    f"ev44 pixel_id length {v.n_pid} != "
                    f"time_of_flight length {v.n_tof}"
                )
            views.append(v)
        except WireError as err:
            errors.append((i, err))
        else:
            nbytes += len(buf)
    if errors:
        _count_decode_errors("ev44", len(errors))
    offsets = np.empty(len(views) + 1, dtype=np.int64)
    offsets[0] = 0
    for j, v in enumerate(views):
        offsets[j + 1] = offsets[j] + v.n_tof
    total = int(offsets[-1])
    lease = arena
    if lease is None:
        from ..core.device_event_cache import default_decode_pool

        lease = default_decode_pool().lease(total)
    pid = lease.pixel[:total]
    toa = lease.toa[:total]
    for j, v in enumerate(views):
        start = int(offsets[j])
        stop = int(offsets[j + 1])
        v.fill_into(pid[start:stop], toa[start:stop])
    return Ev44Batch(
        pixel_id=pid,
        toa=toa,
        offsets=offsets,
        views=views,
        errors=errors,
        n_messages=n_in,
        nbytes=nbytes,
        lease=lease,
    )


def _count_decode_errors(schema: str, amount: int) -> None:
    """Best-effort bump of ``livedata_decode_errors_total{schema}``.

    Lazy import: the wire codecs must stay importable (and unit-testable)
    without dragging the telemetry package in at module load."""
    try:
        from ..telemetry.instruments import DECODE_ERRORS

        DECODE_ERRORS.inc(amount, schema=schema)
    # Silent by design: the wire codec has no logger (this module stays
    # importable without the telemetry/logging stack) and the quarantine
    # itself is already surfaced through Ev44Batch.errors.
    except Exception:  # graftlint: disable=JGL007
        pass  # pragma: no cover - telemetry is advisory


# ---------------------------------------------------------------------------
# f144 — log data
# ---------------------------------------------------------------------------


@dataclass(frozen=True, slots=True)
class F144Message:
    source_name: str
    value: np.ndarray  # float64 (normalized; wire carries a typed union)
    timestamp_ns: int


#: The f144 ``Value`` union, in declaration order (schemas/f144_logdata.fbs):
#: tag 0 is NONE; 1-10 are scalar member tables, 11-20 array member tables.
#: Every member table holds one ``value`` field at slot 0.
_F144_SCALAR_MEMBERS: list[tuple[np.dtype, str]] = [
    (np.dtype(np.int8), "<b"),
    (np.dtype(np.uint8), "<B"),
    (np.dtype(np.int16), "<h"),
    (np.dtype(np.uint16), "<H"),
    (np.dtype(np.int32), "<i"),
    (np.dtype(np.uint32), "<I"),
    (np.dtype(np.int64), "<q"),
    (np.dtype(np.uint64), "<Q"),
    (np.dtype(np.float32), "<f"),
    (np.dtype(np.float64), "<d"),
]
_F144_TAG_DOUBLE = 10  # scalar Double
_F144_TAG_ARRAY_DOUBLE = 20  # ArrayDouble


def encode_f144(source_name: str, value, timestamp_ns: int) -> bytes:
    """Scalar input -> a ``Double`` union member; array input ->
    ``ArrayDouble``. The union adds the hidden ``value_type`` tag at the
    slot before ``value`` — the layout ECDC's generated reader expects.
    """
    b = flatbuffers.Builder(256)
    val = np.asarray(value, dtype=np.float64)
    scalar = val.ndim == 0
    if scalar:
        b.StartObject(1)
        b.PrependFloat64Slot(0, float(val), 0.0)
        member_off = b.EndObject()
        tag = _F144_TAG_DOUBLE
    else:
        v_off = _np_vector(b, np.atleast_1d(val))
        b.StartObject(1)
        _prepend_vec_slot(b, 0, v_off)
        member_off = b.EndObject()
        tag = _F144_TAG_ARRAY_DOUBLE
    src_off = b.CreateString(source_name)
    b.StartObject(4)
    b.PrependUOffsetTRelativeSlot(0, src_off, 0)
    b.PrependUint8Slot(1, tag, 0)
    b.PrependUOffsetTRelativeSlot(2, member_off, 0)
    b.PrependInt64Slot(3, timestamp_ns, 0)
    b.Finish(b.EndObject(), file_identifier=b"f144")
    return bytes(b.Output())


def decode_f144(buf: bytes) -> F144Message:
    """Accepts every ``Value`` union member, normalized to float64.

    (u)int64 values above 2**53 lose precision in the normalization —
    acceptable for the log-data domain this feeds (motor positions,
    temperatures, chopper phases).
    """
    t = _Tbl.root(buf, "f144")
    tag = t.scalar(1, "<B")
    member = t.table(2)
    if member is None or not 1 <= tag <= 20:
        raise WireError(f"f144 value union missing or bad tag {tag}")
    if tag <= 10:
        _, fmt = _F144_SCALAR_MEMBERS[tag - 1]
        value = np.atleast_1d(
            np.asarray(member.scalar(0, fmt), dtype=np.float64)
        )
    else:
        dtype, _ = _F144_SCALAR_MEMBERS[tag - 11]
        value = member.vector_np(0, dtype).astype(np.float64)
    return F144Message(
        source_name=t.string(0),
        value=value,
        timestamp_ns=t.scalar(3, "<q"),
    )


# ---------------------------------------------------------------------------
# da00 — labeled data arrays
# ---------------------------------------------------------------------------


@dataclass(frozen=True, slots=True)
class Da00Variable:
    name: str
    unit: str
    axes: tuple[str, ...]
    data: np.ndarray  # shaped
    label: str = ""
    source: str = ""


@dataclass(frozen=True, slots=True)
class Da00Message:
    source_name: str
    timestamp_ns: int
    variables: list[Da00Variable] = field(default_factory=list)


def _encode_da00_variable(b: flatbuffers.Builder, var: Da00Variable) -> int:
    # Slot layout per schemas/da00_dataarray.fbs: name=0, unit=1,
    # label=2, source=3, data_type=4, axes=5, shape=6, data=7.
    # NB: np.ascontiguousarray promotes 0-d to 1-d — take the shape from
    # the original array so scalars stay scalars on the wire.
    shape = np.asarray(var.data).shape
    data = np.ascontiguousarray(var.data)
    code = _dtype_code(data, _DA00_CODE)
    data_off = _np_vector_required(b, data.reshape(-1).view(np.uint8))
    shape_off = _np_vector(b, np.asarray(shape, dtype=np.int64))
    axes_vec = None
    if var.axes:
        axes_offs = [b.CreateString(a) for a in var.axes]
        b.StartVector(4, len(axes_offs), 4)
        for off in reversed(axes_offs):
            b.PrependUOffsetTRelative(off)
        axes_vec = b.EndVector()
    source_off = b.CreateString(var.source) if var.source else None
    label_off = b.CreateString(var.label) if var.label else None
    unit_off = b.CreateString(var.unit)
    name_off = b.CreateString(var.name)
    b.StartObject(8)
    b.PrependUOffsetTRelativeSlot(0, name_off, 0)
    b.PrependUOffsetTRelativeSlot(1, unit_off, 0)
    if label_off is not None:
        b.PrependUOffsetTRelativeSlot(2, label_off, 0)
    if source_off is not None:
        b.PrependUOffsetTRelativeSlot(3, source_off, 0)
    b.PrependInt8Slot(4, code, 0)
    _prepend_vec_slot(b, 5, axes_vec)
    _prepend_vec_slot(b, 6, shape_off)
    b.PrependUOffsetTRelativeSlot(7, data_off, 0)
    return b.EndObject()


def _encode_da00_native(
    source_name: str, timestamp_ns: int, variables: list[Da00Variable]
) -> bytes | None:
    """Marshal to the native serializer (native/da00_encode.cpp); None =
    library unavailable (callers fall back to the Python builder). The
    native output is byte-identical to the Python path — asserted by
    tests/kafka/native_da00_test.py — so golden fixtures hold for both.
    """
    try:
        from ..native import available, da00_encode_raw
    except Exception:  # pragma: no cover - import cycle/packaging issue
        return None
    if not available():
        return None
    if any(len(v.axes) > 16 for v in variables):
        # Beyond the native writer's fixed axis capacity: fall back to
        # the Python builder rather than surfacing a capacity error.
        return None
    strings: list[bytes] = []
    offs = [0]

    def intern(s: str) -> int:
        raw = s.encode("utf8")
        strings.append(raw)
        offs.append(offs[-1] + len(raw))
        return len(strings) - 1

    src_idx = intern(source_name)
    n = len(variables)
    name_idx = np.empty(n, np.int32)
    unit_idx = np.empty(n, np.int32)
    label_idx = np.empty(n, np.int32)
    source_idx = np.empty(n, np.int32)
    codes = np.empty(n, np.int8)
    axes_start = np.empty(n, np.int32)
    axes_count = np.empty(n, np.int32)
    dims_start = np.empty(n, np.int32)
    dims_count = np.empty(n, np.int32)
    axes_flat: list[int] = []
    shapes_flat: list[int] = []
    data_parts: list[np.ndarray] = []
    for i, var in enumerate(variables):
        shape = np.asarray(var.data).shape
        data = np.ascontiguousarray(var.data)
        codes[i] = _dtype_code(data, _DA00_CODE)
        name_idx[i] = intern(var.name)
        unit_idx[i] = intern(var.unit)
        label_idx[i] = intern(var.label) if var.label else -1
        source_idx[i] = intern(var.source) if var.source else -1
        axes_start[i] = len(axes_flat)
        axes_count[i] = len(var.axes)
        for axis in var.axes:
            axes_flat.append(intern(axis))
        dims_start[i] = len(shapes_flat)
        dims_count[i] = len(shape)
        shapes_flat.extend(int(s) for s in shape)
        # The payload's bytes where they are: the native builder copies
        # each into the message once.
        data_parts.append(data.reshape(-1).view(np.uint8))
    return da00_encode_raw(
        b"".join(strings),
        np.asarray(offs, np.int64),
        src_idx,
        timestamp_ns,
        name_idx,
        unit_idx,
        label_idx,
        source_idx,
        codes,
        axes_start,
        axes_count,
        np.asarray(axes_flat, np.int32),
        dims_start,
        dims_count,
        np.asarray(shapes_flat, np.int64),
        data_parts,
    )


def encode_da00(
    source_name: str, timestamp_ns: int, variables: list[Da00Variable]
) -> bytes:
    encoded = _encode_da00_native(source_name, timestamp_ns, variables)
    if encoded is not None:
        return encoded
    return _encode_da00_python(source_name, timestamp_ns, variables)


def _encode_da00_python(
    source_name: str, timestamp_ns: int, variables: list[Da00Variable]
) -> bytes:
    b = flatbuffers.Builder(4096)
    var_offs = [_encode_da00_variable(b, v) for v in variables]
    b.StartVector(4, len(var_offs), 4)
    for off in reversed(var_offs):
        b.PrependUOffsetTRelative(off)
    vars_vec = b.EndVector()
    src_off = b.CreateString(source_name)
    b.StartObject(3)
    b.PrependUOffsetTRelativeSlot(0, src_off, 0)
    b.PrependInt64Slot(1, timestamp_ns, 0)
    b.PrependUOffsetTRelativeSlot(2, vars_vec, 0)
    b.Finish(b.EndObject(), file_identifier=b"da00")
    return bytes(b.Output())


def _decode_da00_variable(t: _Tbl) -> Da00Variable:
    code = t.scalar(4, "<b")
    dtype = (
        _DA00_DTYPES[code] if 0 <= code < len(_DA00_DTYPES) else None
    )
    if dtype is None:
        raise WireError(f"Bad or unsupported da00 dtype code {code}")
    shape = tuple(int(s) for s in t.vector_np(6, np.int64))
    raw = t.vector_np(7, np.uint8)
    axes = tuple(t.strings(5))
    if shape:
        if any(s < 0 for s in shape):
            raise WireError(f"Negative dimension in da00 shape {shape}")
        # Python-int product: np.prod wraps in int64, so a hostile shape
        # like [2**32, 2**32] would pass the size check as 0.
        n_items = 1
        for s in shape:
            n_items *= s
    else:
        # Shape slot is omitted for 0-d (scalar) data; an absent shape with
        # axes present means a 1-d vector whose length comes from the data.
        n_items = raw.size // dtype.itemsize
        shape = () if (not axes and n_items == 1) else (n_items,)
    if n_items * dtype.itemsize > raw.size:
        # A hostile shape vector must fail the containment contract's
        # way, not as a numpy reshape ValueError.
        raise WireError(
            f"da00 shape {shape} needs {n_items} items but payload "
            f"holds {raw.size // max(dtype.itemsize, 1)}"
        )
    # Slice to the exact byte count first: view() on a length not divisible
    # by the itemsize would raise numpy's own error instead of WireError.
    data = raw[: n_items * dtype.itemsize].view(dtype).reshape(shape)
    return Da00Variable(
        name=t.string(0),
        unit=t.string(1),
        axes=axes,
        data=data,
        label=t.string(2),
        source=t.string(3),
    )


def decode_da00(buf: bytes) -> Da00Message:
    t = _Tbl.root(buf, "da00")
    return Da00Message(
        source_name=t.string(0),
        timestamp_ns=t.scalar(1, "<q"),
        variables=[_decode_da00_variable(v) for v in t.tables(2)],
    )


# ---------------------------------------------------------------------------
# ad00 — area detector images
# ---------------------------------------------------------------------------


@dataclass(frozen=True, slots=True)
class Ad00Image:
    source_name: str
    timestamp_ns: int
    data: np.ndarray  # 2-D


def encode_ad00(
    source_name: str,
    timestamp_ns: int,
    data: np.ndarray,
    *,
    frame_id: int = 0,
) -> bytes:
    # Slot layout per schemas/ad00_area_detector_array.fbs: source_name=0,
    # id=1, timestamp=2, data_type=3, dimensions=4 (int64), data=5.
    data = np.ascontiguousarray(data)
    b = flatbuffers.Builder(4096)
    code = _dtype_code(data, _AD00_CODE)
    data_off = _np_vector_required(b, data.reshape(-1).view(np.uint8))
    shape_off = _np_vector_required(
        b, np.asarray(data.shape, dtype=np.int64)
    )
    src_off = b.CreateString(source_name)
    b.StartObject(6)
    b.PrependUOffsetTRelativeSlot(0, src_off, 0)
    b.PrependInt64Slot(1, frame_id, 0)
    b.PrependInt64Slot(2, timestamp_ns, 0)
    b.PrependInt8Slot(3, code, 0)
    b.PrependUOffsetTRelativeSlot(4, shape_off, 0)
    b.PrependUOffsetTRelativeSlot(5, data_off, 0)
    b.Finish(b.EndObject(), file_identifier=b"ad00")
    return bytes(b.Output())


def decode_ad00(buf: bytes) -> Ad00Image:
    t = _Tbl.root(buf, "ad00")
    code = t.scalar(3, "<b")
    if not 0 <= code < len(_AD00_DTYPES):
        raise WireError(f"Bad dtype code {code}")
    dtype = _AD00_DTYPES[code]
    shape = tuple(int(s) for s in t.vector_np(4, np.int64))
    if any(s < 0 for s in shape):
        raise WireError(f"Negative dimension in ad00 shape {shape}")
    raw = t.vector_np(5, np.uint8)
    # Python-int product (np.prod wraps in int64 for hostile shapes).
    n_items = 1 if shape else 0
    for s in shape:
        n_items *= s
    if raw.size < n_items * dtype.itemsize:
        raise WireError("ad00 data shorter than shape implies")
    # Slice to the exact byte count BEFORE view(): a data vector whose
    # length is not a multiple of the itemsize must fail the containment
    # contract's way (WireError path above), not as numpy's ValueError.
    return Ad00Image(
        source_name=t.string(0),
        timestamp_ns=t.scalar(2, "<q"),
        data=raw[: n_items * dtype.itemsize].view(dtype).reshape(shape),
    )


# ---------------------------------------------------------------------------
# x5f2 — status heartbeats
# ---------------------------------------------------------------------------


@dataclass(frozen=True, slots=True)
class X5f2Status:
    software_name: str
    software_version: str
    service_id: str
    host_name: str
    process_id: int
    update_interval_ms: int
    status_json: str


def encode_x5f2(status: X5f2Status) -> bytes:
    b = flatbuffers.Builder(512)
    js_off = b.CreateString(status.status_json)
    host_off = b.CreateString(status.host_name)
    sid_off = b.CreateString(status.service_id)
    ver_off = b.CreateString(status.software_version)
    name_off = b.CreateString(status.software_name)
    b.StartObject(7)
    b.PrependUOffsetTRelativeSlot(0, name_off, 0)
    b.PrependUOffsetTRelativeSlot(1, ver_off, 0)
    b.PrependUOffsetTRelativeSlot(2, sid_off, 0)
    b.PrependUOffsetTRelativeSlot(3, host_off, 0)
    b.PrependUint32Slot(4, status.process_id, 0)
    b.PrependUint32Slot(5, status.update_interval_ms, 0)
    b.PrependUOffsetTRelativeSlot(6, js_off, 0)
    b.Finish(b.EndObject(), file_identifier=b"x5f2")
    return bytes(b.Output())


def decode_x5f2(buf: bytes) -> X5f2Status:
    t = _Tbl.root(buf, "x5f2")
    return X5f2Status(
        software_name=t.string(0),
        software_version=t.string(1),
        service_id=t.string(2),
        host_name=t.string(3),
        process_id=t.scalar(4, "<I"),
        update_interval_ms=t.scalar(5, "<I"),
        status_json=t.string(6),
    )


# ---------------------------------------------------------------------------
# pl72 / 6s4t — run start/stop
# ---------------------------------------------------------------------------


@dataclass(frozen=True, slots=True)
class RunStartMessage:
    run_name: str
    instrument_name: str
    start_time_ns: int
    stop_time_ns: int  # 0 = open-ended
    job_id: str = ""
    nexus_structure: str = ""
    service_id: str = ""


@dataclass(frozen=True, slots=True)
class RunStopMessage:
    run_name: str
    stop_time_ns: int
    job_id: str = ""
    service_id: str = ""
    command_id: str = ""


def encode_pl72(msg: RunStartMessage) -> bytes:
    # Slot layout per schemas/pl72_run_start.fbs: start_time=0,
    # stop_time=1, run_name=2, instrument_name=3, nexus_structure=4,
    # job_id=5, broker=6, service_id=7, filename=8, metadata=9,
    # detector_spectrum_map=10, control_topic=11. Slots this framework
    # does not populate are omitted (flatbuffers default semantics).
    b = flatbuffers.Builder(256)
    sid_off = b.CreateString(msg.service_id) if msg.service_id else None
    # nexus_structure and job_id are (required) in the upstream ECDC
    # schema: always write the slot (empty string when unset) so a
    # consumer running the flatbuffers verifier accepts our buffers.
    job_off = b.CreateString(msg.job_id)
    nx_off = b.CreateString(msg.nexus_structure)
    inst_off = b.CreateString(msg.instrument_name)
    run_off = b.CreateString(msg.run_name)
    b.StartObject(12)
    b.PrependUint64Slot(0, msg.start_time_ns, 0)
    b.PrependUint64Slot(1, msg.stop_time_ns, 0)
    b.PrependUOffsetTRelativeSlot(2, run_off, 0)
    b.PrependUOffsetTRelativeSlot(3, inst_off, 0)
    b.PrependUOffsetTRelativeSlot(4, nx_off, 0)
    b.PrependUOffsetTRelativeSlot(5, job_off, 0)
    if sid_off is not None:
        b.PrependUOffsetTRelativeSlot(7, sid_off, 0)
    b.Finish(b.EndObject(), file_identifier=b"pl72")
    return bytes(b.Output())


def decode_pl72(buf: bytes) -> RunStartMessage:
    t = _Tbl.root(buf, "pl72")
    return RunStartMessage(
        run_name=t.string(2),
        instrument_name=t.string(3),
        start_time_ns=t.scalar(0, "<Q"),
        stop_time_ns=t.scalar(1, "<Q"),
        job_id=t.string(5),
        nexus_structure=t.string(4),
        service_id=t.string(7),
    )


def encode_6s4t(msg: RunStopMessage) -> bytes:
    # Slot layout per schemas/6s4t_run_stop.fbs: stop_time=0, run_name=1,
    # job_id=2, service_id=3, command_id=4.
    b = flatbuffers.Builder(128)
    cmd_off = b.CreateString(msg.command_id) if msg.command_id else None
    sid_off = b.CreateString(msg.service_id) if msg.service_id else None
    # job_id is (required) upstream: always write the slot (see pl72).
    job_off = b.CreateString(msg.job_id)
    run_off = b.CreateString(msg.run_name)
    b.StartObject(5)
    b.PrependUint64Slot(0, msg.stop_time_ns, 0)
    b.PrependUOffsetTRelativeSlot(1, run_off, 0)
    b.PrependUOffsetTRelativeSlot(2, job_off, 0)
    if sid_off is not None:
        b.PrependUOffsetTRelativeSlot(3, sid_off, 0)
    if cmd_off is not None:
        b.PrependUOffsetTRelativeSlot(4, cmd_off, 0)
    b.Finish(b.EndObject(), file_identifier=b"6s4t")
    return bytes(b.Output())


def decode_6s4t(buf: bytes) -> RunStopMessage:
    t = _Tbl.root(buf, "6s4t")
    return RunStopMessage(
        run_name=t.string(1),
        stop_time_ns=t.scalar(0, "<Q"),
        job_id=t.string(2),
        service_id=t.string(3),
        command_id=t.string(4),
    )

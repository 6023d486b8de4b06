"""ctypes binding for the native ingest shim (ingest.cpp).

The library is compiled on demand with g++ into this package directory
from the two tracked sources and cached under a name that carries a
digest of their bytes, so a binary is only ever loaded for the exact
sources it was built from (a checkout, an edit or a stale untracked
``.so`` can never be trusted by accident). If no compiler is available
the binding reports unavailable and callers fall back to the pure-Python
path (kafka/wire.py decode + ops/event_batch.StagingBuffer) — identical
semantics, same tests; :func:`unavailable_reason` says why.

Reference parity: this is our equivalent of the native machinery the
reference's ingest path rests on (generated FlatBuffers decode in
ess-streaming-data-types + scipp's C++ event buffers; see SURVEY §2.9 and
reference kafka/message_adapter.py:360 for the partial-decode fast path).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from pathlib import Path

import numpy as np

__all__ = [
    "NativeStagingBuffer",
    "available",
    "ev44_info",
    "library_path",
    "load_library",
    "unavailable_reason",
]

logger = logging.getLogger(__name__)

_HERE = Path(__file__).resolve().parent
_SOURCES = [_HERE / "ingest.cpp", _HERE / "da00_encode.cpp"]

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_error: str | None = None

_ERRORS = {
    -1: "short or corrupt flatbuffer",
    -2: "wrong schema (expected ev44)",
    -3: "corrupt table",
    -4: "corrupt vector",
    -5: "time_of_flight/pixel_id length mismatch",
    -6: "staging buffer in use (release() the last batch first)",
    -7: "native allocation failure",
}


def library_path() -> Path:
    """The cached binary for the sources as they are on disk now:
    ``_ingest-<sha256 of both sources, 16 hex>.so`` beside them."""
    digest = hashlib.sha256()
    for source in _SOURCES:
        digest.update(source.read_bytes())
    return _HERE / f"_ingest-{digest.hexdigest()[:16]}.so"


def _compile(target: Path) -> str | None:
    """Build ``target`` from the tracked sources; None on success, else
    the reason. Links to a private temp name and renames into place, so
    a concurrent process never loads a half-written binary."""
    tmp = target.with_name(f".{target.name}.{os.getpid()}.tmp")
    cmd = [
        "g++",
        "-O3",
        "-shared",
        "-fPIC",
        "-pthread",
        "-std=c++17",
        *[str(s) for s in _SOURCES],
        "-o",
        str(tmp),
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired) as err:
        return f"g++ did not run: {err!r}"
    try:
        if proc.returncode != 0 or not tmp.exists():
            return (
                f"g++ exited {proc.returncode}: "
                f"{proc.stderr.decode(errors='replace')[-2000:]}"
            )
        os.replace(tmp, target)
    finally:
        tmp.unlink(missing_ok=True)
    # Binaries of other source digests are dead weight, never loaded.
    for stale in _HERE.glob("_ingest*.so"):
        if stale != target:
            stale.unlink(missing_ok=True)
    return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i64, vp = ctypes.c_int64, ctypes.c_void_p
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.ld_staging_new.restype = vp
    f32 = ctypes.c_float
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.ld_flatten.restype = None
    lib.ld_flatten.argtypes = [
        i32p, f32p, i64, i32p, i64,
        ctypes.c_int32, ctypes.c_int32, f32, f32, f32, ctypes.c_int32, i32p,
    ]
    lib.ld_flatten_nonuniform.restype = None
    lib.ld_flatten_nonuniform.argtypes = [
        i32p, f32p, i64, i32p, i64,
        ctypes.c_int32, ctypes.c_int32, f32p, ctypes.c_int32, i32p,
    ]
    lib.ld_partition.restype = i64
    lib.ld_partition.argtypes = [
        i32p, i32p, i64, i64, i64,
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i64,
    ]
    lib.ld_flatten_partition.restype = i64
    lib.ld_flatten_partition.argtypes = [
        i32p, f32p, i64, i32p, i64,
        ctypes.c_int32, ctypes.c_int32, f32, f32, f32,
        ctypes.c_int32, ctypes.c_int32, i32p, i32p, i64,
    ]
    u16p = ctypes.POINTER(ctypes.c_uint16)
    lib.ld_partition_u16.restype = i64
    lib.ld_partition_u16.argtypes = [
        i32p, i32p, i64, i64, i64,
        ctypes.c_int32, i64, ctypes.c_int32, u16p, i32p, i64,
    ]
    lib.ld_flatten_partition_u16.restype = i64
    lib.ld_flatten_partition_u16.argtypes = [
        i32p, f32p, i64, i32p, i64,
        ctypes.c_int32, ctypes.c_int32, f32, f32, f32,
        ctypes.c_int32, ctypes.c_int32, u16p, i32p, i64,
    ]
    lib.ld_staging_new.argtypes = [i64]
    lib.ld_staging_free.restype = None
    lib.ld_staging_free.argtypes = [vp]
    lib.ld_staging_len.restype = i64
    lib.ld_staging_len.argtypes = [vp]
    lib.ld_staging_add_ev44.restype = i64
    lib.ld_staging_add_ev44.argtypes = [vp, u8p, i64, ctypes.c_int]
    lib.ld_staging_add_raw.restype = i64
    lib.ld_staging_add_raw.argtypes = [
        vp,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_float),
        i64,
    ]
    lib.ld_staging_take.restype = i64
    lib.ld_staging_take.argtypes = [
        vp,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_int32)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(i64),
        ctypes.POINTER(i64),
    ]
    lib.ld_staging_release.restype = None
    lib.ld_staging_release.argtypes = [vp]
    lib.ld_staging_clear.restype = None
    lib.ld_staging_clear.argtypes = [vp]
    lib.ld_ev44_info.restype = i64
    lib.ld_ev44_info.argtypes = [
        u8p,
        i64,
        ctypes.POINTER(i64),
        ctypes.POINTER(i64),
        ctypes.POINTER(i64),
        ctypes.POINTER(i64),
    ]
    i64p = ctypes.POINTER(i64)
    i8p = ctypes.POINTER(ctypes.c_int8)
    i32 = ctypes.c_int32
    lib.ld_da00_encode.restype = i64
    lib.ld_da00_encode.argtypes = [
        u8p, i64p, i32,            # strings blob, offsets, n_strs
        i32, i64, i32,             # source idx, timestamp, n_vars
        i32p, i32p, i32p, i32p,    # name/unit/label/source idx
        i8p,                       # dtype codes
        i32p, i32p, i32p,          # axes start/count/flat idx
        i32p, i32p, i64p,          # dims start/count, shapes flat
        i64p, u8p,                 # data offsets, data blob
        u8p, i64,                  # out, cap
    ]
    return lib


def load_library() -> ctypes.CDLL | None:
    """Load (compiling if needed) the native library; None if unavailable."""
    global _lib, _load_error
    with _lock:
        if _lib is not None:
            return _lib
        if _load_error is not None:
            return None
        try:
            target = library_path()
        except OSError as err:
            _load_error = f"native sources unreadable: {err!r}"
        else:
            if not target.exists():
                # One-time initialization: every caller needs the
                # library and has to wait for this build anyway.
                _load_error = _compile(target)  # graftlint: disable=JGL023
            if _load_error is None:
                try:
                    _lib = _bind(ctypes.CDLL(str(target)))
                except (OSError, AttributeError) as err:
                    _load_error = f"{target.name} did not load: {err!r}"
        if _load_error is not None:
            logger.warning(
                "native ingest shim unavailable, using the numpy paths: %s",
                _load_error,
            )
        return _lib


def available() -> bool:
    return load_library() is not None


def unavailable_reason() -> str | None:
    """Why :func:`available` is False (compiler output included), or
    None when the library loaded."""
    load_library()
    return _load_error


def da00_encode_raw(
    strings_blob: bytes,
    str_offs: np.ndarray,
    source_name_idx: int,
    timestamp_ns: int,
    name_idx: np.ndarray,
    unit_idx: np.ndarray,
    label_idx: np.ndarray,
    source_idx: np.ndarray,
    dtype_codes: np.ndarray,
    axes_start: np.ndarray,
    axes_count: np.ndarray,
    axes_idx_flat: np.ndarray,
    dims_start: np.ndarray,
    dims_count: np.ndarray,
    shapes_flat: np.ndarray,
    data_offs: np.ndarray,
    data_blob: bytes,
) -> bytes | None:
    """Raw interface to the native da00 serializer (da00_encode.cpp);
    marshalling from Da00Variable lives in kafka/wire.py which owns the
    dtype table. None = library unavailable; raises on invalid input."""
    lib = load_library()
    if lib is None:
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i8p = ctypes.POINTER(ctypes.c_int8)

    def p(arr, ptr_type):
        return arr.ctypes.data_as(ptr_type)

    n_vars = int(name_idx.size)
    cap = len(data_blob) + len(strings_blob) + 4096 + 160 * max(n_vars, 1)
    u8p_t = ctypes.POINTER(ctypes.c_uint8)
    for _ in range(3):
        out = np.empty(cap, np.uint8)  # no zero fill (create_string_buffer's)
        rc = lib.ld_da00_encode(
            _as_u8p(strings_blob),
            p(str_offs, i64p),
            int(str_offs.size - 1),
            int(source_name_idx),
            int(timestamp_ns),
            n_vars,
            p(name_idx, i32p),
            p(unit_idx, i32p),
            p(label_idx, i32p),
            p(source_idx, i32p),
            p(dtype_codes, i8p),
            p(axes_start, i32p),
            p(axes_count, i32p),
            p(axes_idx_flat, i32p),
            p(dims_start, i32p),
            p(dims_count, i32p),
            p(shapes_flat, i64p),
            p(data_offs, i64p),
            _as_u8p(data_blob),
            out.ctypes.data_as(u8p_t),
            cap,
        )
        if rc >= 0:
            return out[: int(rc)].tobytes()
        if rc == -1:
            cap *= 4
            continue
        raise ValueError(f"native da00 encode failed rc={rc}")
    raise ValueError("native da00 encode: output did not fit")


def _as_u8p(buf: bytes):
    return ctypes.cast(ctypes.c_char_p(buf), ctypes.POINTER(ctypes.c_uint8))


def flatten_partition(
    pixel_id: np.ndarray,
    toa: np.ndarray,
    *,
    lut: np.ndarray | None,
    n_screen: int,
    n_toa: int,
    lo: float,
    hi: float,
    inv_width: float,
    ppb_shift: int,
    chunk: int,
    cap_chunks: int,
    compact: bool = False,
) -> tuple[np.ndarray, np.ndarray, int] | None:
    """Fused native flatten + block partition (ld_flatten_partition) for
    the pallas2d ingest path — uniform TOA edges, pixel-aligned blocks
    (``bpb = 2**ppb_shift * n_toa``). Returns ``(events, chunk_map,
    n_chunks_used)`` or None when the native library is unavailable.

    ``compact=True`` emits uint16 block-LOCAL offsets (0xFFFF padding) —
    half the host->device wire bytes; requires ``bpb <= 0xFFFF``."""
    lib = load_library()
    if lib is None:
        return None
    from ..ops.event_batch import sanitize_pixel_id

    if compact and (1 << ppb_shift) * n_toa > 0xFFFF:
        raise ValueError("compact partition requires bpb <= 0xFFFF")
    pixel_id = np.ascontiguousarray(sanitize_pixel_id(pixel_id), np.int32)
    toa = np.ascontiguousarray(toa, dtype=np.float32)
    out_dtype = np.uint16 if compact else np.int32
    events = np.empty(cap_chunks * chunk, out_dtype)
    chunk_map = np.empty(cap_chunks, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    if lut is not None:
        lut = np.ascontiguousarray(lut, dtype=np.int32)
        lut_ptr = lut.ctypes.data_as(i32p)
        n_pix = lut.shape[0]
    else:
        lut_ptr = None
        n_pix = 0
    fn = lib.ld_flatten_partition_u16 if compact else lib.ld_flatten_partition
    out_ptr = events.ctypes.data_as(
        ctypes.POINTER(ctypes.c_uint16) if compact else i32p
    )
    used = fn(
        pixel_id.ctypes.data_as(i32p),
        toa.ctypes.data_as(f32p),
        int(pixel_id.shape[0]),
        lut_ptr,
        n_pix,
        int(n_screen),
        int(n_toa),
        float(lo),
        float(hi),
        float(inv_width),
        int(ppb_shift),
        int(chunk),
        out_ptr,
        chunk_map.ctypes.data_as(i32p),
        int(cap_chunks),
    )
    if used < 0:
        raise ValueError("ld_flatten_partition: cap_chunks too small")
    return events, chunk_map, int(used)


def partition_events(
    flat: np.ndarray,
    n_bins_incl_dump: int,
    *,
    shift: int = 0,
    chunk: int,
    cap_chunks: int,
    blk: np.ndarray | None = None,
    n_blocks: int = 0,
    compact_bpb: int = 0,
) -> tuple[np.ndarray, np.ndarray, int] | None:
    """Native block partition for the pallas2d kernel (ld_partition).

    Power-of-two bins-per-block pass ``shift``; non-power-of-two pass a
    precomputed per-event ``blk`` array (with ``n_blocks``) and
    already-routed ``flat``. Returns ``(events, chunk_map,
    n_chunks_used)`` with the full ``cap_chunks`` capacity filled
    (callers slice a rounded-up prefix), or None when the native library
    is unavailable. Raises ValueError if ``cap_chunks`` is too small (a
    caller bug: the bound is static).

    ``compact_bpb`` (a bins-per-block value <= 0xFFFF) switches to the
    uint16 block-LOCAL output (0xFFFF padding) — half the wire bytes.
    """
    lib = load_library()
    if lib is None:
        return None
    compact = bool(compact_bpb)
    if compact and compact_bpb > 0xFFFF:
        raise ValueError("compact partition requires bpb <= 0xFFFF")
    flat = np.ascontiguousarray(flat, dtype=np.int32)
    events = np.empty(cap_chunks * chunk, np.uint16 if compact else np.int32)
    chunk_map = np.empty(cap_chunks, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    if blk is not None:
        blk = np.ascontiguousarray(blk, dtype=np.int32)
        blk_ptr = blk.ctypes.data_as(i32p)
    else:
        blk_ptr = None
    if compact:
        used = lib.ld_partition_u16(
            flat.ctypes.data_as(i32p),
            blk_ptr,
            int(flat.shape[0]),
            int(n_bins_incl_dump),
            int(n_blocks),
            int(shift),
            int(compact_bpb),
            int(chunk),
            events.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
            chunk_map.ctypes.data_as(i32p),
            int(cap_chunks),
        )
    else:
        used = lib.ld_partition(
            flat.ctypes.data_as(i32p),
            blk_ptr,
            int(flat.shape[0]),
            int(n_bins_incl_dump),
            int(n_blocks),
            int(shift),
            int(chunk),
            events.ctypes.data_as(i32p),
            chunk_map.ctypes.data_as(i32p),
            int(cap_chunks),
        )
    if used < 0:
        raise ValueError("ld_partition: cap_chunks too small")
    return events, chunk_map, int(used)


def ev44_info(buf: bytes) -> tuple[int, int, int, int]:
    """(message_id, n_events, ref_time_first, ref_time_last) without a full
    decode — the native analog of the reference's partial-decode fast path."""
    lib = load_library()
    if lib is None:
        raise RuntimeError("native ingest library unavailable")
    mid = ctypes.c_int64()
    n = ctypes.c_int64()
    first = ctypes.c_int64()
    last = ctypes.c_int64()
    rc = lib.ld_ev44_info(
        _as_u8p(buf),
        len(buf),
        ctypes.byref(mid),
        ctypes.byref(n),
        ctypes.byref(first),
        ctypes.byref(last),
    )
    if rc != 0:
        raise ValueError(_ERRORS.get(int(rc), f"native error {rc}"))
    return mid.value, n.value, first.value, last.value


class NativeStagingBuffer:
    """Drop-in native replacement for ops.event_batch.StagingBuffer, with an
    extra ``add_ev44`` fast path that decodes and appends in one C call.

    The arrays handed out by ``take`` are zero-copy views into C-owned
    memory; per the staging contract (same as the reference's
    to_nxevent_data.py:166-171) the caller must finish with them before
    ``release``/``clear``/``add`` is called again. The returned EventBatch
    holds a reference to this buffer (``owner``) so the C memory stays
    alive as long as the batch does.
    """

    def __init__(self, min_bucket: int = 1 << 12) -> None:
        lib = load_library()
        if lib is None:
            raise RuntimeError("native ingest library unavailable")
        self._lib = lib
        self._min_bucket = min_bucket
        self._h = lib.ld_staging_new(min_bucket)
        if not self._h:
            raise MemoryError("native staging allocation failed")

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.ld_staging_free(h)
            self._h = None

    def __len__(self) -> int:
        return int(self._lib.ld_staging_len(self._h))

    def _check(self, rc: int) -> int:
        if rc < 0:
            msg = _ERRORS.get(rc, f"native error {rc}")
            if rc == -6:
                raise RuntimeError(msg)
            if rc == -7:
                raise MemoryError(msg)
            raise ValueError(msg)
        return rc

    def add_ev44(self, buf: bytes, monitor: bool = False) -> int:
        """Decode an ev44 message and append its events. Returns the number
        of events appended; raises ValueError on a malformed buffer."""
        rc = self._lib.ld_staging_add_ev44(
            self._h, _as_u8p(buf), len(buf), 1 if monitor else 0
        )
        return self._check(int(rc))

    def add(self, pixel_id: np.ndarray, toa: np.ndarray) -> None:
        from ..ops.event_batch import sanitize_pixel_id

        pixel_id = np.ascontiguousarray(sanitize_pixel_id(pixel_id), dtype=np.int32)
        toa = np.ascontiguousarray(toa, dtype=np.float32)
        n = int(pixel_id.shape[0])
        if n == 0:
            return
        rc = self._lib.ld_staging_add_raw(
            self._h,
            pixel_id.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            toa.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            n,
        )
        self._check(int(rc))

    def take(self):
        """Pad to the bucket boundary, return an EventBatch of zero-copy
        views into native memory."""
        from ..ops.event_batch import EventBatch

        pixel_p = ctypes.POINTER(ctypes.c_int32)()
        toa_p = ctypes.POINTER(ctypes.c_float)()
        padded = ctypes.c_int64()
        n_valid = ctypes.c_int64()
        rc = self._lib.ld_staging_take(
            self._h,
            ctypes.byref(pixel_p),
            ctypes.byref(toa_p),
            ctypes.byref(padded),
            ctypes.byref(n_valid),
        )
        self._check(int(rc))
        b = int(padded.value)
        pixel = np.ctypeslib.as_array(pixel_p, shape=(b,))
        toa = np.ctypeslib.as_array(toa_p, shape=(b,))
        return EventBatch(
            pixel_id=pixel, toa=toa, n_valid=int(n_valid.value), owner=self
        )

    def release(self) -> None:
        self._lib.ld_staging_release(self._h)

    def clear(self) -> None:
        self._lib.ld_staging_clear(self._h)


def flatten_events(
    pixel_id,
    toa,
    *,
    lut=None,
    n_screen: int,
    n_toa: int,
    lo: float,
    hi: float,
    inv_width: float,
    dump: int,
    edges=None,
    out=None,
):
    """Native event -> flat-bin projection (see ingest.cpp ld_flatten).

    Returns the int32 flat-index array, or None when the native library is
    unavailable (caller falls back to the numpy path). Inputs must be
    contiguous int32/float32 arrays; ``lut`` a contiguous 1-D int32 map or
    None. Passing ``edges`` (float32, n_toa + 1 entries) selects the
    non-uniform binning kernel (binary search, same float32 edges the
    device path bins with).

    ``out`` optionally receives the result (contiguous int32, length of
    ``pixel_id``): the pipelined ingest's chunked flatten hands worker
    slices of one preallocated array so parallel chunks assemble without
    a concatenation copy. The ctypes call releases the GIL, so chunked
    callers overlap for real.
    """
    lib = load_library()
    if lib is None:
        return None
    import numpy as np

    from ..ops.event_batch import sanitize_pixel_id

    pixel_id = np.ascontiguousarray(sanitize_pixel_id(pixel_id), dtype=np.int32)
    toa = np.ascontiguousarray(toa, dtype=np.float32)
    n = pixel_id.shape[0]
    if out is None:
        out = np.empty(n, dtype=np.int32)
    elif (
        out.dtype != np.int32
        or out.shape != (n,)
        or not out.flags["C_CONTIGUOUS"]
    ):
        raise ValueError("out must be a contiguous int32 array of length n")
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    if lut is not None:
        lut = np.ascontiguousarray(lut, dtype=np.int32)
        lut_ptr = lut.ctypes.data_as(i32p)
        n_pix = lut.shape[0]
    else:
        lut_ptr = None
        n_pix = 0
    if edges is not None:
        edges = np.ascontiguousarray(edges, dtype=np.float32)
        if edges.shape[0] != n_toa + 1:
            raise ValueError("edges must have n_toa + 1 entries")
        lib.ld_flatten_nonuniform(
            pixel_id.ctypes.data_as(i32p),
            toa.ctypes.data_as(f32p),
            n,
            lut_ptr,
            n_pix,
            n_screen,
            n_toa,
            edges.ctypes.data_as(f32p),
            dump,
            out.ctypes.data_as(i32p),
        )
        return out
    lib.ld_flatten(
        pixel_id.ctypes.data_as(i32p),
        toa.ctypes.data_as(f32p),
        n,
        lut_ptr,
        n_pix,
        n_screen,
        n_toa,
        lo,
        hi,
        inv_width,
        dump,
        out.ctypes.data_as(i32p),
    )
    return out


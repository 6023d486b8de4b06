"""Host-side staging of ragged event streams into fixed-shape device batches.

XLA compiles one program per input shape, so ragged per-pulse event counts
(reference handles them as scipp binned data, to_nxevent_data.py:131) become
power-of-two *bucketed* batches here: a batch of N events is padded to the
next bucket size, giving a handful of compiled kernels instead of one per N,
and the padded tail is masked out inside the kernel via out-of-range indices
(scatter mode='drop'). This mirrors the reference's zero-copy growable
buffers (_ScippBackedBuffer, to_nxevent_data.py:76-114): the staging buffer
doubles capacity and is reused across batches, so steady-state costs no
allocation on the host side either.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from ..telemetry.instruments import STAGED_EVENTS, STAGING_COPIES
from ..telemetry.trace import TRACER
from .staging_pool import POOL

__all__ = [
    "EventBatch",
    "StagingBuffer",
    "bucket_size",
    "device_token",
    "leaf_device_set",
    "make_staging_buffer",
    "sanitize_pixel_id",
    "ship",
    "stage_raw",
]

MIN_BUCKET = 1 << 12  # 4096: below this, padding waste is irrelevant
MAX_BUCKET = 1 << 26  # 64M events per device batch


def sanitize_pixel_id(pixel_id: np.ndarray) -> np.ndarray:
    """Map ids unrepresentable in int32 to -1 before any int32 cast.

    Every downstream consumer — the device kernel (JAX canonicalizes to
    int32 with x64 disabled), the native C shims, and the numpy staging
    arrays — works in int32 (ev44 pixel ids are already int32 on the
    wire; wide dtypes come from non-ev44 callers passing int64/uint64
    host arrays). A value outside int32 range would silently wrap
    under those casts and count an invalid event into a real bin;
    -1 is the universal out-of-range/dump marker instead. No copy for
    inputs already safely castable.
    """
    pixel_id = np.asarray(pixel_id)
    if np.can_cast(pixel_id.dtype, np.int32):
        return pixel_id
    info = np.iinfo(np.int32)
    return np.where(
        (pixel_id >= info.min) & (pixel_id <= info.max), pixel_id, -1
    ).astype(np.int32)


def bucket_size(n: int, min_bucket: int = MIN_BUCKET) -> int:
    """Smallest power-of-two >= n (clamped to [min_bucket, MAX_BUCKET])."""
    if n > MAX_BUCKET:
        raise ValueError(f"Event batch of {n} exceeds MAX_BUCKET={MAX_BUCKET}")
    b = min_bucket
    while b < n:
        b <<= 1
    return b


@dataclass(slots=True)
class EventBatch:
    """A padded, fixed-shape batch of detector/monitor events.

    ``pixel_id`` and ``toa`` have length ``bucket_size(n_valid)``; entries at
    index >= n_valid are padding with pixel_id == -1 (which every kernel
    treats as out-of-range and drops).
    """

    pixel_id: np.ndarray  # int32 [B]
    toa: np.ndarray  # float32 [B] time-of-arrival within pulse (ns)
    n_valid: int
    # Keeps the memory owner alive when pixel_id/toa are zero-copy views
    # into a native staging buffer (numpy cannot track C-owned memory),
    # or the arena lease when they view a decode arena (ADR 0125).
    owner: object = None
    #: True when ``owner`` is an exclusive lease (decode arena): the
    #: arrays outlive the producer's release() on their own, so
    #: ``detach`` is a no-op instead of an 8 B/event memcpy.
    owned: bool = False
    #: True when pixel ids were landed straight off the wire without the
    #: host sanitize pass (batch decode): ``stage_raw`` fuses the device
    #: decode prologue (ops/decode_prologue.py) into staging so the
    #: validation runs on device, once per (stream, tag).
    prologue: bool = False

    @property
    def padded_size(self) -> int:
        return int(self.pixel_id.shape[0])

    def detach(self) -> EventBatch:
        """An owned copy, safe to hold past the staging buffer's
        ``release()``. The pipelined ingest hands windows across stage
        threads while the service thread reuses the staging buffer for
        the next window (ADR 0111); batches crossing that boundary must
        own their memory. ~8 B/event memcpy — small against the flatten
        it decouples. Arena-leased batches (``owned``) already own their
        memory through the lease: the pool cannot re-issue the arena
        while this batch references it, so they pass through unchanged.
        """
        if self.owned:
            return self
        return EventBatch(
            pixel_id=self.pixel_id.copy(),
            toa=self.toa.copy(),
            n_valid=self.n_valid,
        )

    @classmethod
    def from_arrays(
        cls,
        pixel_id: np.ndarray,
        toa: np.ndarray,
        min_bucket: int = MIN_BUCKET,
    ) -> EventBatch:
        pixel_id = sanitize_pixel_id(pixel_id)
        n = int(pixel_id.shape[0])
        b = bucket_size(n, min_bucket)
        pid = np.full(b, -1, dtype=np.int32)
        t = np.zeros(b, dtype=np.float32)
        pid[:n] = pixel_id
        t[:n] = toa
        return cls(pixel_id=pid, toa=t, n_valid=n)


class StagingBuffer:
    """Accumulates ev44 chunks on the host, emits one padded batch.

    ``add`` appends; ``take`` pads to the bucket boundary and returns an
    EventBatch backed by the internal arrays (zero-copy slice), then resets.
    Capacity doubles on demand and is retained across cycles. The caller
    must consume the batch before the next ``add`` cycle begins — same
    release-buffers contract as the reference (to_nxevent_data.py:166-171),
    enforced with an in-use guard.
    """

    def __init__(self, min_bucket: int = MIN_BUCKET) -> None:
        self._min_bucket = min_bucket
        self._capacity = min_bucket
        self._pixel = np.full(self._capacity, -1, dtype=np.int32)
        self._toa = np.zeros(self._capacity, dtype=np.float32)
        self._n = 0
        self._in_use = False

    def __len__(self) -> int:
        return self._n

    def _grow(self, needed: int) -> None:
        new_cap = self._capacity
        while new_cap < needed:
            new_cap <<= 1
        pixel = np.full(new_cap, -1, dtype=np.int32)
        toa = np.zeros(new_cap, dtype=np.float32)
        pixel[: self._n] = self._pixel[: self._n]
        toa[: self._n] = self._toa[: self._n]
        self._pixel, self._toa = pixel, toa
        self._capacity = new_cap

    def add(self, pixel_id: np.ndarray, toa: np.ndarray) -> None:
        if self._in_use:
            raise RuntimeError(
                "StagingBuffer.add called before release() of the last batch"
            )
        pixel_id = sanitize_pixel_id(pixel_id)
        k = int(pixel_id.shape[0])
        if k == 0:
            return
        if self._n + k > self._capacity:
            self._grow(self._n + k)
        self._pixel[self._n : self._n + k] = pixel_id
        self._toa[self._n : self._n + k] = toa
        self._n += k

    def take(self) -> EventBatch:
        """Pad to bucket boundary and hand out a zero-copy view batch."""
        b = bucket_size(self._n, self._min_bucket)
        if b > self._capacity:
            self._grow(b)
        # Clear the padded tail so stale events never leak into the kernel.
        self._pixel[self._n : b] = -1
        self._toa[self._n : b] = 0.0
        batch = EventBatch(
            pixel_id=self._pixel[:b], toa=self._toa[:b], n_valid=self._n
        )
        self._in_use = True
        return batch

    def release(self) -> None:
        """Mark the last taken batch consumed; buffer may be reused."""
        self._in_use = False
        self._n = 0

    def clear(self) -> None:
        self._n = 0
        self._in_use = False


_CPU_BACKEND: bool | None = None


def _cpu_backend() -> bool:
    global _CPU_BACKEND
    if _CPU_BACKEND is None:
        import jax

        _CPU_BACKEND = jax.default_backend() == "cpu"
    return _CPU_BACKEND


def _copy_now(make_copy):
    """The default ``copying`` of the two staging functions below: just
    make the host copy. ``ship`` passes its own, which also times it."""
    return make_copy()


_FRESH_COPIES = STAGING_COPIES.labels(kind="fresh")


def dispatch_safe(x, copying=_copy_now, kept=True):
    """Stage a host numpy array for an async jitted call.

    ``x`` may be a view of memory that is reused (a staging buffer's
    ``take()``, a decode arena), so it is copied before the call
    returns: ``device_put`` is asynchronous, and a view released and
    overwritten by the next cycle could still be mid-transfer (ADR
    0130).

    - Accelerators: the copy goes into a kept host buffer of
      ``staging_pool.POOL``, reused once the device array put from it
      reads ready, and an explicit async ``jax.device_put`` follows, so
      that the transfer of batch i+1 overlaps the kernel of batch i
      (passing raw numpy into a jitted call would transfer during
      dispatch on the caller's thread). A copy into kept memory runs at
      memcpy speed (3.4 ms for 64 MiB on the chip's host); a fresh
      array of 32 MiB or more is a new mapping faulted in page by page,
      79 ms for the same bytes (``scripts/host_copy_probe.py``).
    - The CPU backend keeps a fresh copy per call: XLA's CPU client
      aliases suitably-aligned numpy buffers into device arrays
      zero-copy, so a kept buffer would be overwritten under a live
      array. It returns numpy, uncommitted.
    - ``kept=False`` asks for the fresh copy on an accelerator too. The
      detector views' flat wires do: a flatten's output is itself a
      fresh array a window, and copying it into a kept buffer and
      freeing it at once left glibc trimming and re-faulting it window
      after window in most runs of ``dream_banks.paced14`` (the median
      picture a quarter older in 4 runs of 5; ``PERF.md`` §6, PR 36).

    ``copying`` is called with the function that makes that host copy
    and returns its result: ``ship`` times the copy through it.
    """
    if not isinstance(x, np.ndarray):
        return x
    if _cpu_backend():
        _FRESH_COPIES.inc()
        return copying(x.copy)
    import jax

    if not kept:
        _FRESH_COPIES.inc()
        return jax.device_put(copying(x.copy))
    return POOL.stage(x, jax.device_put, copying=copying)


_STAGED_SLOTS = STAGED_EVENTS.labels(kind="staged")
_PAD_SLOTS = STAGED_EVENTS.labels(kind="pad")


def ship(
    batch: EventBatch, arrays: tuple, device=None, *, kept=True
) -> tuple:
    """The ``h2d`` leaf span of a stage-cache miss: ``arrays`` (the
    batch's wire, raw or flattened) through ``dispatch_safe`` or, placed,
    ``stage_for``, ``kept`` as theirs. It times the host copy and the
    ENQUEUE of the asynchronous ``device_put``; the transfer itself
    completes under the tick's ``fetch``. Inside it the aggregate
    ``h2d_copy``: the host copies alone, summed over the arrays (each
    array is still copied, then put, before the next is copied), so
    that what is left of ``h2d`` is the enqueue. With it the count of
    what was shipped: the bucket's slots, and those of them that are
    padding."""
    copy_s = 0.0

    def copying(make_copy):
        nonlocal copy_s
        start = time.perf_counter()
        with TRACER.annotated("h2d_copy"):
            copied = make_copy()
        copy_s += time.perf_counter() - start
        return copied

    with TRACER.span("h2d", args={"bytes": sum(a.nbytes for a in arrays)}):
        if device is None:
            shipped = tuple(dispatch_safe(a, copying, kept) for a in arrays)
        else:
            shipped = tuple(
                stage_for(a, device, copying=copying, kept=kept)
                for a in arrays
            )
        TRACER.observe("h2d_copy", copy_s)
    _STAGED_SLOTS.inc(batch.padded_size)
    _PAD_SLOTS.inc(batch.padded_size - batch.n_valid)
    return shipped


def stage_raw(
    batch: EventBatch, cache=None, tag: str = "", device=None, *, on_miss=None
):
    """Stage a batch's raw ``(pixel_id, toa)`` pair for the device path.

    With a window's stream cache (``core/device_event_cache.py``) the
    8 B/event transfer happens ONCE per (stream, tag) and every
    device-path consumer — weighted/replica detector views, Q-family
    kernels — shares the staged arrays by reference. The raw wire does
    not depend on any projection layout, so the key needs no layout
    fingerprint; ``tag`` distinguishes pre-staging content transforms
    (e.g. the monitor workflow's pixel-id clamp).

    ``device`` (mesh-slice placement, parallel/mesh_tick.py) commits the
    staged pair to that device instead of the default; the cache key
    carries it, so two groups placed on different slices each stage once
    — per slice, never per job (ADR 0115).

    Batches carrying ``prologue=True`` (batch-decoded wire, ADR 0125)
    get the device decode prologue fused in here: the pixel-id sanitize
    the per-message host path does eagerly runs as one jitted device op
    on the staged pair instead. The cache key is unchanged — the staged
    VALUE is what downstream kernels consume either way, and the
    prologue's canonicalization (out-of-range → -1) is exactly what
    every kernel already treats as the drop marker.

    ``on_miss`` is called where the pair is really shipped (no cache,
    or a miss of it): the caller's own count of the wires it staged.
    """

    def stage():
        if on_miss is not None:
            on_miss()
        pid, toa = ship(batch, (batch.pixel_id, batch.toa), device)
        if getattr(batch, "prologue", False):
            from .decode_prologue import decode_prologue

            pid, toa = decode_prologue(pid, toa)
        return pid, toa

    if cache is None:
        return stage()
    return cache.get_or_stage(
        ("raw", tag, batch.padded_size, device_token(device)), stage
    )


def device_token(device) -> int | None:
    """Hashable stage-cache token for a placement device (None = the
    process default): the id is stable for the process lifetime and
    cheap, unlike hashing the device object across jax versions."""
    return None if device is None else int(device.id)


def leaf_device_set(leaf, *, committed_only: bool = False):
    """The device set of one array leaf, or None for host values (and,
    under ``committed_only``, for uncommitted arrays — those follow
    whatever placement a dispatch picks, so they carry no placement
    information). The ONE probe shared by the placement layers
    (ops/publish.publish_device, parallel/mesh_tick.state_on,
    ops/histogram._state_slice_device) so a jax ``devices()``/
    ``committed`` semantics change lands in one place."""
    devices = getattr(leaf, "devices", None)
    if not callable(devices):
        return None
    if committed_only and not getattr(leaf, "committed", False):
        return None
    try:
        return devices()
    except Exception:  # pragma: no cover - exotic array types
        return None


def _places_on_cpu(target) -> bool:
    """Whether ``target`` (a sharding or a device) places on a CPU
    device, whose client aliases host memory. Read from the target and
    not from the default backend: a CPU sharding in a process whose
    default backend is the chip still aliases."""
    devices = getattr(target, "device_set", None)
    if devices is None:
        devices = (target,) if hasattr(target, "platform") else ()
    if not devices:
        return _cpu_backend()
    return any(d.platform == "cpu" for d in devices)


def stage_for(arr, sharding, *, dtype=None, copying=_copy_now, kept=True):
    """Stage a batch onto ``sharding`` in ONE placement hop.

    The sharded kernels' counterpart of ``dispatch_safe``: the same
    defensive host copy (into a kept buffer of the staging pool, or a
    fresh array where ``sharding`` places on a CPU device or ``kept``
    is False, so the async transfer never reads memory the caller has
    already reused) and the
    same asynchronous ``device_put``, so batch i+1's transfer overlaps
    batch i's kernel, but placed directly onto the target sharding:
    routing a host array through ``dispatch_safe`` first would commit
    it to the DEFAULT device and pay a second device->device copy on
    the resharded placement. ``dtype`` optionally normalizes wire
    dtypes on the host (one pass: the cast is the copy); device arrays
    cast on device. ``copying`` as in ``dispatch_safe``.
    """
    import jax

    if isinstance(arr, jax.Array):
        if dtype is not None and arr.dtype != np.dtype(dtype):
            arr = arr.astype(dtype)
        return jax.device_put(arr, sharding)
    if not kept or _places_on_cpu(sharding):
        _FRESH_COPIES.inc()
        return jax.device_put(
            copying(lambda: np.array(arr, dtype=dtype, copy=True)), sharding
        )
    return POOL.stage(
        arr,
        lambda staged: jax.device_put(staged, sharding),
        dtype=dtype,
        copying=copying,
    )


def make_staging_buffer(min_bucket: int = MIN_BUCKET, prefer_native: bool = True):
    """StagingBuffer factory: the native C++ buffer (native/ingest.cpp) when
    the compiled shim is available, else the pure-Python one. Both satisfy
    the same add/take/release contract and are covered by the same tests."""
    if prefer_native:
        try:
            from ..native import NativeStagingBuffer, available
        except ImportError as err:
            _log_native_fallback(err)
        else:
            if available():
                try:
                    return NativeStagingBuffer(min_bucket=min_bucket)
                except (OSError, MemoryError, RuntimeError) as err:
                    _log_native_fallback(err)
    return StagingBuffer(min_bucket=min_bucket)


def _log_native_fallback(err: Exception) -> None:
    import logging

    logging.getLogger(__name__).warning(
        "Native staging buffer unavailable, using Python fallback: %s", err
    )

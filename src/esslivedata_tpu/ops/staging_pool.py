"""Kept host buffers for the staging copy (ADR 0130).

The defensive copy in front of an asynchronous ``device_put``
(``ops/event_batch.py``) used to allocate a fresh array per call. Past
glibc's 32 MiB ceiling for its dynamic mmap threshold every such array
is a new mapping, faulted in page by page while it is filled: 79 ms for
64 MiB where a copy into memory that is kept takes 3.4 ms
(``scripts/host_copy_probe.py``). :class:`StagingPool` keeps the
buffers: a slot is one host array, handed out again only once the
device array that was last put from it reads ready.

Nothing is configured. A caller that finds no ready slot of its dtype
and shape gets a new one, so the pool holds one generation of buffers
in the serial loop (the tick's ``fetch`` precedes the next window's
staging) and two where windows overlap (``core/ingest_pipeline.py``).
A slot unused for ``IDLE_SECONDS`` (a bucket left behind after an
escalation relaxes, a stream that stopped) is given back. ``sweep``
does that, and lets go of device arrays seen ready; every staging runs
it, and the service loop's 30 s metrics line does where nothing is
staged any more.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

import numpy as np

from ..telemetry.instruments import STAGING_COPIES, STAGING_POOL_BYTES

__all__ = ["StagingPool", "transfer_done"]

#: A slot not staged into for this long is dropped at the next sweep:
#: a minute of base windows, so a bucket that alternates with another
#: keeps its slots and one that was left behind does not pin its bytes.
IDLE_SECONDS = 60.0

_KEPT = STAGING_COPIES.labels(kind="kept")
_FRESH = STAGING_COPIES.labels(kind="fresh")


def transfer_done(array) -> bool:
    """True once the transfer that fills ``array`` has read its host
    buffer to the end. A deleted array has no transfer left to wait
    for (and ``is_ready`` raises on one)."""
    return array.is_deleted() or array.is_ready()


class _Slot:
    __slots__ = ("buffer", "in_flight", "leased", "used_at")

    def __init__(self, buffer: np.ndarray, now: float) -> None:
        self.buffer = buffer
        #: What was last put from ``buffer``, until it was seen ready.
        self.in_flight: Any = None
        #: True from ``_acquire`` until the put has returned.
        self.leased = True
        self.used_at = now


class StagingPool:
    """Host buffers by (dtype, shape), each reused once its last
    transfer is done. ``ready`` says whether what a ``put`` returned has
    finished reading its host buffer; ``clock`` ages idle slots;
    ``gauge`` follows the bytes held. Thread-safe: the job threads of a
    Q service stage at once."""

    def __init__(
        self,
        *,
        ready: Callable[[Any], bool] = transfer_done,
        clock: Callable[[], float] = time.monotonic,
        gauge=None,
    ) -> None:
        self._ready = ready
        self._clock = clock
        self._gauge = gauge
        self._lock = threading.Lock()
        self._slots: dict[tuple, list[_Slot]] = {}
        self._bytes = 0

    @property
    def nbytes(self) -> int:
        """Host bytes the pool holds."""
        return self._bytes

    def stage(
        self,
        src,
        put: Callable[[np.ndarray], Any],
        *,
        dtype=None,
        copying: Callable[[Callable[[], Any]], Any] = lambda make: make(),
    ):
        """``put(copy of src)``, the copy living in a kept buffer.

        One pass over ``src``: the cast to ``dtype`` (``astype``'s
        rules) is the copy. ``copying`` is called with the function
        that takes the slot and fills it, as ``dispatch_safe``'s is with
        the one that makes its copy. What ``put`` returns is tied to the
        slot until it reads ready, and returned.
        """
        src = np.asarray(src)
        dtype = src.dtype if dtype is None else np.dtype(dtype)

        def fill() -> _Slot:
            slot = self._acquire(dtype, src.shape)
            np.copyto(slot.buffer, src, casting="unsafe")
            return slot

        slot = copying(fill)
        shipped = None
        try:
            shipped = put(slot.buffer)
        finally:
            # A put that raised leaves nothing in flight: the slot is
            # free again.
            with self._lock:
                slot.in_flight, slot.leased = shipped, False
        return shipped

    def _acquire(self, dtype: np.dtype, shape: tuple) -> _Slot:
        """A slot of this dtype and shape whose last transfer is done,
        leased to the caller, or a new one where there is none."""
        key = (dtype.str, shape)
        with self._lock:
            now = self._clock()
            self._sweep(now)
            for slot in self._slots.get(key, ()):
                if not slot.leased and slot.in_flight is None:
                    slot.leased, slot.used_at = True, now
                    _KEPT.inc()
                    return slot
            _FRESH.inc()
            slot = _Slot(np.empty(shape, dtype), now)
            self._slots.setdefault(key, []).append(slot)
            self._set_bytes(self._bytes + slot.buffer.nbytes)
            return slot

    def sweep(self) -> None:
        """Every slot: forget a transfer seen done (the slot's hold on
        the device array ends there, so the HBM of a window's wire is
        free before the next window's is put), and give back a slot
        idle past ``IDLE_SECONDS``."""
        with self._lock:
            self._sweep(self._clock())

    def _sweep(self, now: float) -> None:
        freed = 0
        for key, slots in list(self._slots.items()):
            kept = []
            for slot in slots:
                if slot.in_flight is not None and self._ready(slot.in_flight):
                    slot.in_flight = None
                idle = not slot.leased and slot.in_flight is None
                if idle and now - slot.used_at > IDLE_SECONDS:
                    freed += slot.buffer.nbytes
                else:
                    kept.append(slot)
            if kept:
                self._slots[key] = kept
            else:
                del self._slots[key]
        if freed:
            self._set_bytes(self._bytes - freed)

    def _set_bytes(self, nbytes: int) -> None:
        self._bytes = nbytes
        if self._gauge is not None:
            self._gauge.set(nbytes)


#: The process's pool: what ``dispatch_safe`` and ``stage_for`` copy
#: into on a backend that does not alias host memory.
POOL = StagingPool(gauge=STAGING_POOL_BYTES)

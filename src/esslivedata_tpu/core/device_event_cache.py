"""Stage-once device event cache: one transfer per (stream, layout), not per job.

Before this cache, every job subscribed to a detector stream staged the
window's event batch privately — K jobs on one stream meant K host
flatten/partition passes and K host→device transfers of identical bytes
(``Job.add`` → per-workflow ``accumulate`` → ``dispatch_safe``):
per-job staging scaled the host pass and the transfer by K for no
information gain. This module inverts the
ownership: staging belongs to the *stream*, jobs consume device-resident
arrays by reference — the same share-the-staged-input move inference
serving stacks use to amortize transfer cost across consumers (ADR 0110).

Lifecycle (serial path, driven by ``JobManager.process_jobs``):

- ``begin_window()`` opens a new window generation; per-stream
  :class:`StreamStageSlot` handles are attached to the window's
  ``StagedEvents`` values.
- Consumers (workflow kernels) call ``slot.get_or_stage(key, fn)``:
  the first caller under a key runs ``fn`` (host decode→flatten→
  ``dispatch_safe``) and every later caller — any job, any thread —
  gets the same staged object back.
- ``end_window()`` drops every staged reference. Entries never outlive
  a window (each window carries new events), which also makes job
  attach/detach trivially safe: a job added or removed between windows
  can never observe another generation's arrays.

The pipelined ingest (``core/ingest_pipeline.py``, ADR 0111) overlaps
windows — window i+1 prestages while window i still steps — so a single
"current" generation is not enough there. ``new_generation()`` hands out
an independent, caller-owned :class:`WindowGeneration` whose slots and
lifetime the pipeline controls explicitly; the begin/end window pair
above remains a thin wrapper over the cache-owned current generation.

Keys must capture *everything* that changes the staged bytes: the
staging flavor ("raw"/"flat"/"part"/"shard"), a caller-chosen
``batch_tag`` for pre-staging transforms (e.g. the monitor workflow's
pixel-id clamp), and the projection-layout fingerprint
(``EventHistogrammer.stage_key`` — LUT digest, bin edges, block/chunk
shape). A projection-layout change therefore invalidates by *keying*,
not by flushing: the swapped layout simply misses and stages fresh.

Thread-safety: ``process_jobs`` fans consumers over a thread pool, so a
slot serializes staging per key under its lock — the second job *waits*
for the first transfer instead of duplicating it.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Hashable

import numpy as np

from ..telemetry.trace import TRACER

__all__ = [
    "DecodeArena",
    "DecodeArenaPool",
    "DeviceEventCache",
    "EventIngest",
    "StreamStageSlot",
    "WindowGeneration",
    "default_decode_pool",
]

logger = logging.getLogger(__name__)


# ---------------------------------------------------------------------------
# Decode arenas (ADR 0125): reusable staging landing zones for batch decode
# ---------------------------------------------------------------------------

#: Floor arena capacity: below this, growth churn dominates reuse.
_ARENA_MIN = 1 << 12
#: Free-list depth: the pipelined ingest keeps at most a few windows in
#: flight, so a deeper pool would only pin dead memory.
_ARENA_POOL_DEPTH = 4


def _arena_capacity(n: int) -> int:
    """Power-of-two capacity ≥ max(n, floor) — mirrors the event-batch
    bucketing (ops/event_batch.py) so one steady-state arena per pool
    slot absorbs every poll size without reallocating."""
    cap = _ARENA_MIN
    while cap < n:
        cap <<= 1
    return cap


class DecodeArena:
    """One pinned (page-locked where the allocator provides it; plain
    host-contiguous otherwise) staging landing zone for the batch wire
    decoder: an int32 pixel lane and a float32 time-of-arrival lane that
    grow geometrically and are reused poll after poll.

    Ownership contract: whoever holds the :class:`_ArenaLease` wrapping
    an arena owns BOTH lanes outright — views into them
    (``kafka.wire.Ev44Batch``, the ``EventBatch`` a ref-mode
    ``ToEventBatch`` emits) stay valid exactly as long as the lease is
    referenced, and the arena re-enters its pool only when the lease is
    garbage-collected."""

    __slots__ = ("pixel", "toa", "capacity")

    def __init__(self, capacity: int = _ARENA_MIN) -> None:
        capacity = _arena_capacity(capacity)
        self.capacity = capacity
        self.pixel = np.empty(capacity, dtype=np.int32)
        self.toa = np.empty(capacity, dtype=np.float32)

    def ensure(self, n: int) -> None:
        """Grow (never shrink) to hold at least ``n`` events."""
        if n > self.capacity:
            cap = _arena_capacity(n)
            self.capacity = cap
            self.pixel = np.empty(cap, dtype=np.int32)
            self.toa = np.empty(cap, dtype=np.float32)


class _ArenaLease:
    """Checkout handle for one arena: proxies the lanes, returns the
    arena to its pool on finalization. The return is reference-counted
    by Python itself — a decoded batch keeps its lease alive through
    ``EventBatch.owner``, so an arena can never be handed to the next
    poll while a previous window still reads it."""

    __slots__ = ("_pool", "_arena")

    def __init__(self, pool: DecodeArenaPool, arena: DecodeArena) -> None:
        self._pool = pool
        self._arena = arena

    @property
    def pixel(self) -> np.ndarray:
        return self._arena.pixel

    @property
    def toa(self) -> np.ndarray:
        return self._arena.toa

    @property
    def capacity(self) -> int:
        return self._arena.capacity

    def __del__(self) -> None:
        # A finalizer may run during interpreter shutdown, when the
        # pool's lock/module globals are already torn down — logging
        # here can itself raise, so this swallow stays silent.
        try:
            self._pool._release(self._arena)
        except Exception:  # graftlint: disable=JGL007
            pass  # pragma: no cover - interpreter shutdown


class DecodeArenaPool:
    """Bounded free list of :class:`DecodeArena`.

    ``lease(n)`` hands out an arena sized for ``n`` events (reusing a
    pooled one when available, growing it in place if undersized); the
    lease's finalizer returns it. Keeping the pool bounded means a
    pathological burst allocates transient arenas that simply drop on
    release instead of ratcheting resident memory."""

    def __init__(self, depth: int = _ARENA_POOL_DEPTH) -> None:
        self._lock = threading.Lock()
        self._free: list[DecodeArena] = []
        self._depth = depth

    def lease(self, n: int) -> _ArenaLease:
        with self._lock:
            arena = self._free.pop() if self._free else None
        if arena is None:
            arena = DecodeArena(n)
        else:
            arena.ensure(n)
        return _ArenaLease(self, arena)

    def _release(self, arena: DecodeArena) -> None:
        with self._lock:
            if len(self._free) < self._depth:
                self._free.append(arena)

    def free_count(self) -> int:
        with self._lock:
            return len(self._free)


_DEFAULT_POOL: DecodeArenaPool | None = None
_DEFAULT_POOL_LOCK = threading.Lock()


def default_decode_pool() -> DecodeArenaPool:
    """Process-wide arena pool the batch wire decoder leases from when
    the caller does not bring its own."""
    global _DEFAULT_POOL
    if _DEFAULT_POOL is None:
        with _DEFAULT_POOL_LOCK:
            if _DEFAULT_POOL is None:
                _DEFAULT_POOL = DecodeArenaPool()
    return _DEFAULT_POOL


@dataclass(frozen=True)
class EventIngest:
    """A workflow's offer to have one staged-events value ingested by the
    fused stepping layer instead of its own ``accumulate``.

    Workflows that step a shared :class:`~..ops.histogram.EventHistogrammer`
    state from a ``StagedEvents`` value expose ``event_ingest(stream,
    staged) -> EventIngest | None`` (duck-typed, like ``supports_snapshot``).
    The JobManager groups offers by ``(stream, key)`` and advances every
    group member's state in ONE jitted dispatch (``step_many``) from ONE
    cached staging — then tells the job to skip that stream in
    ``accumulate`` so nothing double-counts.

    ``key`` must be the histogrammer's ``fuse_key`` extended with the
    ``batch_tag``: equal keys promise both identical staged input and an
    identical step program.
    """

    key: tuple
    hist: Any  # EventHistogrammer (duck-typed: step_many)
    batch: Any  # EventBatch, possibly transformed (must match batch_tag)
    batch_tag: str
    get_state: Callable[[], Any]
    set_state: Callable[[Any], None]

    def stage(self, cache, *, pool=None, device=None) -> tuple:
        """The staged device arrays for this offer's wire, handed
        STRAIGHT into a fused/tick program (ops/tick.py, ADR 0114) as a
        flat tuple — no per-job intermediate views are materialized.
        Same keys and staging functions as ``step_many`` would use, so
        the transfer happens once per (stream, layout) however many
        jobs' states the program advances, and a prestaged window
        (ADR 0111) is a guaranteed hit. ``device`` is the group's mesh
        slice (parallel/mesh_tick.py): the wire is committed there and
        the stage-once key carries it, so staging is once per slice.
        The kwarg is forwarded only when set — bespoke duck-typed
        histogrammers predating slice placement keep working."""
        kwargs = {} if device is None else {"device": device}
        return self.hist.tick_staging(
            self.batch, cache, batch_tag=self.batch_tag, pool=pool,
            **kwargs,
        )


def _staged_nbytes(obj: Any) -> int:
    """Approximate wire bytes of a staged object (array or tuple of
    arrays): jax and numpy arrays both expose ``nbytes``."""
    if isinstance(obj, tuple):
        return sum(_staged_nbytes(o) for o in obj)
    return int(getattr(obj, "nbytes", 0))


class _StageEntry:
    """Per-key staging latch: the first claimant stages, later claimants
    wait on the event instead of duplicating the work — while *other*
    keys on the same stream stage concurrently (two projection layouts
    must not serialize each other's host flattens)."""

    __slots__ = ("event", "value", "error")

    def __init__(self) -> None:
        self.event = threading.Event()
        self.value: Any = None
        self.error: BaseException | None = None


class StreamStageSlot:
    """One stream's staging table for the current window."""

    __slots__ = ("_cache", "stream", "_entries", "_lock", "_closed")

    def __init__(self, cache: DeviceEventCache, stream: str) -> None:
        self._cache = cache
        self.stream = stream
        self._entries: dict[Hashable, _StageEntry] = {}
        self._lock = threading.Lock()
        self._closed = False

    def get_or_stage(self, key: Hashable, stage: Callable[[], Any]) -> Any:
        """The staged object for ``key``; runs ``stage`` exactly once per
        window per key (concurrent same-key callers wait; distinct keys
        stage in parallel). After ``end_window`` the slot degrades to a
        passthrough (stage, don't retain) so a late consumer — a
        finishing job flushed on an idle tick — can never pin or read a
        stale generation."""
        with self._lock:
            if self._closed:
                owner, entry = True, None
            else:
                entry = self._entries.get(key)
                owner = entry is None
                if owner:
                    entry = _StageEntry()
                    self._entries[key] = entry
        if entry is None:  # closed slot: pure passthrough
            return stage()
        if owner:
            t0 = time.perf_counter()
            try:
                entry.value = stage()
            except BaseException as err:
                entry.error = err
                # Drop the poisoned entry so a later caller may retry
                # (the private fallback path re-stages after a fused
                # failure, and must not inherit the dead latch).
                with self._lock:
                    if self._entries.get(key) is entry:
                        del self._entries[key]
                raise
            finally:
                entry.event.set()
            # Wall time of the flatten+dispatch against the bytes it
            # moved, measured where the work actually happens.
            self._cache._record_miss(
                _staged_nbytes(entry.value), time.perf_counter() - t0
            )
            return entry.value
        # What this reader waits for the thread that stages the entry
        # (0 for an entry that was ready): with two jobs on one stream
        # the second stands here for the first's ``h2d``.
        with TRACER.aggregate("stage_wait"):
            entry.event.wait()
        if entry.error is not None:
            raise entry.error
        self._cache._record_hit()
        return entry.value

    def __contains__(self, key: Hashable) -> bool:
        with self._lock:
            entry = self._entries.get(key)
            return entry is not None and entry.event.is_set()

    def _close(self) -> None:
        with self._lock:
            self._closed = True
            self._entries.clear()


class WindowGeneration:
    """One window's staging slots, as an explicit caller-owned handle.

    The serial path never sees this class (the cache keeps a private
    current generation behind ``begin_window``/``end_window``); the
    pipelined ingest opens one generation per in-flight window and
    closes it after that window's publish, so two overlapped windows
    can never alias each other's staged arrays."""

    __slots__ = ("_cache", "_slots", "_lock", "_closed")

    def __init__(self, cache: DeviceEventCache) -> None:
        self._cache = cache
        self._slots: dict[str, StreamStageSlot] = {}
        self._lock = threading.Lock()
        self._closed = False

    def slot(self, stream: str) -> StreamStageSlot:
        with self._lock:
            try:
                return self._slots[stream]
            except KeyError:
                s = StreamStageSlot(self._cache, stream)
                if self._closed:
                    # A slot requested after close degrades to the same
                    # passthrough as a closed slot: never retain.
                    s._close()
                self._slots[stream] = s
                return s

    def close(self) -> None:
        """Drop every staged reference; later consumers pass through."""
        with self._lock:
            self._closed = True
            for slot in self._slots.values():
                slot._close()
            self._slots = {}


class DeviceEventCache:
    """Per-stream stage-once cache for one service's event streams."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._current = WindowGeneration(self)
        # Cumulative stats since construction / last drain: the bench's
        # wire_bytes_per_event and the 30 s metrics line read these.
        # Leaf-level lock: _record_* run while a slot lock is held, so
        # they must never reach back for the generation lock above.
        self._stats_lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self._bytes_staged = 0
        self._staging_s = 0.0
        # Cumulative twins drain_stats() never resets — the telemetry
        # collector (ADR 0116) needs monotone counters while the 30 s
        # metrics line keeps draining its own interval totals.
        self._cum_hits = 0
        self._cum_misses = 0
        self._cum_bytes_staged = 0
        self._cum_staging_s = 0.0

    # -- window lifecycle -------------------------------------------------
    def new_generation(self) -> WindowGeneration:
        """An independent window generation the caller owns and closes —
        the pipelined ingest's per-in-flight-window handle."""
        return WindowGeneration(self)

    def begin_window(self) -> None:
        """Open a new window generation: previous slots close (their
        staged references drop) and fresh slots hand out on demand."""
        with self._lock:
            self._current.close()
            self._current = WindowGeneration(self)

    def slot(self, stream: str) -> StreamStageSlot:
        with self._lock:
            return self._current.slot(stream)

    def end_window(self) -> None:
        """Drop every staged reference. Device memory frees once the last
        in-flight kernel consuming an array completes (JAX refcounts);
        the cache never pins a batch past its window."""
        self.begin_window()

    def invalidate(self) -> None:
        """Flush all slots immediately (job attach/detach hook). With
        window-scoped entries this is belt-and-braces — entries cannot
        cross windows anyway — but it keeps the invalidation rule
        explicit at the call sites that change the consumer set."""
        self.begin_window()

    # -- stats ------------------------------------------------------------
    def _record_miss(self, nbytes: int, seconds: float = 0.0) -> None:
        with self._stats_lock:
            self._misses += 1
            self._bytes_staged += nbytes
            self._staging_s += seconds
            self._cum_misses += 1
            self._cum_bytes_staged += nbytes
            self._cum_staging_s += seconds

    def _record_hit(self) -> None:
        with self._stats_lock:
            self._hits += 1
            self._cum_hits += 1

    def cumulative_stats(self) -> dict[str, int | float]:
        """Monotone totals since construction (telemetry collector).
        ``lookups`` is hits + misses: what a share of either is over."""
        with self._stats_lock:
            return {
                "hits": self._cum_hits,
                "misses": self._cum_misses,
                "lookups": self._cum_hits + self._cum_misses,
                "bytes_staged": self._cum_bytes_staged,
                "staging_s": self._cum_staging_s,
            }

    def stats(self) -> dict[str, int | float]:
        """{hits, misses, bytes_staged, staging_s, hit_rate} since the
        last drain."""
        with self._stats_lock:
            total = self._hits + self._misses
            return {
                "hits": self._hits,
                "misses": self._misses,
                "bytes_staged": self._bytes_staged,
                "staging_s": self._staging_s,
                "hit_rate": (self._hits / total) if total else 0.0,
            }

    def drain_stats(self) -> dict[str, int | float]:
        with self._stats_lock:
            total = self._hits + self._misses
            out = {
                "hits": self._hits,
                "misses": self._misses,
                "bytes_staged": self._bytes_staged,
                "staging_s": self._staging_s,
                "hit_rate": (self._hits / total) if total else 0.0,
            }
            self._hits = 0
            self._misses = 0
            self._bytes_staged = 0
            self._staging_s = 0.0
        return out

"""AOT warm-up: compile the tick program BEFORE the job goes live.

Every job commit, layout swap or regroup re-keys the tick
program LRU, and the next live window pays trace + XLA compile + first
execute on the hot path — the exact p99 spike class the PR 9 compile
instrument (``livedata_jit_compiles_total{site,trigger}``) measures.
This module closes the loop (ROADMAP item 1, SNIPPETS.md [1]
``Lowered`` AOT path):

- The :class:`~..core.job_manager.JobManager` plans, at commit time,
  exactly the (histogrammer, group key, staged signature, member set)
  tuples its next publish tick will dispatch — against the batch shape
  the stream has actually been carrying — and submits them here as
  :class:`WarmupRequest`\\ s. Member states travel as
  ``jax.ShapeDtypeStruct`` trees: signatures match the live key
  byte-for-byte, and the warm-up thread can never touch (or donate) a
  live buffer.
- A single background worker synthesizes a zero-filled
  :class:`~..ops.event_batch.EventBatch` of the remembered padded size,
  stages it exactly as the live tick would (same ``tick_staging``, same
  device), and calls :meth:`~..ops.tick.TickCombiner.warm` — which
  AOT-lowers, compiles, and seeds the program LRU with the ready
  executable. The next live tick is a cache hit: no compile event,
  first-tick latency == steady state.
- The runners' persistent compilation cache (utils/runtime.py, every
  entry, no minimum size/time) is written by ``Lowered.compile`` too,
  so a process restart re-lowers but skips XLA entirely.

Warm-up is strictly best-effort: a failed request is counted
(``livedata_durability_warmup_failures_total``) and the live path
compiles honestly — the instrument then reports the miss instead of a
warmed lie. Telemetry: ``livedata_durability_warmup_compiles_total``
(programs actually compiled off the hot path, by trigger),
``livedata_durability_warmup_seconds`` (per-request wall time).
"""

from __future__ import annotations

import logging
import queue
import threading
from dataclasses import dataclass, field
from typing import Any

from ..telemetry.registry import REGISTRY

__all__ = ["CompileWarmupService", "WarmupRequest"]

logger = logging.getLogger(__name__)

_WARMUP_COMPILES = REGISTRY.counter(
    "livedata_durability_warmup_compiles_total",
    "Tick programs AOT-compiled off the hot path by the warm-up "
    "service, by trigger (commit/regroup/layout_swap)",
    labelnames=("trigger",),
)
_WARMUP_FAILURES = REGISTRY.counter(
    "livedata_durability_warmup_failures_total",
    "Warm-up requests that failed (the live path compiles honestly "
    "and the instrument reports the miss), by trigger",
    labelnames=("trigger",),
)
_WARMUP_SECONDS = REGISTRY.histogram(
    "livedata_durability_warmup_seconds",
    "Wall time of one warm-up request (staging + AOT lower + compile)",
)


@dataclass(slots=True)
class WarmupRequest:
    """One tick-program group to warm (built by the JobManager's
    commit-time planner — ``JobManager.plan_warmup``)."""

    #: The combiner whose LRU to seed: the manager's TickCombiner, or
    #: the group's slice-bound MeshTickCombiner (ADR 0115).
    combiner: Any
    #: The group's (shared-configuration) histogrammer.
    hist: Any
    #: The fused-group key (fuse key + batch tag) — ``EventIngest.key``.
    group_key: tuple
    #: The synthetic event batch to stage (zero-filled, padded to the
    #: bucket size the stream has been carrying); already transformed
    #: by the offer (monitor row0-clamp etc.), so staging it reproduces
    #: the live wire's shapes exactly.
    batch: Any
    batch_tag: str
    #: The group's mesh-slice device (None = default placement).
    device: Any
    #: Per-member (publisher, args-as-ShapeDtypeStruct-tree,
    #: static_token), in planner order — the live member order.
    members: list[tuple]
    #: Why this warm-up fired (telemetry label).
    trigger: str = "commit"
    #: Set when the worker finished this request (tests/quiesce).
    done: threading.Event = field(default_factory=threading.Event)


class CompileWarmupService:
    """Background AOT compiler feeding the tick-program LRUs.

    One daemon worker, one bounded queue: warm-up traffic is command-
    rate (job commits, policy flips), so the queue is small and a full
    queue drops the OLDEST request — the newest plan reflects the
    current job set, and an evicted older plan would have warmed a
    member tuple that no longer exists.
    """

    def __init__(self, *, queue_size: int = 64) -> None:
        self._queue: queue.Queue[WarmupRequest | None] = queue.Queue(
            maxsize=max(1, int(queue_size))
        )
        self._dropped = 0
        self._inflight = 0
        self._lock = threading.Lock()
        self._idle = threading.Event()
        self._idle.set()
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="compile-warmup", daemon=True
        )
        self._thread.start()

    # -- submission --------------------------------------------------------
    def submit(self, requests) -> int:
        """Enqueue warm-up requests; returns how many were accepted.
        Never blocks the caller (the service thread submits at command
        time): on overflow the oldest queued request drops."""
        accepted = 0
        for request in requests:
            if self._closed:
                break
            with self._lock:
                self._inflight += 1
                self._idle.clear()
            while True:
                try:
                    self._queue.put_nowait(request)
                    accepted += 1
                    break
                except queue.Full:
                    try:
                        dropped = self._queue.get_nowait()
                    except queue.Empty:  # pragma: no cover - race
                        continue
                    if dropped is not None:
                        self._request_done(dropped)
                        with self._lock:
                            self._dropped += 1
        return accepted

    def quiesce(self, timeout: float = 30.0) -> bool:
        """Block until every accepted request has been processed (or
        dropped). The bench/tests use this to assert the 0-compile
        contract deterministically; services never call it."""
        return self._idle.wait(timeout)

    def close(self) -> None:
        self._closed = True
        try:
            self._queue.put_nowait(None)
        except queue.Full:
            pass
        self._thread.join(timeout=5.0)

    def stats(self) -> dict[str, int]:
        with self._lock:
            return {"inflight": self._inflight, "dropped": self._dropped}

    # -- worker ------------------------------------------------------------
    def _request_done(self, request: WarmupRequest) -> None:
        request.done.set()
        with self._lock:
            self._inflight -= 1
            if self._inflight <= 0:
                self._inflight = 0
                self._idle.set()

    # graft: thread=warmup   (the AOT compile worker)
    def _run(self) -> None:
        while True:
            try:
                # Timeboxed get (JGL010): the worker re-checks the
                # close flag instead of parking forever — a close()
                # whose sentinel was dropped by a full queue must
                # still terminate it.
                request = self._queue.get(timeout=1.0)
            except queue.Empty:
                if self._closed:
                    return
                continue
            if request is None:
                return
            try:
                self._warm_one(request)
            except Exception:
                _WARMUP_FAILURES.inc(trigger=request.trigger)
                logger.exception(
                    "warm-up failed for group %r (trigger %s); the "
                    "live path will compile on its next tick",
                    request.group_key,
                    request.trigger,
                )
            finally:
                self._request_done(request)

    @staticmethod
    def _warm_one(request: WarmupRequest) -> None:
        import time as _time

        from ..ops.publish import PublishRequest

        t0 = _time.perf_counter()
        # Stage the synthetic batch exactly as the live tick would —
        # same tick_staging, same device — so the staged signature in
        # the warmed key equals the live key. cache=None: the warm-up
        # must never populate (or collide with) a window's stream slot.
        kwargs = {} if request.device is None else {
            "device": request.device
        }
        staged = request.hist.tick_staging(
            request.batch,
            None,
            batch_tag=request.batch_tag,
            **kwargs,
        )
        requests = [
            PublishRequest(publisher, args, static_token)
            for publisher, args, static_token in request.members
        ]
        compiled = request.combiner.warm(
            request.hist, request.group_key, staged, requests
        )
        seconds = _time.perf_counter() - t0
        _WARMUP_SECONDS.observe(seconds)
        if compiled:
            _WARMUP_COMPILES.inc(compiled, trigger=request.trigger)
            logger.info(
                "warmed %d tick program(s) for group %r in %.0f ms "
                "(trigger %s)",
                compiled,
                request.group_key,
                1e3 * seconds,
                request.trigger,
            )

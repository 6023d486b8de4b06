"""Pipelined host ingest executor: decode | prestage | step/publish.

Why
---
PERF.md's stage table says steady-state throughput should be
``max(stage)``, but the serial service loop pays ``sum(stages)``: only
the device transfer overlaps compute (``dispatch_safe``'s async
``device_put``), while ev44 accumulate/collect (decode), the host
flatten/partition (~32 ms per 4M events — the measured host bound once
pallas2d beats the 93M ev/s scatter ceiling) and the fused step/publish
all run back to back on the one service-loop thread. This module turns
the loop into a bounded three-stage pipeline (ADR 0111):

- **decode** — ``MessagePreprocessor`` accumulate + collect, then the
  window's staged events are *detached* (owned copies) so the service
  thread can release and refill the staging buffers for the next batch
  while this one is still in flight.
- **stage** — a fresh cache generation is attached
  (``JobManager.open_window``) and every subscribed consumer's wire is
  prestaged (``prestage_window``: host flatten/partition — optionally
  chunked over a thread pool — plus the async device transfer), warming
  the stage-once slots the step stage will hit.
- **step** — ``JobManager.process_jobs(prestaged=True)`` + publish, the
  only stage that touches job state, in submission order. On the
  tick-program fast path (ops/tick.py, ADR 0114) the stage's device
  work collapses to ONE submit: the prestaged wire feeds a single
  jitted step+publish program per group, so a steady-state window costs
  this stage one execute + one fetch — the "publish" timing below is
  sink serialization only, never a second device round trip.

Ordering and parity
-------------------
One worker per stage and FIFO bounded queues give a strict global order:
window i's step always precedes window i+1's step, and publishes leave
in submission order (asserted: a reordering is a bug, not a mode). The
work each stage runs is byte-for-byte the work the serial path runs —
prestaging uses the same keys and staging functions ``step_batch``/
``step_many`` would use, and per-state op order is unchanged — so
outputs are bit-identical to serial ingest (pinned by
tests/workflows/cache_parity_test.py).

Backpressure and shutdown
-------------------------
Queues are bounded and every put/get carries a timeout (graftlint
JGL010: an unbounded hand-off turns a slow stage into unbounded memory;
a timeout-less block turns shutdown into a hang). ``submit`` blocks when
the in-flight window count reaches the pipeline depth — a slow stage
throttles the service thread, which the adaptive batcher then sees as
processing time and answers with bigger windows. ``stop(drain=True)``
refuses new work, drains every queued window through all stages (no
drops, no reorders — pinned by tests/core/ingest_pipeline_test.py), and
joins the workers. A worker failure latches the exception and re-raises
it on the service thread at the next submit, preserving the serial
loop's fail-fast supervisor contract (core/service.py).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable

from ..telemetry.e2e import observe_stage
from ..telemetry.trace import TRACER
from ..utils.profiling import StageTimer

__all__ = ["IngestPipeline", "PipelineWindow"]

logger = logging.getLogger(__name__)

#: Worker poll tick: every blocking queue op times out at this interval
#: to observe shutdown (JGL010 — no timeout-less blocking on threads
#: that also dispatch jitted work).
_TICK_S = 0.1


@dataclass(slots=True)
class PipelineWindow:
    """One window moving through the stages."""

    seq: int
    payload: Any  # decode-stage input (MessageBatch or prebuilt window)
    start: Any = None
    end: Any = None
    data: dict[str, Any] = field(default_factory=dict)
    context: dict[str, Any] = field(default_factory=dict)
    fresh_context: set[str] | None = None
    generation: Any = None  # WindowGeneration, attached by the stage stage
    #: Every result the window published, ahead or at its end.
    results: list = field(default_factory=list)
    #: Wall seconds per stage for THIS window (the completion callback's
    #: load signal: the slowest stage is the pipeline's service time).
    stage_s: dict[str, float] = field(default_factory=dict)
    t_submit: float = 0.0
    #: Telemetry trace id (ADR 0116), allocated at decode: every span
    #: this window records — across all three stage workers and the
    #: device layers — shares it, so a slow tick decomposes by phase.
    trace: int | None = None
    #: Source data timestamp (ns) of the newest message in this window
    #: (ADR 0120): born at consume from ``MessageBatch.end``, it anchors
    #: every ``livedata_e2e_latency_seconds`` boundary the window
    #: crosses (staged/published here; fanout/delivery in the serving
    #: plane via ``JobResult.source_ts_ns``).
    source_ts_ns: int | None = None
    #: Source timestamp (ns) of the OLDEST message in this window: the
    #: ``stage=decode`` observation anchors here (ADR 0125). Decode is
    #: batch-granular — one observation per window, not per message —
    #: and anchoring at the oldest member keeps the histogram an upper
    #: bound on any single message's decode latency instead of
    #: understating it by up to the window span. Falls back to
    #: ``source_ts_ns`` when the batcher provides no per-message view.
    oldest_ts_ns: int | None = None


class IngestPipeline:
    """Bounded multi-stage ingest executor (see module docstring).

    Parameters
    ----------
    job_manager:
        The service's JobManager; supplies ``open_window``,
        ``prestage_window`` and ``process_jobs``.
    decode:
        ``decode(payload) -> (data, context, fresh_context)`` — the
        processor's preprocess+collect+detach step. Receives the
        submitted payload; ``None`` payloads (empty windows flushed for
        finishing jobs) skip decode.
    publish:
        ``publish(results, end)`` — called from the step worker, in
        submission order, only when results are nonempty.
    on_complete:
        Optional ``on_complete(window)`` called after publish with the
        per-stage timings (the processor feeds the batcher and its
        metrics from this).
    depth:
        Bound on in-flight windows, and the capacity of each stage's
        queue. Depth 1 degenerates to serial-with-threads.
    flatten_workers:
        >1 enables the chunked parallel host flatten in prestaging.
    """

    def __init__(
        self,
        *,
        job_manager,
        decode: Callable[[Any], tuple[dict, dict, set[str] | None]],
        publish: Callable[[list, Any], None],
        on_complete: Callable[[PipelineWindow], None] | None = None,
        depth: int = 2,
        flatten_workers: int = 0,
        name: str = "ingest",
    ) -> None:
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self._job_manager = job_manager
        self._decode = decode
        self._publish = publish
        self._on_complete = on_complete
        #: In-flight window bound (the submit gate).
        self.depth = depth
        self._flatten_pool = (
            ThreadPoolExecutor(
                max_workers=flatten_workers,
                thread_name_prefix=f"{name}-flatten",
            )
            if flatten_workers > 1
            else None
        )
        # Bounded stage hand-offs (JGL010): the submit gate below
        # admits at most ``depth`` windows, so no queue can hold more.
        self._decode_q: queue.Queue[PipelineWindow] = queue.Queue(
            maxsize=depth
        )
        self._stage_q: queue.Queue[PipelineWindow] = queue.Queue(
            maxsize=depth
        )
        self._step_q: queue.Queue[PipelineWindow] = queue.Queue(
            maxsize=depth
        )
        self._inflight = 0
        self._state_lock = threading.Condition()
        self._seq = 0
        self._last_completed_seq = -1
        self._completed = 0
        self._published = 0
        self._accepting = True
        self._stopped = threading.Event()
        self._failure: BaseException | None = None
        self._timer = StageTimer()
        self._t_started = time.monotonic()
        #: Fault-injection schedule (harness/chaos.py, ADR 0120): None
        #: in production — every hook is a single attribute check.
        self._chaos = None
        self.name = name
        self._workers = [
            threading.Thread(
                target=self._guarded, args=(fn,), name=f"{name}-{label}",
                daemon=True,
            )
            for label, fn in (
                ("decode", self._decode_loop),
                ("stage", self._stage_loop),
                ("step", self._step_loop),
            )
        ]
        for worker in self._workers:
            worker.start()

    # -- submission --------------------------------------------------------
    def submit(
        self, payload, *, start=None, end=None, oldest_ts_ns=None
    ) -> int:
        """Enqueue one window; blocks while the pipeline is at depth
        (backpressure — the caller's stall is the load signal). Returns
        the window's sequence number. ``oldest_ts_ns`` anchors the
        batch-granular ``stage=decode`` e2e observation (ADR 0125);
        omitted, it falls back to the window-end timestamp. Raises a
        latched worker failure or RuntimeError after ``stop()``."""
        self._reraise_failure()
        window = PipelineWindow(
            seq=-1, payload=payload, start=start, end=end,
            t_submit=time.perf_counter(),
            source_ts_ns=(
                int(end.ns) if hasattr(end, "ns") else None
            ),
            oldest_ts_ns=(
                int(oldest_ts_ns) if oldest_ts_ns is not None else None
            ),
        )
        with self._state_lock:
            while self._accepting and self._inflight >= self.depth:
                self._state_lock.wait(timeout=_TICK_S)
                self._reraise_failure()
            if not self._accepting:
                raise RuntimeError(f"pipeline {self.name} is stopped")
            window.seq = self._seq
            self._seq += 1
            self._inflight += 1
        if not self._put(self._decode_q, window):
            self._reraise_failure()
            raise RuntimeError(f"pipeline {self.name} is stopped")
        return window.seq

    def flush(self, timeout: float | None = None) -> bool:
        """Wait until every submitted window has completed; True on
        drained, False on timeout."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._state_lock:
            while self._inflight > 0:
                self._reraise_failure()
                remaining = (
                    _TICK_S
                    if deadline is None
                    else min(_TICK_S, deadline - time.monotonic())
                )
                if remaining <= 0:
                    return False
                self._state_lock.wait(timeout=remaining)
            return True

    def stop(self, *, drain: bool = True, timeout: float = 30.0) -> bool:
        """Refuse new submits, optionally drain all in-flight windows
        through every stage (no drops, no reorders), stop the workers.
        Returns True when the drain completed. Idempotent."""
        with self._state_lock:
            self._accepting = False
            self._state_lock.notify_all()
        drained = True
        try:
            if drain and self._failure is None:
                drained = self.flush(timeout=timeout)
                if not drained:
                    logger.warning(
                        "pipeline %s: drain timed out with %d windows in "
                        "flight",
                        self.name,
                        self._inflight,
                    )
        finally:
            # A failure latched mid-drain makes flush raise — the
            # workers and the flatten pool must still be torn down, or
            # every in-process restart leaks three polling threads.
            self._stopped.set()
            for worker in self._workers:
                worker.join(timeout=5.0)
            if self._flatten_pool is not None:
                self._flatten_pool.shutdown(wait=False)
        return drained

    def set_chaos(self, chaos) -> None:
        """Install a fault-injection schedule (harness/chaos.py). The
        hooks fire on the worker threads; the schedule's own seeded
        draws keep runs reproducible."""
        self._chaos = chaos

    # -- introspection -----------------------------------------------------
    @property
    def failure(self) -> BaseException | None:
        return self._failure

    def stats(self) -> dict[str, Any]:
        """Per-stage busy time + utilization since the last drain.

        ``utilization`` is stage busy seconds over pipeline wall
        seconds: the slowest stage's utilization approaches 1.0 at
        steady state, and the *sum* exceeding 1.0 is the overlap the
        serial loop forfeits (bench.py --pipeline reports this)."""
        wall = max(time.monotonic() - self._t_started, 1e-9)
        stages = self._timer.drain()
        self._t_started = time.monotonic()
        with self._state_lock:
            completed, published = self._completed, self._published
            inflight = self._inflight
        return {
            "wall_s": wall,
            "completed": completed,
            "published": published,
            "inflight": inflight,
            "depth": self.depth,
            "stages": stages,
            "utilization": {
                stage: entry["total_s"] / wall
                for stage, entry in stages.items()
            },
        }

    def queue_depths(self) -> dict[str, int]:
        """Instantaneous per-stage queue depths (telemetry gauges,
        ADR 0116): a persistently full queue names the bottleneck stage
        the utilization averages can only hint at. ``qsize`` is racy by
        nature — that is fine for a gauge sampled at scrape time."""
        return {
            "decode": self._decode_q.qsize(),
            "stage": self._stage_q.qsize(),
            "step": self._step_q.qsize(),
        }

    def telemetry(self) -> dict[str, Any]:
        """Scrape-time snapshot for the telemetry collector: queue
        depths, in-flight/limit, window counts and CUMULATIVE per-stage
        busy seconds (never drained — ``stats()`` keeps its 30 s
        drain-and-reset semantics for the metrics log)."""
        with self._state_lock:
            completed, published = self._completed, self._published
            inflight = self._inflight
        return {
            "queues": self.queue_depths(),
            "inflight": inflight,
            "depth": self.depth,
            "completed": completed,
            "published": published,
            "stages": self._timer.cumulative(),
        }

    # -- stage workers -----------------------------------------------------
    def _guarded(self, loop: Callable[[], None]) -> None:
        try:
            loop()
        except BaseException as err:  # latch: resurfaced on submit
            logger.exception("pipeline %s worker failed", self.name)
            with self._state_lock:
                self._failure = err
                self._state_lock.notify_all()

    def _reraise_failure(self) -> None:
        if self._failure is not None:
            raise RuntimeError(
                f"pipeline {self.name} worker failed"
            ) from self._failure

    def _put(self, q: queue.Queue, window: PipelineWindow) -> bool:
        """Bounded hand-off to the next stage. False = the pipeline was
        stopped without drain; the caller discards the window."""
        while not self._stopped.is_set():
            try:
                q.put(window, timeout=_TICK_S)
                return True
            except queue.Full:
                if self._failure is not None:
                    break
        self._discard(window)
        return False

    def _discard(self, window: PipelineWindow) -> None:
        """Account for a window abandoned by a no-drain stop."""
        if window.generation is not None:
            window.generation.close()
        with self._state_lock:
            self._inflight -= 1
            self._state_lock.notify_all()

    def _get(self, q: queue.Queue) -> PipelineWindow | None:
        while not self._stopped.is_set():
            try:
                return q.get(timeout=_TICK_S)
            except queue.Empty:
                continue
        return None

    # graft: thread=decode
    def _decode_loop(self) -> None:
        while True:
            window = self._get(self._decode_q)
            if window is None:
                return
            # The trace id is born HERE, with the window's decode
            # (ADR 0116): every later span — prestage on the stage
            # worker, tick-execute/fetch in the device layers, finalize
            # and sink on the step worker — records against it.
            window.trace = TRACER.new_trace()
            t0 = time.perf_counter()
            with self._timer.stage("decode"):
                if window.payload is None:
                    window.data, window.context = {}, {}
                    window.fresh_context = None
                else:
                    (
                        window.data,
                        window.context,
                        window.fresh_context,
                    ) = self._decode(window.payload)
                    window.payload = None  # drop message refs early
            window.stage_s["decode"] = time.perf_counter() - t0
            TRACER.record(
                "decode", t0, window.stage_s["decode"], window.trace
            )
            observe_stage(
                "decode",
                window.oldest_ts_ns
                if window.oldest_ts_ns is not None
                else window.source_ts_ns,
            )
            if self._chaos is not None:
                # Chaos site (ADR 0120): a stalled decode worker — the
                # shape of a slow preprocessor or GC pause — backs the
                # whole pipeline up into the submit gate.
                self._chaos.maybe_delay("decode_stall")
            if not self._put(self._stage_q, window):
                return

    # graft: thread=stage
    def _stage_loop(self) -> None:
        while True:
            window = self._get(self._stage_q)
            if window is None:
                return
            t0 = time.perf_counter()
            with self._timer.stage("stage"):
                window.generation = self._job_manager.open_window(window.data)
                self._job_manager.prestage_window(
                    window.data, pool=self._flatten_pool
                )
            window.stage_s["stage"] = time.perf_counter() - t0
            TRACER.record(
                "prestage", t0, window.stage_s["stage"], window.trace
            )
            observe_stage("staged", window.source_ts_ns)
            if not self._put(self._step_q, window):
                return

    # graft: thread=step
    def _step_loop(self) -> None:
        while True:
            window = self._get(self._step_q)
            if window is None:
                return

            def publish(results: list, window=window) -> None:
                # The window's publisher: the manager calls it with
                # each tick group's results while later groups are
                # still on the chip (ADR 0128), this loop once more
                # with what is left.
                with TRACER.span("sink", window.trace):
                    self._publish(results, window.end)
                window.results.extend(results)

            try:
                t0 = time.perf_counter()
                # Bind the window's trace for everything the step runs:
                # the device layers (tick combiner execute/fetch spans,
                # finalize) read the thread-bound id — they don't know
                # the window.
                with self._timer.stage("step"), TRACER.bind(window.trace):
                    rest = self._job_manager.process_jobs(
                        window.data,
                        context=window.context,
                        fresh_context=window.fresh_context,
                        start=window.start,
                        end=window.end,
                        prestaged=True,
                        publish=publish,
                    )
                window.stage_s["step"] = time.perf_counter() - t0
                t0 = time.perf_counter()
                with self._timer.stage("publish"):
                    if rest:
                        publish(rest)
                    if window.results:
                        # "published" means results actually left: an
                        # empty window (no jobs due) records nothing.
                        # Once per window, at its last publish.
                        observe_stage("published", window.source_ts_ns)
                # Publish-stage time here is the serialization of what
                # was left at the window's end; the device round trip
                # and the groups published ahead are inside the step.
                window.stage_s["publish"] = time.perf_counter() - t0
            finally:
                if window.generation is not None:
                    window.generation.close()
            if window.seq != self._last_completed_seq + 1:
                # Single-worker FIFO stages make this structurally
                # impossible; if it ever fires, ordering — a correctness
                # guarantee consumers rely on — broke. Fail loudly.
                raise RuntimeError(
                    f"pipeline {self.name} reordered windows: completed "
                    f"{window.seq} after {self._last_completed_seq}"
                )
            self._last_completed_seq = window.seq
            if window.trace is not None:
                # Slow-tick watchdog (ADR 0116): submit->published wall
                # time against the latched threshold; a breach logs this
                # window's full span breakdown.
                TRACER.finish_tick(
                    window.trace, time.perf_counter() - window.t_submit
                )
            if self._on_complete is not None:
                try:
                    self._on_complete(window)
                except Exception:
                    logger.exception(
                        "pipeline %s completion callback failed", self.name
                    )
            with self._state_lock:
                self._inflight -= 1
                self._completed += 1
                if window.results:
                    self._published += 1
                self._state_lock.notify_all()

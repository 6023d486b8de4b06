"""Per-tick tracer: trace ids, spans, Chrome trace_event export, watchdog.

The 30 s metrics line says WHAT is slow on average; it cannot say what
happened inside the one tick that blew the p99. This module closes that
gap (ADR 0116): every ingest window gets a **trace id** when it is
decoded, and each phase of its life records a span ``(trace_id, name,
start, duration, thread, args)`` into a bounded ring buffer.
Correlation is the whole point: the spans of one window share its id
across the three pipeline workers and the job threads, so a slow tick
decomposes into which phase ate the time.

The spans of the serial loop, in the order a tick runs them:

- ``decode``: preprocess + collect of the window's messages (``args``:
  ``hold_us``, how long the window's last message sat in the batcher);
  inside it the aggregate ``land``, one observation per stream of the
  window: landing the stream's chunks into its one wire
  (``preprocessors/event_data.ToEventBatch``);
- ``flatten``: host flatten / partition of one stream's events, once
  per stage-cache miss (``events``, ``padded``);
- ``h2d``: the host copy and the ENQUEUE of the asynchronous
  ``device_put`` (``bytes``); the transfer itself completes on the
  device's queue, behind the program of the group before;
- ``tick_execute`` (``publish_execute`` on the combined-publish path):
  the dispatch. It is asynchronous: host Python plus the submit, not
  the program's run time;
- ``fetch``: the wait for the chip (``block_until_ready`` on the
  program's outputs) followed by the copy back, whose transfers are
  enqueued before the wait; what is left of the copy after the wait
  is the aggregate ``d2h``;
- ``finalize``, ``sink``.

A tick with several (stream, fuse-key) groups runs ``flatten``,
``h2d``, ``tick_execute`` once per group and only then the groups'
``fetch`` spans (``JobManager._run_tick_programs``): the host stages
group i+1 while the chip runs group i, so the ``fetch`` spans add up to
what of the chip's work is left to wait for after the last dispatch.
Each ``fetch`` but the last is followed by the ``finalize`` and the
``sink`` of the group it collected (ADR 0128: a group's results leave
while the chip works on the groups behind); the last group's, and
every job's outside a tick group, come once more at the tick's end.

The pipelined path adds ``prestage`` (its stage worker's flatten +
H2D as one span). **The ring stays flat**: the spans one thread
records never overlap, a span's parent is its tick (the trace id), and
an enclosing or contained phase, or a wait for threads whose own spans
are in the ring, is an aggregate on ``/metrics``
(:meth:`TickTracer.observe`, :meth:`TickTracer.aggregate`), never a
second ring entry. ``benchmark/harness/trace_reduce.py:name_gap`` adds
the ring's spans up and relies on it. The aggregates:

- ``tick``, ``unspanned`` (:meth:`TickTracer.finish_tick`), ``d2h``
  (inside ``fetch``), ``land`` (inside ``decode``);
- ``accumulate_wait``: the loop thread's wait for the pool that runs a
  window's private ``job.add`` calls (``JobManager.process_jobs``), the
  pool branch alone. The job threads' ``h2d`` and ``q_step`` are in the
  ring under their own thread, so this one **counts toward the loop
  thread's coverage** instead (``covers=True``): ``unspanned`` is what
  no phase explains, with or without a pool;
- ``accumulate`` (one per job per window, on whichever thread runs it:
  context delivery + ``job.add``) and ``pool_queue`` (the pool branch:
  from the fan-out to the start of that job's turn on a pool thread);
- ``stage_wait``: what a stage-once hit waited for the thread that
  stages the entry (``StreamStageSlot.get_or_stage``; 0 for an entry
  that was ready);
- ``h2d_copy``: the host copies of one ``h2d`` alone, one observation
  a ``ship`` call; the rest of ``h2d`` is ``device_put``'s enqueue.

One clock: spans and tick totals read ``time.perf_counter()``; a dump
names it and carries the offset to the epoch sampled in this process.
While a ``jax.profiler`` session runs (``--profile``, ``POST
/profile``) every :meth:`TickTracer.span` is mirrored into the
profiler's own trace as a ``TraceAnnotation``, on the profiler's
clock, next to the device ops; so are the aggregates that are timed
regions (``accumulate_wait``, ``stage_wait``, ``h2d_copy``): the
profiler's trace is per thread and nests, only the ring has to stay
flat.

Three consumers:

- ``--trace-dump PATH`` on every service runner writes the ring as
  Chrome ``trace_event`` JSON (chrome://tracing / Perfetto loadable) at
  exit; tests and operators can also call :meth:`TickTracer.dump` live.
- The **slow-tick watchdog**: :meth:`TickTracer.finish_tick` checks the
  window's wall time against a latched threshold and logs the full span
  breakdown of the offending tick — the threshold latches onto the
  triggering duration and decays back toward the configured floor
  (``LIVEDATA_SLOW_TICK_MS``, default 250), so a persistently slow
  phase logs once per regime shift instead of once per tick.
- Span durations feed the ``livedata_tick_span_seconds`` histogram in
  the metrics registry, so the scrape carries the same decomposition
  in aggregate.

Hot-path cost: an enabled span is two ``perf_counter`` calls, one
histogram observe, one deque append under the ring lock and, where jax
is loaded, the profiler's is-a-session-running flag test; an aggregate
is the same without the append (and without the flag test where the
caller times it itself: ``accumulate``, ``pool_queue``); a disabled
tracer (``LIVEDATA_TRACE=0``) costs one attribute read (the callers
that time a region themselves keep their clock reads). Span recording
must NEVER run inside jit-traced code — it would measure trace time,
not execution (graftlint JGL018 polices this).

Thread the ACTIVE id, don't pass it: stages run on different workers,
and the device layers (``ops/tick.py``) don't know the window. The
step worker calls :meth:`set_current` before ``process_jobs``; anything
downstream records against :meth:`current` via thread-local storage.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass

from .registry import REGISTRY

__all__ = ["TRACER", "Span", "TickTracer"]

logger = logging.getLogger(__name__)

#: Aggregate span-duration decomposition on the scrape; buckets from
#: sub-ms host phases up through multi-second device ticks.
_SPAN_SECONDS = REGISTRY.histogram(
    "livedata_tick_span_seconds",
    "Duration of per-tick phases (decode/flatten/h2d/prestage/"
    "tick_execute/fetch/finalize/sink; aggregate only: tick/unspanned/"
    "d2h/land/accumulate_wait/accumulate/pool_queue/stage_wait/"
    "h2d_copy), labeled by span name",
    labelnames=("span",),
)


@dataclass(frozen=True, slots=True)
class Span:
    """One recorded phase of one traced window."""

    trace_id: int
    name: str
    start_s: float  # perf_counter timebase
    duration_s: float
    thread: str
    #: Small counts taken at the span's boundary (events, bytes, ...):
    #: exported in the Chrome event's ``args`` and as the profiler
    #: annotation's keywords.
    args: dict[str, int] | None = None


def _session_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session runs,
    else None: what gives a span its twin in the profiler's own trace.

    One flag test when no session runs, and only where jax is already
    loaded: the fakes and the relay never import it, and this module
    must not be what does."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None or not profiler.TraceAnnotation.is_enabled():
        return None
    return profiler.TraceAnnotation


class TickTracer:
    """Bounded ring of spans + trace-id allocation + slow-tick watchdog.

    ``capacity`` bounds memory for long-running services: at 15 (three
    tick groups) to 31 (seven) spans per 1 s window the default 8192
    spans hold the last four to nine minutes — enough to dump the
    context around any slow tick the watchdog just logged.
    """

    def __init__(
        self,
        capacity: int = 8192,
        *,
        enabled: bool | None = None,
        slow_tick_s: float | None = None,
    ) -> None:
        if enabled is None:
            enabled = os.environ.get("LIVEDATA_TRACE", "1").lower() not in (
                "0",
                "false",
                "no",
            )
        if slow_tick_s is None:
            slow_tick_s = (
                float(os.environ.get("LIVEDATA_SLOW_TICK_MS", "250")) / 1e3
            )
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._spans: deque[Span] = deque(maxlen=int(capacity))
        self._next_id = 1
        self._local = threading.local()
        #: Watchdog latch: starts at the configured floor; a triggering
        #: tick raises it to the observed duration (so a sustained
        #: regime logs once, not every tick) and every healthy tick
        #: decays it back toward the floor.
        self._slow_floor_s = float(slow_tick_s)
        self._slow_latch_s = float(slow_tick_s)
        self._slow_ticks = 0
        #: True from a breach until the latch decays back to the floor
        #: — the /healthz degraded signal (telemetry/health.py): "a
        #: slow-tick regime happened and has not yet cleared".
        self._slow_latched = False

    # -- trace ids ---------------------------------------------------------
    def new_trace(self) -> int:
        """Allocate the id for one window — called at decode."""
        with self._lock:
            trace_id = self._next_id
            self._next_id += 1
        return trace_id

    def set_current(self, trace_id: int | None) -> None:
        """Bind ``trace_id`` as this thread's active trace (None clears):
        downstream layers (tick combiners, finalize) record against it
        without knowing the window."""
        self._local.trace_id = trace_id

    def current(self) -> int | None:
        return getattr(self._local, "trace_id", None)

    @contextmanager
    def bind(self, trace_id: int | None):
        previous = self.current()
        self.set_current(trace_id)
        try:
            yield
        finally:
            self.set_current(previous)

    # -- spans -------------------------------------------------------------
    def record(
        self, name: str, start_s: float, duration_s: float,
        trace_id: int | None = None, args: dict[str, int] | None = None,
    ) -> None:
        """Fold one externally timed span in (hot path; see module
        docstring for cost). ``trace_id=None`` uses the thread's bound
        trace; spans with no trace at all still aggregate into the
        histogram but skip the ring (a ring entry without an id cannot
        be correlated, which is the ring's only job)."""
        if not self.enabled:
            return
        _SPAN_SECONDS.observe(duration_s, span=name)
        if trace_id is None:
            trace_id = self.current()
        if trace_id is None:
            return
        span = Span(
            trace_id=trace_id,
            name=name,
            start_s=start_s,
            duration_s=duration_s,
            thread=threading.current_thread().name,
            args=args,
        )
        self._cover(trace_id, duration_s)
        with self._lock:
            self._spans.append(span)

    def _cover(self, trace_id: int, seconds: float) -> None:
        """What this thread's phases cover of the trace, for
        finish_tick's ``unspanned``: one running sum per thread (a
        thread works on one tick at a time), never a ring scan."""
        local = self._local
        if getattr(local, "covered_id", None) == trace_id:
            local.covered_s += seconds
        else:
            local.covered_id = trace_id
            local.covered_s = seconds

    def observe(
        self, name: str, seconds: float, *, covers: bool = False
    ) -> None:
        """An aggregate-only phase: into the span histogram, never the
        ring. For a phase that encloses ring spans (``tick``), lies
        inside one (``d2h`` inside ``fetch``) or waits for threads
        whose own spans are in the ring: a ring entry for it would
        overlap them, and the ring stays flat. ``covers``: the calling
        thread spent this time on its bound trace under no ring span of
        its own (``accumulate_wait``), so it counts toward what
        ``finish_tick(tiled=True)`` takes off ``unspanned``."""
        if not self.enabled:
            return
        _SPAN_SECONDS.observe(seconds, span=name)
        if covers:
            trace_id = self.current()
            if trace_id is not None:
                self._cover(trace_id, seconds)

    def annotated(
        self, name: str, trace_id: int | None = None,
        args: dict[str, int] | None = None,
    ):
        """A context manager: the region's ``TraceAnnotation`` in the
        profiler's own trace while a session runs, nothing otherwise.
        It records nothing here: :meth:`span` and :meth:`aggregate`
        time the region around it, and a caller that sums several
        regions into one aggregate (``h2d_copy``) times them itself."""
        annotation = _session_annotation() if self.enabled else None
        if annotation is None:
            return nullcontext()
        tick = self.current() if trace_id is None else trace_id
        return annotation(name, trace_id=tick or 0, **(args or {}))

    @contextmanager
    def span(
        self, name: str, trace_id: int | None = None,
        args: dict[str, int] | None = None,
    ):
        """Record the wrapped region as one span, mirrored into the
        profiler's trace while a session runs. Never place this inside
        jit-traced code (JGL018): it times Python trace/dispatch, not
        device execution. ``record()`` (externally timed) has no such
        twin: the profiler cannot be told of a region after the fact."""
        if not self.enabled:
            yield
            return
        twin = self.annotated(name, trace_id, args)  # it starts at its enter
        start = time.perf_counter()
        try:
            with twin:
                yield
        finally:
            self.record(
                name, start, time.perf_counter() - start, trace_id, args
            )

    @contextmanager
    def aggregate(self, name: str, *, covers: bool = False):
        """The wrapped region as one :meth:`observe` (histogram only:
        the ring keeps its span names), with the profiler twin a span
        has. JGL018 holds as for :meth:`span`."""
        if not self.enabled:
            yield
            return
        twin = self.annotated(name)
        start = time.perf_counter()
        try:
            with twin:
                yield
        finally:
            self.observe(name, time.perf_counter() - start, covers=covers)

    # -- watchdog ----------------------------------------------------------
    def finish_tick(
        self, trace_id: int, total_s: float, *, tiled: bool = False
    ) -> None:
        """Window completion hook. Observes the tick's wall time
        (``perf_counter``, like the spans) as the aggregate ``tick``
        and, where the calling thread's spans tile the tick (``tiled``:
        the serial loop; the pipelined stages overlap across threads),
        what none of them covered as ``unspanned`` (negative where
        they overlap: the ring is then no longer flat). Then the
        watchdog:
        log the span breakdown of a tick whose wall time exceeds the
        latched threshold (see class docstring for the latch/decay
        shape)."""
        if not self.enabled:
            return
        _SPAN_SECONDS.observe(total_s, span="tick")
        if tiled:
            local = self._local
            covered = (
                local.covered_s
                if getattr(local, "covered_id", None) == trace_id
                else 0.0
            )
            # Signed: spans that nest or are recorded twice cover more
            # than the tick, and the remainder then reads NEGATIVE on
            # the scrape (``tick_unspanned_ms``). A clamp at zero would
            # report that fault as perfect tiling.
            _SPAN_SECONDS.observe(total_s - covered, span="unspanned")
        with self._lock:
            threshold = self._slow_latch_s
            if total_s > threshold:
                self._slow_latch_s = total_s
                self._slow_ticks += 1
                self._slow_latched = True
                spans = [s for s in self._spans if s.trace_id == trace_id]
            else:
                # Decay toward the floor so the latch re-arms once the
                # slow regime passes; reaching the floor clears the
                # degraded signal.
                self._slow_latch_s = max(
                    self._slow_floor_s, self._slow_latch_s * 0.95
                )
                if self._slow_latch_s <= self._slow_floor_s:
                    self._slow_latched = False
                return
        # SUM same-named spans: a window legitimately records several
        # (one tick_execute/fetch pair per tick group and per mesh
        # slice) — keeping only the last would point the operator at a
        # fraction of the dominant phase.
        totals: dict[str, float] = {}
        counts: dict[str, int] = {}
        for span in spans:
            totals[span.name] = totals.get(span.name, 0.0) + span.duration_s
            counts[span.name] = counts.get(span.name, 0) + 1
        breakdown = {
            name: (
                round(total * 1e3, 3)
                if counts[name] == 1
                else f"{round(total * 1e3, 3)}ms/{counts[name]}x"
            )
            for name, total in totals.items()
        }
        logger.warning(
            "slow tick: trace=%d wall=%.1f ms (threshold %.1f ms) "
            "span breakdown (ms): %s",
            trace_id,
            total_s * 1e3,
            threshold * 1e3,
            breakdown or "(no spans recorded)",
        )

    @property
    def slow_ticks(self) -> int:
        with self._lock:
            return self._slow_ticks

    @property
    def watchdog_latched(self) -> bool:
        """True between a slow-tick breach and the latch's decay back
        to the configured floor — /healthz reports ``degraded`` while
        this holds (telemetry/health.py)."""
        with self._lock:
            return self._slow_latched

    # -- export ------------------------------------------------------------
    def export(self) -> list[Span]:
        """ONE consistent snapshot of the ring, taken under the lock.

        Every exporter (:meth:`spans`, :meth:`chrome_trace`,
        :meth:`dump`) goes through here: a consumer that read the ring
        once and then came back for a count (or a second filtered view)
        would otherwise race concurrent writers — the deque trims on
        append, so spans recorded between the two reads silently
        evict spans the first read promised were there. Pinned by the
        export hammer in tests/telemetry/trace_test.py."""
        with self._lock:
            return list(self._spans)

    def spans(self, trace_id: int | None = None) -> list[Span]:
        snapshot = self.export()
        if trace_id is None:
            return snapshot
        return [s for s in snapshot if s.trace_id == trace_id]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def chrome_trace(self, spans: list[Span] | None = None) -> dict:
        """The ring as Chrome ``trace_event`` JSON (object format).

        Complete ('X') events in microseconds; the trace id rides
        ``pid`` so chrome://tracing groups one window's spans into one
        row-set, with the worker thread preserved in ``tid`` and the
        span's counts in ``args``.
        ``spans`` lets a caller render an :meth:`export` snapshot it
        already holds (dump does — payload and count must describe the
        SAME snapshot)."""
        if spans is None:
            spans = self.export()
        return {
            # Which clock ``ts`` is on, and what to add to put it on the
            # epoch: sampled HERE, in the process that recorded the
            # spans (a reader in another process can only guess).
            "clock": "perf_counter",
            "epoch_minus_clock_ns": time.time_ns() - time.perf_counter_ns(),
            "traceEvents": [
                {
                    "name": span.name,
                    "cat": "tick",
                    "ph": "X",
                    "ts": span.start_s * 1e6,
                    "dur": span.duration_s * 1e6,
                    "pid": span.trace_id,
                    "tid": span.thread,
                    "args": {"trace_id": span.trace_id, **(span.args or {})},
                }
                for span in spans
            ],
            "displayTimeUnit": "ms",
        }

    def dump(self, path: str) -> None:
        """Write :meth:`chrome_trace` to ``path`` (--trace-dump). One
        snapshot backs both the payload and the logged count — reading
        the live ring again for the count would describe a different
        (possibly trimmed) ring than the file holds."""
        spans = self.export()
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(self.chrome_trace(spans), fh)
        logger.info("trace dumped to %s (%d spans)", path, len(spans))


#: Process-wide tracer: the service runners, pipeline and device layers
#: all record here (LIVEDATA_TRACE=0 disables span recording globally).
TRACER = TickTracer()

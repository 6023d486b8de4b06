"""The heart of a backend service: one processor cycle.

Parity with reference ``core/orchestrating_processor.py`` (process:200):
pull -> split commands/run-control/data (:212-218) -> dispatch commands ->
batch -> preprocess per stream (MessagePreprocessor:55) -> context
enrichment -> JobManager.process_jobs (:286) -> publish results -> release
buffers (zero-copy contract :287) -> 2 s status heartbeats (:327) and 30 s
metrics (:364-415) -> idempotent finalize (:417) publishing final stopped
statuses. Per-batch processing time feeds the adaptive batcher — the
implicit load profiler.
"""

from __future__ import annotations

import logging
import threading
import time
from collections.abc import Iterable
from typing import Any

from ..config.acknowledgement import CommandAcknowledgement
from ..core.preprocessor import PreprocessorFactory
from ..telemetry.e2e import observe_stage
from ..telemetry.instruments import BATCH_HOLD_SECONDS
from ..telemetry.trace import TRACER
from .command_dispatcher import CommandDispatcher
from .job_manager import JobManager
from .job import JobResult, ServiceStatus, StreamLag, StreamLagReport
from .message import (
    RESPONSE_STREAM,
    STATUS_STREAM,
    Message,
    MessageSink,
    MessageSource,
    RunStart,
    RunStop,
    StreamId,
    StreamKind,
)
from .message_batcher import BatchHold, MessageBatcher
from .timestamp import Duration, Timestamp

__all__ = ["MessagePreprocessor", "OrchestratingProcessor"]

logger = logging.getLogger(__name__)

HEARTBEAT_INTERVAL_S = 2.0
METRICS_INTERVAL_S = 30.0


def _transport_of(source, max_depth: int = 8):
    """Innermost transport exposing health+metrics, or None.

    The processor's source is a decorator chain (AdaptingMessageSource
    holds ``_source``; the synthesizers hold ``_wrapped``); the
    circuit-breaker state lives on the raw transport at the bottom
    (kafka/source.py BackgroundMessageSource.health)."""
    s = source
    for _ in range(max_depth):
        if s is None:
            return None
        if hasattr(s, "health") and hasattr(s, "metrics"):
            return s
        s = getattr(s, "_source", None) or getattr(s, "_wrapped", None)
    return None


def _oldest_ts_ns(batch) -> int | None:
    """Oldest member timestamp (ns) of a closed MessageBatch — the
    batch-granular ``stage=decode`` e2e anchor (ADR 0125). Batches are
    mostly time-ordered but merge multiple streams, so take the true
    minimum; None when the batch carries no timestamped messages."""
    messages = getattr(batch, "messages", None)
    if not messages:
        return None
    try:
        return min(int(m.timestamp.ns) for m in messages)
    except (AttributeError, TypeError, ValueError):
        return None


class MessagePreprocessor:
    """Routes batch messages into per-stream accumulators."""

    def __init__(self, factory: PreprocessorFactory) -> None:
        self._factory = factory
        self._accumulators: dict[StreamId, Any] = {}
        self._touched: set[StreamId] = set()
        self._dropped_streams: set[StreamId] = set()
        self.message_counts: dict[str, int] = {}
        # Pipelined ingest moves preprocess onto the decode worker while
        # the service thread keeps reading the counts for heartbeats —
        # the increment is a read-modify-write and the status snapshot
        # iterates the dict, so both sides take this lock (uncontended
        # acquisition is tens of ns against the >= 71 ms window).
        self._counts_lock = threading.Lock()

    def _get(self, stream: StreamId):
        if stream in self._accumulators:
            return self._accumulators[stream]
        if stream in self._dropped_streams:
            return None
        acc = self._factory.make_preprocessor(stream)
        if acc is None:
            self._dropped_streams.add(stream)
            return None
        self._accumulators[stream] = acc
        return acc

    def preprocess(self, messages: Iterable[Message]) -> None:
        for msg in messages:
            acc = self._get(msg.stream)
            if acc is None:
                continue
            try:
                acc.add(msg.timestamp, msg.value)
            except Exception:
                logger.exception("Accumulator failed for %s", msg.stream)
                continue
            self._touched.add(msg.stream)
            with self._counts_lock:
                self.message_counts[msg.stream.name] = (
                    self.message_counts.get(msg.stream.name, 0) + 1
                )

    def collect_window(self) -> dict[str, Any]:
        """Primary (non-context) data accumulated since last collect."""
        out: dict[str, Any] = {}
        for stream in self._touched:
            acc = self._accumulators[stream]
            if getattr(acc, "is_context", False):
                continue
            try:
                out[stream.name] = acc.get()
            except Exception:
                logger.exception("Accumulator get failed for %s", stream)
        return out

    def collect_context(self) -> dict[str, Any]:
        """Latest value of every context accumulator that has one.

        ``also_context`` marks primary accumulators whose value is
        additionally exposed as context — e.g. timeseries logs that both
        republish as data and gate/parameterize other jobs (the reference
        routes the same f144 stream to republish and to spec-scope context
        bindings)."""
        out: dict[str, Any] = {}
        for stream, acc in self._accumulators.items():
            if not (
                getattr(acc, "is_context", False)
                or getattr(acc, "also_context", False)
            ):
                continue
            if hasattr(acc, "has_value") and not acc.has_value:
                continue
            try:
                out[stream.name] = acc.get()
            except ValueError:
                continue
        return out

    def fresh_context_names(self) -> set[str]:
        """Context streams that received data in this batch.

        The JobManager delivers ``set_context`` to active jobs only for
        these, so an unchanged cached value never re-fires downstream
        recompute. Must be read before :meth:`release` clears the batch's
        touched set.
        """
        out: set[str] = set()
        for stream in self._touched:
            acc = self._accumulators.get(stream)
            if acc is not None and (
                getattr(acc, "is_context", False)
                or getattr(acc, "also_context", False)
            ):
                out.add(stream.name)
        return out

    def snapshot_counts(self) -> dict[str, int]:
        """Copy of the per-stream message counts, safe against the
        decode worker's concurrent increments."""
        with self._counts_lock:
            return dict(self.message_counts)

    def release(self) -> None:
        for stream in self._touched:
            self._accumulators[stream].release_buffers()
        self._touched.clear()


class OrchestratingProcessor:
    """Processor implementation wiring source -> jobs -> sink."""

    def __init__(
        self,
        *,
        source: MessageSource,
        sink: MessageSink,
        preprocessor_factory: PreprocessorFactory,
        job_manager: JobManager,
        batcher: MessageBatcher,
        instrument: str,
        service_name: str,
        registry=None,
        device_extractor=None,
        stream_counter=None,
        clock=time.monotonic,
        heartbeat_interval_s: float = HEARTBEAT_INTERVAL_S,
        pipelined: bool = False,
        pipeline_depth: int = 2,
        flatten_threads: int = 0,
        result_fanout=None,
        durability=None,
    ) -> None:
        self._source = source
        self._sink = sink
        self._preprocessor = MessagePreprocessor(preprocessor_factory)
        self._job_manager = job_manager
        self._batcher = batcher
        self._hold = BatchHold()
        self._dispatcher = CommandDispatcher(
            job_manager=job_manager,
            instrument=instrument,
            service_name=service_name,
            registry=registry,
        )
        self._instrument = instrument
        self._service_name = service_name
        self._device_extractor = device_extractor
        self._stream_counter = stream_counter
        self._clock = clock
        self._heartbeat_interval_s = heartbeat_interval_s
        self._start_wall = clock()
        self._last_heartbeat = -float("inf")
        self._last_metrics = clock()
        self._last_batch_len = 0
        self._finalized = False
        self.last_lag_report = StreamLagReport()
        self._lag_report_wall_ns = time.time_ns()
        from ..utils.profiling import StageTimer

        self.stage_timer = StageTimer()
        # Pipelined ingest (ADR 0111): decode | prestage | step/publish
        # overlap across successive windows instead of summing on this
        # thread.
        self._pipeline = None
        #: Result fan-out tier (serving/plane.py, ADR 0117), duck-typed:
        #: ``publish_results(results, timestamp)`` mirrors the sink
        #: publish. None = no serving plane (classic deployments,
        #: tests).
        self._result_fanout = result_fanout
        #: Durability plane (durability/checkpoint.py, ADR 0118):
        #: periodic state + offset checkpoints taken HERE, on the
        #: service thread, only at quiescent window boundaries (no
        #: partial window in the batcher, no in-flight pipeline
        #: window) — the one point where "every delivered offset is in
        #: job state" holds, which is what makes restore + replay
        #: exactly-once instead of double-counting.
        self._durability = durability
        if durability is not None:
            set_durability = getattr(job_manager, "set_durability", None)
            if set_durability is not None:
                set_durability(durability)
        if result_fanout is not None:
            # Removed jobs drop their cached streams: without this the
            # plane would list a dead job in /results and pin a ring of
            # its full frames forever under job churn.
            drop_job = getattr(result_fanout, "drop_job", None)
            set_retire = getattr(job_manager, "set_retire_observer", None)
            if drop_job is not None and set_retire is not None:
                set_retire(drop_job)
        if pipelined:
            from .ingest_pipeline import IngestPipeline

            self._pipeline = IngestPipeline(
                job_manager=job_manager,
                decode=self._decode_window,
                publish=self._publish_results,
                on_complete=self._on_window_complete,
                depth=pipeline_depth,
                flatten_workers=flatten_threads,
                name=f"{service_name}-ingest",
            )
        # Unified telemetry (ADR 0116): one keyed collector per
        # processor feeding the process registry at scrape time —
        # pipeline depths/utilization, stream/sink/source
        # counters, stage-once cache totals and HBM gauges all ride it.
        # Keyed by service name so a rebuilt processor (tests, restarts)
        # REPLACES its predecessor instead of stacking dead callbacks.
        from ..telemetry.registry import REGISTRY as _registry

        self._telemetry_key = f"processor:{service_name}"
        _registry.register_collector(
            self._telemetry_key, self._telemetry_families
        )

    # -- cycle ------------------------------------------------------------
    def process(self) -> None:
        messages = list(self._source.get_messages())
        polled_at = time.perf_counter()

        commands = [m for m in messages if m.stream.kind.is_command]
        run_control = [m for m in messages if m.stream.kind.is_run_control]
        data = [m for m in messages if m.stream.kind.is_data]

        if commands:
            acks = self._dispatcher.process_messages(commands)
            self._publish_acks(acks)
        for msg in run_control:
            if isinstance(msg.value, (RunStart, RunStop)):
                self._job_manager.handle_run_transition(msg.value)

        batch = self._batcher.batch(data)
        arrived_at = self._hold.arrival(polled_at, data, batch)
        if batch is not None:
            t0 = self._clock()
            hold_s = time.perf_counter() - arrived_at
            BATCH_HOLD_SECONDS.observe(hold_s)
            if self._pipeline is not None:
                self._submit_batch(batch)
            else:
                self._process_batch(batch, hold_s)
            # Pipelined: the duration is decode+submit, where submit
            # blocks while the pipeline is at depth — backpressure from
            # a slow stage reaches the adaptive batcher as load through
            # the exact same channel serial processing time does.
            self._batcher.report_processing_time(
                Duration.from_s(self._clock() - t0)
            )
        elif self._job_manager.has_finishing_jobs():
            # A stop must complete even when no beam data flows: run an
            # empty window so finishing jobs flush any pending
            # accumulation and leave the active set (otherwise a job
            # stopped during a beam-off period stays 'finishing'
            # forever and its delisting heartbeat never happens).
            if self._pipeline is not None:
                # Through the pipeline, so the flush cannot overtake an
                # in-flight window and publishes stay ordered. end=None
                # keeps the serial semantics: no data time advances, and
                # the publish (if any) stamps wall time at publish.
                self._pipeline.submit(None)
            else:
                results = self._job_manager.process_jobs({})
                if results:
                    self._publish_results(results, Timestamp.now())

        now = self._clock()
        if now - self._last_heartbeat >= self._heartbeat_interval_s:
            self._last_heartbeat = now
            self._publish_status()
        if now - self._last_metrics >= METRICS_INTERVAL_S:
            self._last_metrics = now
            self._log_metrics()
        if self._durability is not None:
            self._maybe_checkpoint()

    # -- durability plane (durability/, ADR 0118) --------------------------
    # graft: protocol=replay (ADR 0124: the quiescent gate below is the
    # modeled guard of the exactly-once bookmark arithmetic)
    def _quiescent(self) -> bool:
        """True when every delivered message is in job state: no
        partial window buffered in the batcher, no window in flight in
        the pipeline. Checkpoints only happen here — a bookmark taken
        mid-window would either lose the buffered tail (too new) or
        replay data the dumped states already contain (too old)."""
        # A batcher that does NOT expose the probe is treated as
        # never-quiescent (no checkpoint, no bookmark): a custom
        # batcher with invisible buffering must not get bookmarks that
        # silently skip its buffered tail on restore.
        pending = getattr(self._batcher, "pending_messages", None)
        if pending is None or pending:
            return False
        if self._pipeline is not None:
            try:
                if self._pipeline.telemetry()["inflight"]:
                    return False
            except Exception:  # pragma: no cover - defensive
                return False
        return True

    def _bookmarks(self) -> dict[str, int]:
        """Per-topic next-consume offsets of everything handed to this
        processor, from the raw transport (duck-typed ``positions``;
        in-memory fakes simply have none — the manifest then carries no
        bookmarks and a restart pins to the high watermark, exactly the
        pre-durability behavior)."""
        transport = _transport_of(self._source)
        positions = getattr(transport, "positions", None)
        if positions is None:
            return {}
        try:
            return dict(positions())
        except Exception:  # pragma: no cover - defensive
            logger.debug("bookmark probe failed", exc_info=True)
            return {}

    def _maybe_checkpoint(self, *, force: bool = False) -> None:
        """Take one checkpoint when due AND quiescent (deferred
        otherwise — the next quiescent cycle retries; replay covers
        whatever the deferral leaves out)."""
        plane = self._durability
        try:
            if not force and not plane.due():
                return
            if not self._quiescent():
                return
            entries = self._job_manager.checkpoint_snapshot()
            if not entries:
                return
            plane.checkpoint(
                entries,
                offsets=self._bookmarks(),
                reset_seq=getattr(self._job_manager, "reset_seq", 0),
            )
        except Exception:
            logger.exception("checkpoint failed; will retry next cycle")

    # -- pipelined ingest (ADR 0111) ---------------------------------------
    @property
    def stop_grace_s(self) -> float:
        """How long a stop should wait for finalize (core/service.py
        reads this): pipelined processors drain in-flight windows
        before the stopped statuses go out — worst case the pipeline's
        30 s drain timeout plus three 5 s worker joins, with headroom
        for the status publish."""
        return 50.0 if self._pipeline is not None else 5.0

    def _submit_batch(self, batch) -> None:
        """Hand one closed batch to the pipeline; blocks at depth."""
        self._last_batch_len = len(batch.messages)
        self._record_lag(batch)
        self._pipeline.submit(
            batch,
            start=batch.start,
            end=batch.end,
            oldest_ts_ns=_oldest_ts_ns(batch),
        )

    # graft: thread=decode   (IngestPipeline decode worker callback)
    def _decode_window(self, batch):
        """Decode stage (pipeline decode worker): accumulate + collect,
        then detach the window so the NEXT batch's preprocess — on this
        same worker — reuses the accumulators' buffers while the
        detached window travels on. Staged events copy their arrays
        (``StagedEvents.detach``); DataArray values copy too, because
        some accumulators hand out live views into growable buffers
        (``ToNXlog.get`` sorts its prefix in place on the next collect —
        a window still in flight must not see that mutation)."""
        self._preprocessor.preprocess(batch.messages)
        window = self._preprocessor.collect_window()
        context = self._preprocessor.collect_context()
        fresh_context = self._preprocessor.fresh_context_names()
        from ..preprocessors.event_data import StagedEvents

        def detach(value):
            if isinstance(value, StagedEvents):
                return value.detach()
            copy = getattr(value, "copy", None)
            return copy() if callable(copy) else value

        data = {name: detach(value) for name, value in window.items()}
        context = {name: detach(value) for name, value in context.items()}
        self._preprocessor.release()
        return data, context, fresh_context

    # graft: thread=step   (IngestPipeline step-worker completion callback)
    def _on_window_complete(self, window) -> None:
        """Step-worker callback: fold the window's stage timings into
        the metrics timer."""
        for stage, seconds in window.stage_s.items():
            self.stage_timer.record(stage, seconds)

    def _process_batch(self, batch, hold_s: float = 0.0) -> None:
        self._last_batch_len = len(batch.messages)
        # Serial-path tracing (ADR 0116): the trace id is born at
        # decode, exactly like the pipelined decode worker's, so the
        # span names line up across both ingest modes (no prestage
        # span here — the serial loop stages at step time).
        trace_id = TRACER.new_trace()
        # The e2e anchor (ADR 0120): the window-end data time, same
        # birth point as PipelineWindow.source_ts_ns ("staged" is
        # pipelined-only — this loop stages at step time).
        source_ts_ns = (
            int(batch.end.ns) if hasattr(batch.end, "ns") else None
        )
        # Decode is batch-granular (ADR 0125): one observation per
        # window, anchored at the OLDEST member so the histogram upper-
        # bounds any single message's decode latency (same rule as the
        # pipelined decode worker).
        oldest_ts_ns = _oldest_ts_ns(batch)
        decode_ts_ns = (
            oldest_ts_ns if oldest_ts_ns is not None else source_ts_ns
        )
        t_start = time.perf_counter()
        # The hold rides the decode span so that a dump shows it tick
        # by tick beside the aggregate livedata_batch_hold_seconds.
        with self.stage_timer.stage("preprocess"), TRACER.span(
            "decode", trace_id, {"hold_us": round(hold_s * 1e6)}
        ):
            self._preprocessor.preprocess(batch.messages)
            window = self._preprocessor.collect_window()
            context = self._preprocessor.collect_context()
            fresh_context = self._preprocessor.fresh_context_names()
        observe_stage("decode", decode_ts_ns)
        self._record_lag(batch)
        published = False

        def publish(results: list[JobResult]) -> None:
            # The window's publisher: the manager calls it with each
            # tick group's results while later groups are still on the
            # chip (ADR 0128), this loop once more with what is left.
            nonlocal published
            with self.stage_timer.stage("publish"), TRACER.span(
                "sink", trace_id
            ):
                self._publish_results(results, batch.end)
            published = published or bool(results)

        try:
            with self.stage_timer.stage("process_jobs"), TRACER.bind(
                trace_id
            ):
                results = self._job_manager.process_jobs(
                    window,
                    context=context,
                    fresh_context=fresh_context,
                    start=batch.start,
                    end=batch.end,
                    publish=publish,
                )
            publish(results)
            if published:
                # "published" means results actually left: a window
                # with no due jobs records nothing. Once per window,
                # at its last publish.
                observe_stage("published", source_ts_ns)
        finally:
            self._preprocessor.release()
            # The serial loop's spans tile the tick: what none covers is
            # reported as ``unspanned`` (telemetry/trace.py).
            TRACER.finish_tick(
                trace_id, time.perf_counter() - t_start, tiled=True
            )

    def _record_lag(self, batch) -> None:
        now_ns = time.time_ns()
        lags = [
            StreamLag(
                stream_name=name,
                lag_s=(now_ns - batch.end.ns) / 1e9,
            )
            for name in {m.stream.name for m in batch.messages}
        ]
        self.last_lag_report = StreamLagReport(lags=lags)
        self._lag_report_wall_ns = now_ns

    def _current_lag_report(self) -> StreamLagReport:
        """The last report AGED to now: a stream that stopped producing
        has its staleness grow with the silence (a frozen snapshot would
        report 'ok' forever on a fully stalled stream — the worst case),
        and a future-timestamped error relaxes as the wall clock catches
        up with the data."""
        if not self.last_lag_report.lags:
            return self.last_lag_report
        age_s = (time.time_ns() - self._lag_report_wall_ns) / 1e9
        return StreamLagReport(
            lags=[
                StreamLag(
                    stream_name=lag.stream_name,
                    lag_s=lag.lag_s + age_s,
                    min_s=(
                        None if lag.min_s is None else lag.min_s + age_s
                    ),
                    max_s=(
                        None if lag.max_s is None else lag.max_s + age_s
                    ),
                    count=lag.count,
                )
                for lag in self.last_lag_report.lags
            ]
        )

    # -- publishing -------------------------------------------------------
    # Pipelined mode publishes from the step worker; serial mode calls
    # this from the service thread — both roles reach it.
    # graft: thread=step
    def _publish_results(
        self, results: list[JobResult], timestamp: Timestamp | None
    ) -> None:
        if timestamp is None:
            # Empty-window flushes carry no data time (pipelined path).
            timestamp = Timestamp.now()
        messages: list[Message] = []
        for result in results:
            for key, da in zip(result.keys(), result.outputs.values(), strict=True):
                messages.append(
                    Message(
                        timestamp=timestamp,
                        stream=StreamId(
                            kind=StreamKind.LIVEDATA_DATA, name=key.to_string()
                        ),
                        value=da,
                    )
                )
        if self._device_extractor is not None:
            # Contracted outputs additionally ride the stable-identity NICOS
            # device stream (ADR 0006, core/nicos_devices.py).
            messages.extend(self._device_extractor.extract(results))
        if messages:
            self._sink.publish_messages(messages)
        if results and self._result_fanout is not None:
            # Result fan-out tier (ADR 0117): the broadcast plane gets
            # the same finalized results the sink just published —
            # bounded host work (one delta encode per output, one
            # bounded enqueue per subscriber), contained so a fan-out
            # failure can never take the publish path down.
            try:
                self._result_fanout.publish_results(results, timestamp)
            except Exception:
                logger.exception("result fan-out failed")

    def _publish_acks(self, acks: list[CommandAcknowledgement]) -> None:
        if not acks:
            return
        self._sink.publish_messages(
            [
                Message(
                    timestamp=Timestamp.now(),
                    stream=RESPONSE_STREAM,
                    value=ack,
                )
                for ack in acks
            ]
        )

    def _service_status(self, state: str = "running") -> ServiceStatus:
        return ServiceStatus(
            service_name=self._service_name,
            instrument=self._instrument,
            state=state,
            jobs=self._job_manager.job_statuses(),
            last_batch_message_count=self._last_batch_len,
            stream_message_counts=self._preprocessor.snapshot_counts(),
            uptime_s=self._clock() - self._start_wall,
            lag_level=(report := self._current_lag_report()).worst_level,
            # The badge number must describe the lag that SET the level,
            # not an unrelated healthy stream's.
            worst_lag_s=max(
                (
                    abs(lag.lag_s)
                    for lag in report.lags
                    if lag.level != "ok"
                ),
                default=0.0,
            ),
            stream_lags={
                lag.stream_name: (round(lag.lag_s, 3), lag.level)
                for lag in report.lags
            },
            # Duck-typed: Kafka-backed transports expose circuit-breaker
            # health + counters; in-memory fakes simply don't. The
            # transport sits under decorator layers (AdaptingMessageSource,
            # synthesizers), so walk the chain to the innermost source.
            source_health=(
                h.value
                if (t := _transport_of(self._source)) is not None
                and hasattr(h := t.health, "value")
                else "ok"
            ),
            source_metrics=dict(
                t.metrics if t is not None else {}
            ),
        )

    def _publish_status(self, state: str = "running") -> None:
        status = self._service_status(state)
        now = Timestamp.now()
        # One service heartbeat plus one per-job heartbeat: NICOS monitors
        # individual jobs by their source:job_number identity while the
        # dashboard consumes the aggregated service document. On shutdown
        # the per-job heartbeats must report STOPPED — a NICOS cache keyed
        # on the job identity would otherwise latch the last live code
        # (green) for jobs of a dead service.
        jobs = status.jobs
        if state in ("stopping", "stopped"):
            from .job import JobState

            jobs = [
                job.model_copy(update={"state": JobState.STOPPED})
                for job in jobs
            ]
        self._sink.publish_messages(
            [Message(timestamp=now, stream=STATUS_STREAM, value=status)]
            + [
                Message(timestamp=now, stream=STATUS_STREAM, value=job)
                for job in jobs
            ]
        )

    def _telemetry_families(self) -> list:
        """Scrape-time collector (ADR 0116): every per-service metric
        surface this processor owns, rendered as labeled families. The
        hot path pays nothing here — each producer keeps its own
        thread-safe counters and this only snapshots them when
        ``/metrics`` is pulled (or bench embeds the registry)."""
        from ..telemetry.registry import MetricFamily, Sample

        svc = (("service", self._service_name),)

        def family(name, kind, help, rows):
            fam = MetricFamily(name, kind, help)
            suffix = "_total" if kind == "counter" else ""
            fam.samples = [
                Sample(suffix, svc + tuple(labels), float(value))
                for labels, value in rows
            ]
            return fam

        scale = getattr(self._batcher, "scale", None)
        families = [
            family(
                "livedata_stream_messages",
                "counter",
                "Messages mapped per (topic, source) by the adapter layer",
                [
                    ((("topic", t), ("source", s)), n)
                    for (t, s), n in sorted(
                        self._stream_counter.cumulative_counts().items()
                    )
                ]
                if self._stream_counter is not None
                else [],
            ),
            family(
                "livedata_preprocessed_messages",
                "counter",
                "Messages accumulated per stream by the preprocessor",
                [
                    ((("stream", name),), n)
                    for name, n in sorted(
                        self._preprocessor.snapshot_counts().items()
                    )
                ],
            ),
            family(
                "livedata_jobs",
                "gauge",
                "Jobs this service hosts",
                [((), self._job_manager.n_jobs)],
            ),
            family(
                "livedata_batcher_window_scale",
                "gauge",
                "Current window scale of the adaptive batcher (1 = the "
                "base window; each doubling recompiles the tick programs)",
                [] if scale is None else [((), scale)],
            ),
            family(
                "livedata_processor_stage_seconds",
                "counter",
                "Cumulative wall seconds per processor stage",
                [
                    ((("stage", stage),), entry["total_s"])
                    for stage, entry in sorted(
                        self.stage_timer.cumulative().items()
                    )
                ],
            ),
        ]
        cache_stats = getattr(
            self._job_manager, "event_cache_cumulative_stats", None
        )
        if cache_stats is not None:
            families.append(
                family(
                    "livedata_event_cache_events",
                    "counter",
                    "Stage-once cache totals (ADR 0110): misses ~= one "
                    "per (stream, window) regardless of job count; "
                    "lookups = hits + misses; bytes_staged is the "
                    "actual wire traffic",
                    [
                        ((("kind", kind),), value)
                        for kind, value in sorted(cache_stats().items())
                    ],
                )
            )
        if self._pipeline is not None:
            pipe = self._pipeline.telemetry()
            families.append(
                family(
                    "livedata_pipeline_queue_depth",
                    "gauge",
                    "Windows queued per pipeline stage (ADR 0111)",
                    [
                        ((("stage", stage),), depth)
                        for stage, depth in sorted(pipe["queues"].items())
                    ],
                )
            )
            families.append(
                family(
                    "livedata_pipeline_inflight",
                    "gauge",
                    "In-flight windows vs the configured depth bound",
                    [
                        ((("kind", "inflight"),), pipe["inflight"]),
                        ((("kind", "depth"),), pipe["depth"]),
                    ],
                )
            )
            families.append(
                family(
                    "livedata_pipeline_windows",
                    "counter",
                    "Windows completed/published through the pipeline",
                    [
                        ((("kind", "completed"),), pipe["completed"]),
                        ((("kind", "published"),), pipe["published"]),
                    ],
                )
            )
            families.append(
                family(
                    "livedata_pipeline_stage_busy_seconds",
                    "counter",
                    "Cumulative busy seconds per pipeline stage "
                    "(utilization = rate of this over wall time; the "
                    "sum across stages exceeding 1 is the overlap the "
                    "serial loop forfeits)",
                    [
                        ((("stage", stage),), entry["total_s"])
                        for stage, entry in sorted(pipe["stages"].items())
                    ],
                )
            )
        sink_metrics = getattr(self._sink, "metrics", None)
        if callable(sink_metrics):
            try:
                rows = sorted(sink_metrics().items())
            except Exception:
                rows = []
            families.append(
                family(
                    "livedata_kafka_sink_events",
                    "counter",
                    "Sink drop/error counters incl. the per-path "
                    "consecutive-failure streaks behind the breaker",
                    [((("kind", kind),), value) for kind, value in rows],
                )
            )
        transport = _transport_of(self._source)
        if transport is not None:
            families.append(
                family(
                    "livedata_kafka_source_events",
                    "counter",
                    "Raw transport counters (consumed/queued/dropped)",
                    [
                        ((("kind", kind),), value)
                        for kind, value in sorted(transport.metrics.items())
                    ],
                )
            )
            health = transport.health
            families.append(
                family(
                    "livedata_source_up",
                    "gauge",
                    "1 = consume transport healthy, 0 = stale/breaker open",
                    [
                        (
                            (),
                            int(
                                getattr(health, "value", health) == "ok"
                            ),
                        )
                    ],
                )
            )
        from ..utils.runtime import device_identity

        identity = device_identity()
        families.append(
            family(
                "livedata_device_info",
                "gauge",
                "The device this service computes on, as jax reports it "
                "(platform / device_kind / count as labels; value 1)",
                [(tuple((k, str(v)) for k, v in identity.items()), 1)],
            )
        )
        hbm = MetricFamily(
            "livedata_hbm_bytes",
            "gauge",
            "Per-device HBM statistics (bytes_in_use / peak_bytes_in_use "
            "/ bytes_limit); empty on backends without memory_stats",
        )
        try:
            from ..utils.profiling import device_memory_stats

            # Service-labeled like every family here: two processors in
            # one process must emit DISTINCT samples, not byte-identical
            # duplicate lines (which real scrapers reject).
            hbm.samples = [
                Sample(
                    "",
                    svc
                    + (
                        ("device", key.partition(":")[0]),
                        ("kind", key.partition(":")[2]),
                    ),
                    float(value),
                )
                for key, value in sorted(device_memory_stats().items())
            ]
        except Exception:  # pragma: no cover - backend without stats
            logger.debug("device_memory_stats unavailable", exc_info=True)
        families.append(hbm)
        return families

    def _log_metrics(self) -> None:
        extra = {
            "service": self._service_name,
            "jobs": self._job_manager.n_jobs,
            "stream_counts": self._preprocessor.snapshot_counts(),
            "lag_level": self._current_lag_report().worst_level,
        }
        # Stage-once cache counters (ADR 0110). The engagement signal is
        # misses ~= one per (stream, window) INDEPENDENT of job count —
        # not hit_rate: a fused group touches the cache exactly once, so
        # hit_rate legitimately reads 0 when sharing works best (hits
        # only appear when jobs stage privately against a warm slot).
        # bytes_staged over the interval is the actual wire traffic.
        cache_stats = getattr(self._job_manager, "event_cache_stats", None)
        if cache_stats is not None:
            extra["event_cache"] = cache_stats()
        try:
            from ..utils.profiling import device_memory_stats

            if memory := device_memory_stats():
                extra["device_memory"] = memory
        except Exception:  # pragma: no cover - backend without stats
            # Memory stats are best-effort, but a permanently failing
            # backend query should at least be visible at debug level
            # (graftlint JGL007: no silent swallows in the service loop).
            logger.debug("device_memory_stats unavailable", exc_info=True)
        if self._stream_counter is not None:
            # Adapter-layer per-(topic,source) counts + producer lag,
            # accumulated since the last rollover (kafka/stream_counter.py).
            stats = self._stream_counter.drain(METRICS_INTERVAL_S)
            extra["input_counts"] = {
                f"{s.topic}/{s.source_name}": s.count for s in stats.streams
            }
            extra["unmapped"] = [s.source_name for s in stats.unmapped]
            lag_report = self._stream_counter.drain_lag()
            if lag_report is not None:
                self.last_lag_report = lag_report
                extra["producer_lag_level"] = lag_report.worst_level
        if stages := self.stage_timer.drain():
            extra["stages"] = stages
        # A stream that stopped stages nothing, so nothing sweeps the
        # staging pool for it: done here, it lets go of the last
        # window's device arrays and, a minute on, of its host buffers
        # (ADR 0130).
        from ..ops.staging_pool import POOL

        POOL.sweep()
        if self._pipeline is not None:
            extra["pipeline"] = self._pipeline.stats()
        # Device dispatch decomposition (ADR 0113/0114): publish/tick
        # executes+fetches and separate step dispatches since process
        # start. SNAPSHOT, not drain — the counters are process-wide and
        # the bench/tests drain them around their own measured loops; a
        # metrics tick must never zero a loop someone else is timing.
        try:
            from ..ops.publish import METRICS as publish_metrics

            extra["publish"] = publish_metrics.snapshot()
        except Exception:  # pragma: no cover - defensive
            logger.debug("publish metrics unavailable", exc_info=True)
        logger.info("processor_metrics", extra=extra)

    def finalize(self) -> None:
        """Publish final stopped statuses; idempotent (reference :417)."""
        if self._finalized:
            return
        self._finalized = True
        if self._pipeline is not None:
            # Drain first: every accepted window flushes through step and
            # publish before the stopped statuses go out — a service stop
            # must not drop or reorder in-flight batches.
            try:
                self._pipeline.stop(drain=True)
            except Exception:
                logger.exception("Ingest pipeline drain failed")
        try:
            self._publish_status(state="stopped")
        except Exception:
            logger.exception("Failed to publish final status")
        if self._durability is not None:
            # Final checkpoint on graceful stop (the pipeline just
            # drained): the restart resumes from HERE, replaying only
            # what arrived after the stop. Quiescence still gates it —
            # a batcher holding a partial window defers to the last
            # periodic generation, whose bookmark replays that window.
            self._maybe_checkpoint(force=True)
        self._job_manager.shutdown()
        # Drop this processor's scrape collector: the registry is
        # process-wide and a finalized processor must not keep feeding
        # stale families (or pin the whole object graph) forever.
        # Identity-guarded: if a rebuilt processor already REPLACED the
        # key, this late shutdown must not delete the successor's live
        # collector.
        from ..telemetry.registry import REGISTRY as _registry

        _registry.unregister_collector(
            self._telemetry_key, self._telemetry_families
        )

"""Job = one workflow instance bound to one source, plus wire-status models.

Parity with reference ``core/job.py``: Job:255 (add/process/get with time
coords stamped on outputs :209), JobState:95 phases, JobStatus:59,
ServiceStatus:193, stream-lag model :141-177 with WARN >= 2 s stale /
ERROR > 0.1 s future thresholds (:132-138).
"""

from __future__ import annotations

import time
import uuid
from collections.abc import Mapping
from enum import StrEnum
from typing import Any

import numpy as np
from pydantic import BaseModel, Field

from ..config.workflow_spec import JobId, JobSchedule, ResultKey, WorkflowId
from ..telemetry.health import HEALTH
from ..utils.labeled import DataArray, Variable
from ..workflows.workflow_factory import Workflow
from .timestamp import Duration, Timestamp

__all__ = [
    "Job",
    "JobResult",
    "JobState",
    "JobStatus",
    "ServiceStatus",
    "StreamLag",
    "StreamLagReport",
]

STALE_WARN_THRESHOLD = Duration.from_s(2.0)
FUTURE_ERROR_THRESHOLD = Duration.from_s(0.1)


class JobState(StrEnum):
    SCHEDULED = "scheduled"
    PENDING_CONTEXT = "pending_context"
    ACTIVE = "active"
    FINISHING = "finishing"
    WARNING = "warning"
    ERROR = "error"
    STOPPED = "stopped"


class JobStatus(BaseModel):
    """Per-job status as published in heartbeats (x5f2 status_json)."""

    source_name: str
    job_number: uuid.UUID
    workflow_id: str
    state: JobState
    message: str = ""
    has_primary_data: bool = False
    #: The start command's validated params — lets the dashboard offer
    #: "restart with edited params" with the real current values.
    params: dict = {}


class StreamLag(BaseModel):
    """Data-time vs wall-clock skew of one stream at batch close."""

    stream_name: str
    lag_s: float  # positive = stale, negative = from the future
    # Optional window aggregation (filled by kafka.stream_counter on the
    # 30 s metrics rollover; single-sample reports leave them at defaults).
    min_s: float | None = None
    max_s: float | None = None
    count: int = 1

    @property
    def level(self) -> str:
        future = self.min_s if self.min_s is not None else self.lag_s
        if future < -FUTURE_ERROR_THRESHOLD.seconds:
            return "error"
        if self.lag_s > STALE_WARN_THRESHOLD.seconds:
            return "warning"
        return "ok"


class StreamLagReport(BaseModel):
    lags: list[StreamLag] = Field(default_factory=list)

    @property
    def worst_level(self) -> str:
        levels = {lag.level for lag in self.lags}
        for level in ("error", "warning"):
            if level in levels:
                return level
        return "ok"


class ServiceStatus(BaseModel):
    """Service heartbeat payload (2 s cadence)."""

    service_name: str
    instrument: str
    state: str = "running"
    jobs: list[JobStatus] = Field(default_factory=list)
    last_batch_message_count: int = 0
    stream_message_counts: dict[str, int] = Field(default_factory=dict)
    uptime_s: float = 0.0
    #: Worst stream-lag level at the last batch ('ok'/'warning'/'error')
    #: and the worst data-time lag in seconds — the operator's first
    #: clue that a service is falling behind its streams.
    lag_level: str = "ok"
    worst_lag_s: float = 0.0
    #: Per-stream lag detail for the dashboard drill-down (reference
    #: workflow_status_widget surfaces per-source staleness): stream
    #: name -> (lag seconds, level).
    stream_lags: dict[str, tuple[float, str]] = Field(default_factory=dict)
    #: Transport-source health: 'ok' | 'stale' | 'stopped' ('stopped' =
    #: the consume thread's circuit breaker opened — reference
    #: system_status_widget surfaces consumer health per service).
    source_health: str = "ok"
    #: Source counters (queued/dropped batches, consumed messages).
    source_metrics: dict[str, int] = Field(default_factory=dict)


class JobResult:
    """Finalized outputs of one job for one window."""

    __slots__ = (
        "job_id",
        "workflow_id",
        "outputs",
        "start",
        "end",
        "state_epoch",
    )

    def __init__(
        self,
        *,
        job_id: JobId,
        workflow_id: WorkflowId,
        outputs: dict[str, DataArray],
        start: Timestamp | None,
        end: Timestamp | None,
        state_epoch: int = 0,
    ) -> None:
        self.job_id = job_id
        self.workflow_id = workflow_id
        self.outputs = outputs
        self.start = start
        self.end = end
        #: The producing job's state generation at finalize (see
        #: ``Job.state_epoch``) — the fan-out tier's epoch signal.
        self.state_epoch = state_epoch

    @property
    def source_ts_ns(self) -> int | None:
        """The source timestamp this result answers for (ADR 0120):
        the window-end data time — the ev44 reference time / payload
        timestamp of the newest message folded into these outputs.
        Every e2e latency boundary downstream of finalize (publish,
        fan-out encode, subscriber delivery) measures against it; None
        for windows that carried no data time (empty finishing-job
        flushes)."""
        return None if self.end is None else int(self.end.ns)

    def keys(self) -> list[ResultKey]:
        return [
            ResultKey(
                workflow_id=self.workflow_id,
                job_id=self.job_id,
                output_name=name,
            )
            for name in self.outputs
        ]


class Job:
    """Owns a workflow instance; maps window data in, stamped results out."""

    def __init__(
        self,
        *,
        job_id: JobId,
        workflow_id: WorkflowId,
        workflow: Workflow,
        schedule: JobSchedule | None = None,
        primary_streams: set[str] | None = None,
        aux_streams: set[str] | None = None,
        context_keys: set[str] | None = None,
        optional_context_keys: set[str] | None = None,
        reset_on_run_transition: bool = True,
        params: dict | None = None,
    ) -> None:
        self.job_id = job_id
        self.workflow_id = workflow_id
        #: None after release(): a stopped job keeps metadata only.
        self.workflow: Workflow | None = workflow
        self.params = dict(params or {})
        self.schedule = schedule or JobSchedule()
        self.primary_streams = primary_streams or {job_id.source_name}
        self.aux_streams = aux_streams or set()
        self.context_keys = context_keys or set()
        self.optional_context_keys = optional_context_keys or set()
        self.reset_on_run_transition = reset_on_run_transition
        # Generation start: data time of the first message accumulated since
        # job start or last reset. Stamped on outputs as ``start_time``, it
        # is constant for the lifetime of a generation and changes on reset/
        # reconfigure — NICOS uses the jump as a change-detector to tell a
        # post-reset zero from a genuine low reading (reference job.py:111,
        # ADR 0006).
        self._generation_start: Timestamp | None = None
        self._window_end: Timestamp | None = None
        self._start_wall = time.time()
        #: Output names whose last finalize returned None (warning surface).
        self.none_outputs: tuple[str, ...] = ()
        #: State-generation counter for downstream consumers (the result
        #: fan-out tier, ADR 0117): bumped whenever the accumulation
        #: restarts — clear()/reset and ``note_state_lost`` (a donated
        #: dispatch failure rebuilt the buffers mid-generation). A delta
        #: stream must never splice frames across a bump, so the serving
        #: plane folds this into its epoch token.
        self.state_epoch: int = 0

    @property
    def subscribed_streams(self) -> set[str]:
        return self.primary_streams | self.aux_streams

    def add(
        self,
        data: Mapping[str, Any],
        *,
        start: Timestamp | None = None,
        end: Timestamp | None = None,
        skip_accumulate: frozenset[str] | set[str] = frozenset(),
    ) -> bool:
        """Feed one window of stream-keyed data; returns True if any of it
        was for this job.

        ``skip_accumulate`` names streams whose values were already
        accumulated out-of-band by the JobManager's fused stepping layer:
        they still count as delivered data (window stamps, primary-data
        bookkeeping) but must not reach ``workflow.accumulate`` a second
        time."""
        if all(k in self.subscribed_streams for k in data):
            # Common case: the JobManager pre-filters per job — no copy.
            relevant: Mapping[str, Any] = data
        else:
            relevant = {
                k: v for k, v in data.items() if k in self.subscribed_streams
            }
        if not relevant:
            return False
        if self.workflow is None:
            raise RuntimeError(f"Job {self.job_id} is released (stopped)")
        if start is not None and self._generation_start is None:
            self._generation_start = start
        if end is not None:
            self._window_end = end
        if skip_accumulate:
            to_accumulate = {
                k: v for k, v in relevant.items() if k not in skip_accumulate
            }
            if to_accumulate:
                self.workflow.accumulate(to_accumulate)
        else:
            self.workflow.accumulate(relevant)
        return True

    def set_context(self, context: Mapping[str, Any]) -> None:
        deliverable = self.context_keys | self.optional_context_keys
        relevant = {k: v for k, v in context.items() if k in deliverable}
        if relevant and hasattr(self.workflow, "set_context"):
            self.workflow.set_context(relevant)

    def get(self) -> JobResult:
        """Finalize the window into a JobResult, stamping generation-start /
        window-end time coords on every output (reference job.py:209-245).

        Outputs that already carry ``start_time``/``end_time`` (a workflow
        stamping window-local coords on a per-update view) or a ``time``
        coord (timeseries data with its own timestamps) are left alone.
        """
        if self.workflow is None:
            raise RuntimeError(f"Job {self.job_id} is released (stopped)")
        raw = self.workflow.finalize()
        # None-valued outputs degrade to a per-job WARNING, publishing the
        # rest (reference: warning_from_none_values propagates to the job
        # status) — one absent output must not error the whole job.
        outputs = {k: v for k, v in raw.items() if v is not None}
        self.none_outputs = tuple(k for k, v in raw.items() if v is None)
        start, end = self._generation_start, self._window_end
        for da in outputs.values():
            if "time" in da.coords or "end_time" in da.coords:
                continue
            if start is not None:
                da.coords.setdefault(
                    "start_time",
                    Variable(np.asarray(start.ns, dtype=np.int64), (), "ns"),
                )
            if end is not None:
                da.coords["end_time"] = Variable(
                    np.asarray(end.ns, dtype=np.int64), (), "ns"
                )
        # Workflows may carry their own epoch contribution (duck-typed
        # ``publish_epoch``): a calibration swap (ADR 0122) keeps the
        # accumulation — no clear, no state loss — but downstream delta
        # streams must still resync on ONE keyframe at the handover.
        # Summing keeps both counters monotone and independent; the
        # serving tier only compares tokens for equality.
        wf_epoch = int(getattr(self.workflow, "publish_epoch", 0) or 0)
        return JobResult(
            job_id=self.job_id,
            workflow_id=self.workflow_id,
            outputs=outputs,
            start=start,
            end=end,
            state_epoch=self.state_epoch + wf_epoch,
        )

    def process(
        self,
        data: Mapping[str, Any],
        *,
        start: Timestamp | None = None,
        end: Timestamp | None = None,
    ) -> JobResult:
        self.add(data, start=start, end=end)
        return self.get()

    # graft: protocol=epoch (ADR 0124: the state_epoch bumps below must
    # reach every exit path — the modeled epoch-bump⇒keyframe guard)
    def clear(self) -> None:
        """Reset accumulation; starts a new generation (start_time jumps)."""
        if self.workflow is not None:
            self.workflow.clear()
        self._generation_start = None
        self._window_end = None
        self.state_epoch += 1

    def note_state_lost(self) -> None:
        """Record a mid-generation state rebuild (a donated dispatch
        failed after consuming the buffers and the JobManager reset the
        accumulator, ADR 0113/0114): downstream delta streams must
        keyframe — the next published frame does not continue the
        previous one. Also feeds the process health latch (ADR 0120):
        /healthz reports degraded for an interval after a loss, and
        ``livedata_state_lost_total`` counts the rate."""
        self.state_epoch += 1
        HEALTH.note_state_lost()

    @property
    def generation_start_ns(self) -> int | None:
        """The current generation's start time in ns (None before the
        first accumulated message) — checkpointed by the durability
        plane (ADR 0118) so a restored job stamps the same
        ``start_time`` coord an uninterrupted process would have."""
        start = self._generation_start
        return None if start is None else int(start.ns)

    def adopt_checkpoint(
        self,
        *,
        state_epoch: int,
        generation_start_ns: int | None,
    ) -> None:
        """Adopt a restored checkpoint's job-level metadata (ADR 0118):
        the generation start (so ``start_time`` continues rather than
        jumping, which NICOS reads as a reset — ADR 0006) and the
        ``state_epoch`` (so the serving tier's delta/epoch discipline
        continues the restored accumulation's lineage). Only called on
        schedule-time restore, BEFORE any data reaches the job; the
        mid-run ``state_lost`` recovery path must NOT adopt — its epoch
        already bumped past the checkpoint's."""
        self.state_epoch = int(state_epoch)
        self._generation_start = (
            None
            if generation_start_ns is None
            else Timestamp.from_ns(int(generation_start_ns))
        )

    def release(self) -> None:
        """Drop the workflow instance (and with it the device-resident
        accumulator state). Called when the job reaches STOPPED: the
        record stays visible for status/removal, but a stopped
        detector-view job must not pin hundreds of MB of HBM until an
        operator clicks remove — under clear-at-commit every recommit
        retires a predecessor, so leaked predecessors would accumulate
        per recommit."""
        self.workflow = None

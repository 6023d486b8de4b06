"""Job lifecycle and scheduling.

Parity with reference ``core/job_manager.py``: JobFactory.create:140 (eager
workflow build at schedule time — startup cost paid at the command, not in
the hot loop), phase machine scheduled -> pending_context -> active with a
finishing overlay (:223), data-time-driven activation (_advance_to_time:357),
context gating per ADR 0002 (_open_context_gates:599), run-transition resets
(:486-501), thread-pool fan-out of per-job work (:560,690) and per-job
error/warning containment instead of service death (:640-682).

TPU note on the fan-out: device kernels serialize on the chip anyway, so
threads only overlap the *host-side* staging/finalize portions — the
default thread count stays modest (reference default 5).
"""

from __future__ import annotations

import bisect
import logging
import threading
import time
import uuid
from collections.abc import Callable, Mapping
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from enum import StrEnum
from typing import Any, Literal

from pydantic import BaseModel, model_validator

from ..config.workflow_spec import JobId, WorkflowConfig
from ..preprocessors.event_data import StagedEvents
from ..telemetry.instruments import JOB_PUBLISHES, JOB_WINDOWS, TICK_GROUPS
from ..telemetry.trace import TRACER
from ..workflows.workflow_factory import WorkflowFactory, workflow_registry
from .device_event_cache import DeviceEventCache
from .job import Job, JobResult, JobState, JobStatus
from .message import RunStart, RunStop
from .state_snapshot import supports_snapshot
from .timestamp import Timestamp

__all__ = ["JobCommand", "JobFactory", "JobManager"]

logger = logging.getLogger(__name__)


class JobCommand(BaseModel):
    """stop/remove/reset command from the dashboard (reference :67).

    Selector forms (reference job_manager broadcast/by-workflow actions):

    - exact: ``source_name`` + ``job_number`` — one job;
    - by source: ``source_name`` alone — every job on that source;
    - by workflow: ``workflow_id`` (optionally + ``source_name``) —
      every job of that workflow;
    - broadcast: no selector — every job this service hosts.
    """

    action: Literal["stop", "remove", "reset"]
    source_name: str | None = None
    job_number: uuid.UUID | None = None
    workflow_id: str | None = None

    @model_validator(mode="after")
    def _job_number_needs_source(self):
        if self.job_number is not None and self.source_name is None:
            raise ValueError("job_number requires source_name")
        return self

    def matches(self, job_id: JobId, workflow_id) -> bool:
        if self.job_number is not None:
            return (
                job_id.source_name == self.source_name
                and job_id.job_number == self.job_number
            )
        if self.source_name is not None and job_id.source_name != self.source_name:
            return False
        if self.workflow_id is not None and str(workflow_id) != self.workflow_id:
            return False
        return True


class JobFactory:
    """Builds Jobs from start commands via the workflow registry."""

    def __init__(self, registry: WorkflowFactory | None = None) -> None:
        self._registry = registry if registry is not None else workflow_registry

    def create(self, config: WorkflowConfig) -> Job:
        spec = self._registry[config.identifier]
        workflow = self._registry.create(config)
        aux = set(config.aux_source_names.values())
        return Job(
            job_id=config.job_id,
            workflow_id=config.identifier,
            workflow=workflow,
            schedule=config.schedule,
            primary_streams={config.job_id.source_name},
            aux_streams=aux,
            context_keys=set(spec.context_keys),
            optional_context_keys=set(spec.optional_context_keys),
            reset_on_run_transition=spec.reset_on_run_transition,
            params=dict(config.params),
        )


class _Phase(StrEnum):
    SCHEDULED = "scheduled"
    PENDING_CONTEXT = "pending_context"
    ACTIVE = "active"
    STOPPED = "stopped"


@dataclass
class _JobRecord:
    job: Job
    phase: _Phase = _Phase.SCHEDULED
    finishing: bool = False
    error: str = ""
    warning: str = ""
    has_primary_data: bool = False
    # A run-transition reset whose workflow.clear() failed; retried before
    # the job may accumulate again, so data from the old and new run can
    # never mix in a wedged workflow.
    needs_reset: bool = False
    # Context streams whose latest cached value this job has not received
    # yet. Persisted across windows so an update arriving while the job is
    # idle (no data, nothing pending) is delivered before its next add —
    # a fresh value is queued once and stays queued until a successful
    # set_context.
    stale_context: set[str] = field(default_factory=set)

    @property
    def state(self) -> JobState:
        if self.error:
            return JobState.ERROR
        if self.phase == _Phase.STOPPED:
            return JobState.STOPPED
        if self.finishing:
            return JobState.FINISHING
        if self.phase == _Phase.PENDING_CONTEXT:
            # More informative than WARNING; the missing-context warning
            # still rides the status message field.
            return JobState.PENDING_CONTEXT
        if self.warning:
            return JobState.WARNING
        return JobState(self.phase.value)


class JobManager:
    """Keeps the job table; drives activation, gating, processing, resets."""

    def __init__(
        self,
        *,
        job_factory: JobFactory | None = None,
        job_threads: int = 5,
        snapshot_store=None,
        combine_publish: bool = True,
        tick_program: bool = True,
        placement=None,
        durability=None,
    ) -> None:
        self._factory = job_factory or JobFactory()
        #: Cross-job publish combiner (ADR 0113): every job due in a
        #: publish tick is served from ONE device execute + ONE packed
        #: fetch per device. ``combine_publish=False`` keeps the per-job
        #: path (the parity tests' reference).
        from ..ops.publish import PublishCombiner
        from ..ops.tick import TickCombiner

        self._publish_combiner = (
            PublishCombiner() if combine_publish else None
        )
        #: Whole-tick program (ADR 0114): a (stream, fuse-key) group
        #: whose every member is due a publish steps AND publishes in
        #: ONE jitted dispatch + ONE fetch, replacing the worst-case
        #: stage/step/publish triple. ``tick_program=False`` keeps the
        #: separate fused-step + combined-publish path (the parity
        #: tests' reference); it requires combining — without the
        #: combiner's offer plumbing there is nothing to fuse into.
        self._tick_combiner = (
            TickCombiner() if (combine_publish and tick_program) else None
        )
        #: Mesh-slice placement policy (parallel/mesh_tick.py,
        #: ADR 0115): assigns every (stream, fuse-key) tick/fused group
        #: a sticky mesh slice — a single device round-robin for
        #: single-device histogrammers, the whole mesh for bank-sharded
        #: ones. Staging keys carry the slice (one transfer per slice),
        #: member states are committed to it once at assignment, and
        #: mesh groups run through the slice's MeshTickCombiner.
        #: None = classic single-placement behavior, byte-identical.
        self._placement = placement
        #: Job-retirement observer (``set_retire_observer``): called
        #: with each removed JobId so downstream caches — the result
        #: fan-out tier's ResultCache (ADR 0117) — drop the job's
        #: streams instead of serving stale keyframes forever.
        self._retire_observer = None
        #: Optional core.state_snapshot.SnapshotStore: device-resident
        #: accumulation is dumped at run boundaries + shutdown and
        #: restored when an identically-configured job is scheduled
        #: (SURVEY §5 checkpoint note).
        self._snapshot_store = snapshot_store
        #: Optional durability plane (durability/checkpoint.py,
        #: ADR 0118): the periodic checkpoint channel. Consulted FIRST
        #: at schedule-time restore (fresher than the shutdown-only
        #: store), re-seeds fresh states at the state-loss containment
        #: sites, and receives the run-boundary reset sequence so stale
        #: manifests can never resurrect old-run data.
        self._durability = durability
        #: Run-boundary reset sequence — persisted by the durability
        #: plane as the manifest staleness gate. Seeded from the
        #: plane's persisted marker: a process that restarts AFTER a
        #: reset must stamp new manifests at (or past) the marker, or
        #: every post-restart checkpoint would be rejected as stale
        #: forever (pinned in tests/durability).
        self._reset_seq = self._seed_reset_seq(durability)
        #: Optional AOT warm-up service (durability/warmup.py): job
        #: commits and removals plan the next tick's program
        #: keys and compile them off the hot path before the change
        #: goes live.
        self._warmup = None
        #: Fault-injection schedule (harness/chaos.py, ADR 0120);
        #: None in production.
        self._chaos = None
        #: Fleet assignment (fleet/assignment.py, ADR 0121): when set,
        #: each window processes only the (stream, fuse-key) groups
        #: this replica owns; None = single-replica (everything local).
        self._fleet = None
        #: Last seen padded batch size per stream — the staged-signature
        #: memory warm-up plans against (a tick program's key includes
        #: the staged wire's shape, and commit-time warm-up must
        #: compile against the shape the stream actually carries).
        self._stream_batch_shapes: dict[str, int] = {}
        self._records: dict[JobId, _JobRecord] = {}
        #: Stage-once staging per stream (ADR 0110): every window's event
        #: batches decode/flatten/transfer ONCE per (stream, layout) no
        #: matter how many jobs subscribe; slots are attached to the
        #: window's StagedEvents values in process_jobs.
        self._event_cache = DeviceEventCache()
        self._lock = threading.RLock()
        # Reset times scheduled by run transitions, sorted; each fires when
        # DATA time reaches it (reference :486-501) — never on arrival
        # order, so a run-start announced ahead of the data stream resets
        # exactly at the boundary even if messages straddle it.
        self._pending_reset_times: list[Timestamp] = []
        self._executor = (
            ThreadPoolExecutor(max_workers=job_threads, thread_name_prefix="job")
            if job_threads > 1
            else None
        )

    # -- scheduling --------------------------------------------------------
    def schedule_job(self, config: WorkflowConfig) -> JobId:
        """Create + register a job. The workflow builds eagerly here so
        compile/LUT cost lands at command time, not in the data path."""
        with self._lock:
            if config.job_id in self._records:
                raise ValueError(f"Job {config.job_id} already exists")
            job = self._factory.create(config)
            self._records[config.job_id] = _JobRecord(job=job)
            logger.info("Scheduled job %s (%s)", config.job_id, config.identifier)
            # Consumer-set change: flush staged slots (ADR 0110). Entries
            # are window-scoped anyway; this keeps the rule explicit.
            self._event_cache.invalidate()
            self._maybe_restore(job)
        # Outside the lock: warm-up planning calls workflow offer code.
        # The commit re-keys every tick group the new job joins (member
        # tuple change), so the programs its FIRST live window needs
        # compile on the warm-up thread now instead of stalling that
        # window (ADR 0118).
        self._queue_warmup("commit")
        return config.job_id

    def _maybe_restore(self, job: Job) -> None:
        """Adopt a prior process's accumulation for this configuration.

        The durability plane's periodic checkpoint (ADR 0118) is
        consulted first — it is at most one checkpoint interval stale,
        against the shutdown-only store's crash-loses-everything — and
        carries job-level meta (state_epoch, generation start) the old
        channel never had. The ADR 0107 store stays as the fallback so
        a deployment with only LIVEDATA_SNAPSHOT_DIR keeps its exact
        pre-durability behavior.
        """
        if self._durability is not None:
            try:
                if self._durability.restore_job(job):
                    return
            except Exception:
                logger.exception(
                    "checkpoint restore failed for %s; trying the "
                    "snapshot store",
                    job.job_id,
                )
        store, wf = self._snapshot_store, job.workflow
        if store is None or not supports_snapshot(wf):
            return
        try:
            # Non-consuming load: a workflow that refuses the arrays
            # (device state not built yet) keeps the file for a later
            # schedule instead of losing it.
            arrays = store.load(
                workflow_id=str(job.workflow_id),
                source_name=job.job_id.source_name,
                fingerprint=wf.state_fingerprint(),
                consume=False,
            )
            if arrays is not None and wf.restore_state(arrays):
                store.discard(
                    workflow_id=str(job.workflow_id),
                    source_name=job.job_id.source_name,
                )
                logger.info(
                    "Restored snapshot state for %s/%s",
                    job.workflow_id,
                    job.job_id.source_name,
                )
        except Exception:
            logger.exception(
                "Snapshot restore failed for %s; starting fresh", job.job_id
            )

    def _dump_snapshot(
        self, rec: _JobRecord, reason: str, archive: bool = False
    ) -> None:
        store, wf = self._snapshot_store, rec.job.workflow
        if store is None or not supports_snapshot(wf):
            return
        try:
            arrays = wf.dump_state()
            if not arrays:
                # Nothing accumulated yet (context-gated workflow before
                # its first table): don't overwrite a prior snapshot.
                return
            store.save(
                workflow_id=str(rec.job.workflow_id),
                source_name=rec.job.job_id.source_name,
                fingerprint=wf.state_fingerprint(),
                arrays=arrays,
                reason=reason,
                archive=archive,
            )
        except Exception:
            logger.exception("Snapshot dump failed for %s", rec.job.job_id)

    def dump_snapshots(self, reason: str = "shutdown") -> None:
        # Every non-stopped job, INCLUDING still-scheduled ones: a job
        # that restored a snapshot but never activated holds that
        # accumulation only in its workflow — skipping it here would
        # destroy it (the restore consumed the file).
        with self._lock:
            for rec in self._records.values():
                if rec.phase != _Phase.STOPPED:
                    self._dump_snapshot(rec, reason)

    # -- durability plane (durability/, ADR 0118) --------------------------
    @staticmethod
    def _seed_reset_seq(plane) -> int:
        """The persisted reset marker (0 without a plane/marker)."""
        marker = getattr(plane, "reset_marker", None)
        if marker is None:
            return 0
        try:
            return int(marker())
        except Exception:
            logger.exception("reset-marker read failed; seeding 0")
            return 0

    def set_durability(self, plane) -> None:
        """Attach the periodic checkpoint plane (duck-typed:
        ``restore_job``/``note_reset``/``reset_marker``)
        post-construction; the reset sequence re-seeds from the
        plane's persisted marker (never backward)."""
        self._durability = plane
        with self._lock:
            self._reset_seq = max(
                self._reset_seq, self._seed_reset_seq(plane)
            )

    def set_warmup(self, service) -> None:
        """Attach the AOT warm-up service (durability/warmup.py):
        commits and removals submit tick-program warm-up
        requests through it."""
        self._warmup = service

    def set_chaos(self, chaos) -> None:
        """Install a fault-injection schedule (harness/chaos.py,
        ADR 0120). Two sites: ``slow_tick`` delays a window before any
        lock is taken (a slow-tick storm, the watchdog's prey), and
        ``tick_dispatch`` raises AFTER a tick program's dispatch ran —
        the post-donation failure mode, exercising the exact
        ``note_state_lost`` containment the live failure would. None
        (production) costs one attribute check per window."""
        self._chaos = chaos

    def set_fleet(self, assignment) -> None:
        """Partition this manager across a replica fleet (duck-typed:
        ``owns(stream, fuse_tag)`` — fleet/assignment.py, ADR 0121).
        Each window then processes only the (stream, fuse-key) groups
        rendezvous-hashed to THIS replica: fresh data for unowned
        groups is dropped (a peer replica is accumulating it), while
        state already accumulated here still flushes — so a rebalance
        drains cleanly and the new owner's checkpoint-restore + replay
        (ADR 0118) carries the group forward as a gap, not a reset."""
        with self._lock:
            self._fleet = assignment

    @property
    def reset_seq(self) -> int:
        """Run-boundary resets fired since construction — rides every
        checkpoint manifest as its staleness tag."""
        with self._lock:
            return self._reset_seq

    def checkpoint_snapshot(self) -> list[dict]:
        """Per-job state-dump entries for the CheckpointPlane: every
        non-stopped snapshot-capable job's host arrays plus the meta a
        restart needs (fingerprint gate, state_epoch, generation
        start). The record list is captured under the lock; the
        device→host fetches run outside it with per-job containment —
        the plane's caller (the processor) only checkpoints at
        quiescent window boundaries, so nothing steps these states
        concurrently, and a job that still fails to dump is skipped
        this generation rather than wedging the checkpoint."""
        with self._lock:
            records = [
                rec
                for rec in self._records.values()
                if rec.phase != _Phase.STOPPED
            ]
        entries: list[dict] = []
        for rec in records:
            wf = rec.job.workflow
            if wf is None or not supports_snapshot(wf):
                continue
            try:
                arrays = wf.dump_state()
                if not arrays:
                    # Nothing accumulated yet (context-gated workflow
                    # before its first table): no entry beats an empty
                    # state resurrecting over a later restore.
                    continue
                entries.append(
                    {
                        "workflow_id": str(rec.job.workflow_id),
                        "source_name": rec.job.job_id.source_name,
                        "job_number": str(rec.job.job_id.job_number),
                        "fingerprint": wf.state_fingerprint(),
                        "state_epoch": rec.job.state_epoch,
                        "generation_start_ns": rec.job.generation_start_ns,
                        "arrays": arrays,
                    }
                )
            except Exception:
                logger.exception(
                    "checkpoint dump failed for %s; skipped this "
                    "generation",
                    rec.job.job_id,
                )
        return entries

    def _after_state_loss(self, rec: _JobRecord) -> None:
        """Durability hook at every ``note_state_lost`` containment
        site (ADR 0118): the fresh zeroed state just installed is
        re-seeded from the newest checkpoint, so a donated-dispatch
        failure costs the gap since the last checkpoint instead of the
        whole accumulated run. ``adopt_meta=False`` — the epoch already
        bumped, and regressing it would let a delta stream splice
        across the rebuild (the next publish must keyframe)."""
        plane = self._durability
        if plane is None:
            return
        try:
            if plane.restore_job(
                rec.job, adopt_meta=False, reason="state_lost"
            ):
                rec.warning += "; re-seeded from last checkpoint"
        except Exception:
            logger.exception(
                "state-loss checkpoint restore failed for %s",
                rec.job.job_id,
            )

    def request_warmup(self, trigger: str) -> None:
        """Plan + submit tick-program warm-up for the current job set
        (ADR 0118). Called internally on commits/removals; public so
        layout-swap appliers can pre-compile before a change goes
        live."""
        self._queue_warmup(trigger)

    def _queue_warmup(self, trigger: str) -> None:
        warmup = self._warmup
        if warmup is None or self._tick_combiner is None:
            return
        try:
            requests = self.plan_warmup(trigger)
        except Exception:
            logger.exception("warm-up planning failed (%s)", trigger)
            return
        if requests:
            warmup.submit(requests)

    def plan_warmup(self, trigger: str = "commit") -> list:
        """Plan one WarmupRequest per tick-eligible (stream, fuse-key)
        group, against the batch shape each stream has actually been
        carrying (``_stream_batch_shapes``) — the staged signature in a
        tick program's key. Mirrors the live planners: the record
        predicate is ``prestage_window``'s (active, or scheduled with
        no gate — those activate on their first window), grouping is
        ``_plan_fused_steps``'s (event_ingest offers keyed by (stream,
        offer key)), and eligibility is ``_split_tick_groups``'s
        (publish offer present, args[0] IS the ingest state). Offers
        are side-effect free by contract, and member args travel as
        ``jax.ShapeDtypeStruct`` trees — planning never touches (or
        pins) a live device buffer. Streams with no remembered shape
        (nothing consumed yet) are skipped: there is no signature to
        warm against, and their first window compiles as a startup
        ``new_group`` exactly as before.
        """
        import jax as _jax
        import numpy as np

        from ..durability.warmup import WarmupRequest
        from ..ops.event_batch import EventBatch

        with self._lock:
            if self._tick_combiner is None:
                return []
            records = [
                rec
                for rec in self._records.values()
                if not rec.needs_reset
                and (
                    rec.phase == _Phase.ACTIVE
                    or (
                        rec.phase == _Phase.SCHEDULED
                        and rec.job.schedule.start is None
                        and not rec.job.context_keys
                    )
                )
            ]
            shapes = dict(self._stream_batch_shapes)
        groups: dict[tuple, list] = {}
        for stream, padded in shapes.items():
            value = StagedEvents(
                batch=EventBatch(
                    pixel_id=np.full(padded, -1, dtype=np.int32),
                    toa=np.zeros(padded, dtype=np.float32),
                    n_valid=0,
                ),
                first_timestamp=None,
                last_timestamp=None,
                n_chunks=1,
            )
            for rec in records:
                if stream not in rec.job.subscribed_streams:
                    continue
                ingest_fn = getattr(rec.job.workflow, "event_ingest", None)
                if ingest_fn is None:
                    continue
                try:
                    offer = ingest_fn(stream, value)
                except Exception:
                    logger.exception(
                        "event_ingest failed during warm-up planning "
                        "for %s",
                        rec.job.job_id,
                    )
                    continue
                if offer is not None:
                    groups.setdefault((stream, offer.key), []).append(
                        (rec, offer)
                    )
        requests = []
        for (stream, key), members in groups.items():
            ingest0 = members[0][1]
            device = combiner = None
            if self._placement is not None:
                # Sticky-assignment PROBE only: state moves stay on the
                # step thread (``_group_placement``'s ensure_state_on),
                # exactly like the prestage path's probe.
                try:
                    plc = self._placement.assign(stream, key, ingest0.hist)
                    device, combiner = plc.device, plc.combiner
                except Exception:
                    logger.debug(
                        "warm-up placement probe failed", exc_info=True
                    )
            member_specs = []
            for rec, ingest in members:
                offer_fn = getattr(rec.job.workflow, "publish_offer", None)
                if offer_fn is None:
                    member_specs = None
                    break
                try:
                    offer = offer_fn()
                    if (
                        offer is None
                        or not offer.args
                        or offer.args[0] is not ingest.get_state()
                    ):
                        member_specs = None
                        break
                    sharding = (
                        None
                        if device is None
                        else _jax.sharding.SingleDeviceSharding(device)
                    )
                    args = _jax.tree_util.tree_map(
                        lambda a: _jax.ShapeDtypeStruct(
                            tuple(a.shape),
                            a.dtype,
                            **(
                                {}
                                if sharding is None
                                else {"sharding": sharding}
                            ),
                        ),
                        offer.args,
                    )
                except Exception:
                    logger.debug(
                        "warm-up offer capture failed for %s",
                        rec.job.job_id,
                        exc_info=True,
                    )
                    member_specs = None
                    break
                member_specs.append(
                    (offer.publisher, args, offer.static_token)
                )
            if not member_specs:
                # Not tick-eligible: this group dispatches separately on
                # the live path, where the fused-step/publish jits have
                # their own (per-K) caches — nothing to warm here.
                continue
            requests.append(
                WarmupRequest(
                    combiner=(
                        combiner
                        if combiner is not None
                        else self._tick_combiner
                    ),
                    hist=ingest0.hist,
                    group_key=key,
                    batch=ingest0.batch,
                    batch_tag=ingest0.batch_tag,
                    device=device,
                    members=member_specs,
                    trigger=trigger,
                )
            )
        return requests

    def handle_command(self, command: JobCommand) -> int:
        """Apply ``command``; return how many jobs it acted on.

        Zero for an unknown job is routine, not exceptional: every service
        sees the shared commands topic but owns a disjoint job set, and a
        non-owner must stay silent (the dispatcher acks only on count > 0).
        """
        removed: list[JobId] = []
        with self._lock:
            matched = [
                (jid, rec)
                for jid, rec in self._records.items()
                if command.matches(jid, rec.job.workflow_id)
            ]
            for jid, rec in matched:
                if command.action == "stop":
                    # Graceful: the job processes one more window and
                    # flushes a final result before leaving the active set.
                    rec.finishing = True
                elif command.action == "remove":
                    rec.phase = _Phase.STOPPED
                    del self._records[jid]
                    # Consumer detach: flush staged slots (ADR 0110).
                    self._event_cache.invalidate()
                    removed.append(jid)
                elif command.action == "reset":
                    self._reset_record(rec)
        # Outside the lock: observers reach foreign subsystems (the
        # fan-out tier's own hub lock) — never from inside ours.
        observer = self._retire_observer
        if observer is not None:
            for jid in removed:
                try:
                    observer(jid)
                except Exception:
                    logger.exception("retire observer failed for %s", jid)
        if removed:
            # A removal re-keys every group the job belonged to (member
            # tuple shrinks): warm the survivors' programs off the hot
            # path (ADR 0118).
            self._queue_warmup("regroup")
        return len(matched)

    def set_retire_observer(self, observer) -> None:
        """Attach a ``fn(job_id)`` called after each job removal — the
        serving plane drops the job's cached streams through this
        (ADR 0117)."""
        self._retire_observer = observer

    # -- run transitions ---------------------------------------------------
    def handle_run_transition(self, event: RunStart | RunStop) -> None:
        """Schedule deferred resets at the run boundary's data time."""
        with self._lock:
            if isinstance(event, RunStart):
                bisect.insort(self._pending_reset_times, event.start_time)
                if event.stop_time is not None:
                    bisect.insort(self._pending_reset_times, event.stop_time)
                logger.info(
                    "Run start %r: reset scheduled at %s",
                    event.run_name,
                    event.start_time,
                )
            else:
                bisect.insort(self._pending_reset_times, event.stop_time)
                logger.info(
                    "Run stop %r: reset scheduled at %s",
                    event.run_name,
                    event.stop_time,
                )

    def _fire_pending_resets(self, data_time: Timestamp) -> None:
        """Fire every scheduled reset that data time has now reached."""
        due = bisect.bisect_right(self._pending_reset_times, data_time)
        if not due:
            return
        del self._pending_reset_times[:due]
        if any(
            rec.job.reset_on_run_transition
            for rec in self._records.values()
        ):
            # Run-boundary staleness gate (ADR 0118): once any job's
            # accumulation resets at this boundary, every checkpoint
            # written before it must never restore — the marker is
            # persisted BEFORE the resets run, so a crash anywhere
            # after this line cannot resurrect old-run state.
            # graftlint: disable=JGL004 caller (process_jobs) holds self._lock
            self._reset_seq += 1
            if self._durability is not None:
                try:
                    self._durability.note_reset(self._reset_seq)
                except Exception:
                    logger.exception("reset-marker persist failed")
        for rec in self._records.values():
            if rec.job.reset_on_run_transition:
                # The run's final accumulation, captured before the reset
                # wipes it (SURVEY §5: snapshot at run boundaries). Goes
                # to the ARCHIVE key — restore never reads it, so a
                # finished run can't be resurrected into a later job.
                if rec.phase in (_Phase.ACTIVE, _Phase.PENDING_CONTEXT):
                    self._dump_snapshot(
                        rec, reason="run_boundary", archive=True
                    )
                self._reset_record(rec)

    def _reset_record(self, rec: _JobRecord) -> None:
        """Clear accumulation and retry/error state; phase is unchanged
        (context is sticky across run boundaries, so a gated job stays
        gated). A workflow whose clear() raises keeps its error recorded
        and does not take the other jobs' resets down with it; the record
        is flagged ``needs_reset`` and excluded from processing until a
        retry succeeds, so old-run and new-run data cannot mix."""
        try:
            rec.job.clear()
        except Exception as err:
            rec.needs_reset = True
            rec.error = f"Reset failed: {type(err).__name__}: {err}"
            logger.exception("Job %s failed clearing on reset", rec.job.job_id)
            return
        rec.needs_reset = False
        rec.has_primary_data = False
        rec.error = ""
        rec.warning = ""

    # -- phase machine -----------------------------------------------------
    def _advance_to_time(self, data_time: Timestamp) -> None:
        for rec in self._records.values():
            job = rec.job
            if rec.phase == _Phase.SCHEDULED:
                start = job.schedule.start
                if start is None or data_time >= start:
                    rec.phase = (
                        _Phase.PENDING_CONTEXT
                        if job.context_keys
                        else _Phase.ACTIVE
                    )
            if rec.phase in (_Phase.ACTIVE, _Phase.PENDING_CONTEXT):
                # A job still gated on context can also reach its end time
                # and must finish (reference :375-377).
                end = job.schedule.end
                if end is not None and data_time >= end:
                    rec.finishing = True

    def _open_context_gates(
        self, context: Mapping[str, Any]
    ) -> set[JobId]:
        """pending_context -> active once every needed context stream has a
        value (ADR 0002); still-gated jobs carry a warning naming what is
        missing, so the dashboard shows why nothing is produced.

        Returns the ids of jobs that graduated in this pass — they received
        the full cached context here and must not get a second (partial)
        delivery from the processing fan-out.
        """
        graduated: set[JobId] = set()
        for job_id, rec in self._records.items():
            if rec.phase != _Phase.PENDING_CONTEXT:
                continue
            missing = {k for k in rec.job.context_keys if k not in context}
            if missing:
                rec.warning = (
                    "Waiting for context streams: "
                    + ", ".join(sorted(missing))
                )
            else:
                # Contained per job: one workflow rejecting its context
                # must not abort the batch for every other job.
                try:
                    rec.job.set_context(context)
                except Exception as err:
                    rec.warning = (
                        f"Applying context failed: {type(err).__name__}: {err}"
                    )
                    logger.exception(
                        "Job %s failed applying gate context", job_id
                    )
                    continue
                rec.phase = _Phase.ACTIVE
                rec.warning = ""
                rec.stale_context.clear()
                graduated.add(job_id)
        return graduated

    # -- publish combining (ADR 0113) --------------------------------------
    def _run_combined_publish(self, due: list[_JobRecord]) -> None:
        """Serve every due job's publish from one execute + one packed
        fetch per device (ADR 0113).

        Jobs whose workflows offer ``publish_offer`` are grouped by the
        device their state lives on; each group runs through the
        :class:`~..ops.publish.PublishCombiner` and the unpacked per-job
        trees are handed back via ``offer.consume`` — the subsequent
        ``job.get()`` then consumes the prefetched outputs instead of
        dispatching privately. Singletons ride the combiner too: in the
        manager-driven flow the workflow's private publish jit never
        compiles, so a K=1 program is the only compile either way.

        Containment mirrors the fused stepping layer: a member whose
        unpack failed still adopts its (valid) folded carry and
        republishes privately; a dispatch failure that consumed the
        donated buffers resets that member's state with a visible
        warning; everyone else is unaffected."""
        if self._publish_combiner is None:
            return
        from ..ops.publish import (
            PublishRequest,
            publish_args_consumed,
            publish_device,
        )

        offers = []
        for rec in due:
            offer_fn = getattr(rec.job.workflow, "publish_offer", None)
            if offer_fn is None:
                continue
            try:
                offer = offer_fn()
            except Exception:
                logger.exception(
                    "publish_offer failed for %s", rec.job.job_id
                )
                continue
            if offer is not None:
                offers.append((rec, offer))
        groups: dict[Any, list] = {}
        for rec, offer in offers:
            groups.setdefault(publish_device(offer.args), []).append(
                (rec, offer)
            )
        for members in groups.values():
            requests = [
                PublishRequest(o.publisher, o.args, o.static_token)
                for _, o in members
            ]
            try:
                results = self._publish_combiner.publish(requests)
            except Exception:
                # The combiner contains plan/dispatch/unpack failures
                # per member; anything escaping is a combiner bug — it
                # must degrade this group to private publishes, never
                # take the window (or the pipeline's step worker) down.
                logger.exception(
                    "combined publish failed (%d jobs); falling back to "
                    "per-job publishes",
                    len(members),
                )
                for rec, offer in members:
                    if publish_args_consumed(offer.args):
                        if offer.reset is not None:
                            offer.reset()
                        rec.job.note_state_lost()
                        rec.warning = (
                            "combined publish failed after buffer "
                            "donation; accumulation reset (see service "
                            "log)"
                        )
                        self._after_state_loss(rec)
                continue
            for (rec, offer), res in zip(members, results, strict=True):
                if res.error is not None:
                    if res.state_lost:
                        # Donation already invalidated the buffers: the
                        # pre-publish accumulation is unrecoverable in
                        # place. Rebuild a fresh state and surface the
                        # loss instead of erroring on a deleted array
                        # every publish from here on.
                        if offer.reset is not None:
                            offer.reset()
                        rec.job.note_state_lost()
                        rec.warning = (
                            "combined publish failed after buffer "
                            "donation; accumulation reset (see service "
                            "log)"
                        )
                        self._after_state_loss(rec)
                    elif res.carry:
                        # The fold already ran on device: adopt the new
                        # state so the job keeps a live buffer, and let
                        # finalize republish privately (this tick's
                        # window summaries read zero; the cumulative is
                        # intact).
                        try:
                            offer.consume(None, res.carry)
                        except Exception:
                            logger.exception(
                                "publish carry adoption failed for %s",
                                rec.job.job_id,
                            )
                    continue
                try:
                    offer.consume(res.outputs, res.carry)
                except Exception:
                    logger.exception(
                        "publish consume failed for %s", rec.job.job_id
                    )

    # -- one-dispatch tick programs (ops/tick.py, ADR 0114) ----------------
    def _split_tick_groups(
        self, work: list[tuple[_JobRecord, dict[str, Any]]], fuse_groups
    ) -> tuple[dict[tuple, list], list[tuple[tuple, Any, list]]]:
        """Partition the fused-step groups into tick-program groups —
        stepped AND published in one dispatch — and plain fused groups.

        A group rides the tick fast path only when EVERY member can:
        the member's window data is exactly the fused stream (any other
        stream would accumulate into the state AFTER the tick published
        it), the stream is primary (so the publish bookkeeping marks the
        record due and finalize consumes the prefetched tree — an
        aux-only window must never leave a stale prefetch behind), and
        the workflow's ``publish_offer`` names the SAME state object the
        ingest offer steps (the ``make_publish_offer`` args[0]/carry
        contract — verified by identity, so a bespoke offer that breaks
        it degrades to the separate-dispatch path instead of publishing
        the wrong buffers). Mixed groups stay whole on the fused path —
        splitting one would pay two dispatches for one group.

        Context ordering is inherited, not re-checked: ``fuse_groups``
        comes from ``_plan_fused_steps``, which already excludes any
        record with queued context (``rec.stale_context``) — so a
        window that carries a fresh geometry/position update never
        ticks, and the set_context-before-accumulate-before-publish
        contract holds on this path exactly as on the private one
        (pinned in tick_program_test.py).

        Unlike fused stepping, singleton groups DO tick: K=1 still
        collapses step + publish from two dispatches to one.
        """
        if self._tick_combiner is None:
            return fuse_groups, []
        data_keys = {id(rec): frozenset(jd) for rec, jd in work}
        rest: dict[tuple, list] = {}
        ticks: list[tuple[tuple, Any, list]] = []
        for group_key, members in fuse_groups.items():
            # Slice assignment happens BEFORE offers are collected: a
            # member whose state must move to its slice gets the moved
            # state captured in offer.args[0], keeping the identity
            # check below (and the tick program's donation layout)
            # honest. Assignment is sticky, so this is a metadata probe
            # on every tick after a group's first.
            plc = self._group_placement(group_key, members)
            enriched: list | None = []
            for rec, stream, value, ingest in members:
                if (
                    data_keys.get(id(rec)) != frozenset((stream,))
                    or stream not in rec.job.primary_streams
                ):
                    enriched = None
                    break
                offer_fn = getattr(rec.job.workflow, "publish_offer", None)
                if offer_fn is None:
                    enriched = None
                    break
                try:
                    offer = offer_fn()
                except Exception:
                    logger.exception(
                        "publish_offer failed for %s", rec.job.job_id
                    )
                    enriched = None
                    break
                if (
                    offer is None
                    or not offer.args
                    or offer.args[0] is not ingest.get_state()
                ):
                    enriched = None
                    break
                enriched.append((rec, stream, value, ingest, offer))
            if enriched:
                ticks.append((group_key, plc, enriched))
            else:
                rest[group_key] = members
        return rest, ticks

    def _group_placement(self, group_key: tuple, members: list):
        """The (sticky) mesh slice for one (stream, fuse-key) group —
        None without a placement policy. Member states are committed to
        a single-device slice here, before state identity is captured
        anywhere (publish offers, fused-step tuples); a move failure
        degrades the group to its current placement rather than taking
        the window down."""
        if self._placement is None:
            return None
        stream, key = group_key
        ingest0 = members[0][3]
        try:
            plc = self._placement.assign(stream, key, ingest0.hist)
            if plc.device is not None:
                for _rec, _strm, _value, ingest in members:
                    self._placement.ensure_state_on(ingest, plc.device)
            return plc
        except Exception:
            logger.exception(
                "slice placement failed for group %r", group_key
            )
            return None

    def _run_tick_programs(
        self,
        tick_groups: list[tuple[tuple, Any, list]],
        hand_over: Callable[[list[tuple[_JobRecord, dict[str, Any]]]], None]
        | None = None,
    ) -> tuple[set[int], dict[JobId, set[str]]]:
        """Execute every ((stream, key), slice, members) tick group as
        ONE device dispatch + ONE fetch, the groups of a tick
        software-pipelined: for each group stage, then dispatch; then,
        in the same order, collect each and do its members' bookkeeping.
        The dispatch is asynchronous, so the host flattens and ships
        group i+1 while the chip runs group i, and a group's copy back
        runs under the next group's program. The per-group contract
        (ADR 0114) and every result are unchanged; only the host's
        position in time moves.

        In that second pass a group's served members are handed to
        ``hand_over``, each with the window data its program stepped,
        right after its collect, as long as a later group is still
        uncollected:
        ``process_jobs`` finalizes and publishes them there, beside the
        chip's work on the groups behind (ADR 0128). The last group of
        a tick, and whatever a drain inside the dispatch pass collects,
        is handed to nobody and leaves at the end of the window.

        Three cases stay serial, by what can be observed here and by
        no switch. A group whose program misses the program LRU ran
        its compile round to the end inside ``dispatch``: it, and
        whatever is pending, is collected on the spot, so nothing is
        dispatched ahead across a compile. A tick with one group has
        nothing to run ahead of. A record that appears in a second
        group of the tick (none today: ``_split_tick_groups`` admits
        only single-stream members) waits for the first's collect,
        which adopts the state its next dispatch donates.
        ``livedata_tick_groups_total{dispatched}`` counts each group at
        its dispatch: ``ahead`` when an earlier group of the tick was
        still uncollected, ``alone`` otherwise.

        Returns (served record ids, job_id -> streams accumulated
        out-of-band). Served records' publishes are complete — the
        combined-publish pass must skip them and finalize consumes their
        prefetched trees; the stream map feeds ``Job.add``'s
        ``skip_accumulate`` exactly like the fused-step map.

        Containment (mirrors ``_run_combined_publish`` +
        ``_run_fused_steps``), per group, every other group's pending
        handle staying collectable: a staging failure drops the whole
        group to the separate-dispatch path (nothing was touched); a
        plan failure drops only that member; an unpack failure adopts
        the member's folded carry — the fold already ran on device, so
        the stream is still marked accumulated and finalize republishes
        privately; a dispatch failure after donation, synchronous or
        surfacing at the collect, resets exactly the members whose
        buffers were consumed (``state_lost``), with a visible warning,
        and the private path re-adds THIS window's batch into the fresh
        state.
        """
        served: set[int] = set()
        streams_done: dict[JobId, set[str]] = {}
        if not tick_groups:
            return served, streams_done
        from ..ops.publish import PublishRequest

        # (members, combiner, pending handle) of every group dispatched
        # and not yet collected, and the records among their members.
        in_flight: list[tuple] = []
        flying: set[int] = set()

        def drain(hand_over=None) -> None:
            while in_flight:
                members, combiner, pending = in_flight.pop(0)
                collected = self._collect_tick_group(
                    members, combiner, pending, served, streams_done
                )
                # Ahead means ahead of a group still on the chip.
                if hand_over is not None and in_flight and collected:
                    hand_over(collected)
            flying.clear()

        for (stream, key), plc, members in tick_groups:
            if any(id(rec) in flying for rec, *_ in members):
                drain()
            _rec0, _stream0, value0, ingest0, _offer0 = members[0]
            try:
                staged = ingest0.stage(
                    value0.cache,
                    device=None if plc is None else plc.device,
                )
            except Exception:
                logger.exception(
                    "tick staging failed for stream %r (%d jobs); "
                    "falling back to separate dispatches",
                    stream,
                    len(members),
                )
                continue
            requests = [
                PublishRequest(o.publisher, o.args, o.static_token)
                for _rec, _strm, _value, _ingest, o in members
            ]
            # Mesh-spanning groups run through their slice's
            # MeshTickCombiner (replicated outputs, one fetch for the
            # whole mesh); single-device slices share the manager's
            # combiner — programs are keyed per (hist, group) anyway.
            combiner = self._tick_combiner
            slice_key = None
            if plc is not None:
                slice_key = plc.label
                if plc.combiner is not None:
                    combiner = plc.combiner
            try:
                pending = combiner.dispatch(
                    ingest0.hist, key, staged, requests,
                    slice_key=slice_key,
                )
                TICK_GROUPS.labels(
                    dispatched=(
                        "ahead"
                        if in_flight and not pending.compiled
                        else "alone"
                    )
                ).inc()
                if self._chaos is not None:
                    # Chaos site (ADR 0120): the dispatch RAN — donated
                    # member buffers are consumed — and then "fails".
                    # The containment sees exactly what a real
                    # post-donation XLA failure produces: consumed args,
                    # no adoptable results, note_state_lost + re-seed.
                    self._chaos.check("tick_dispatch")
            except Exception:
                self._tick_group_failed(members)
                continue
            in_flight.append((members, combiner, pending))
            flying.update(id(rec) for rec, *_ in members)
            if pending.compiled:
                drain()
        drain(hand_over)
        return served, streams_done

    def _tick_group_failed(self, members: list) -> None:
        """The combiner contains plan/dispatch/unpack failures per
        member; anything escaping is a combiner bug (or the chaos
        site) — it must degrade this group to the separate path, never
        take the window or another group down. States a partial
        dispatch already consumed are rebuilt with a visible warning."""
        from ..ops.publish import publish_args_consumed

        logger.exception(
            "tick program failed (%d jobs); falling back to "
            "separate dispatches",
            len(members),
        )
        for rec, _strm, _value, _ingest, offer in members:
            if publish_args_consumed(offer.args):
                if offer.reset is not None:
                    offer.reset()
                rec.job.note_state_lost()
                rec.warning = (
                    "tick program failed after buffer donation; "
                    "accumulation reset (see service log)"
                )
                self._after_state_loss(rec)

    def _collect_tick_group(
        self,
        members: list,
        combiner,
        pending,
        served: set[int],
        streams_done: dict[JobId, set[str]],
    ) -> list[tuple[_JobRecord, dict[str, Any]]]:
        """The second half of one tick group: wait for its program,
        then the per-member bookkeeping into ``served`` and
        ``streams_done`` (``_run_tick_programs``). Returns the members
        it served, each with the window data the program stepped (all
        of the member's window: ``_split_tick_groups``)."""
        collected: list[tuple[_JobRecord, dict[str, Any]]] = []
        try:
            results = combiner.collect(pending)
        except Exception:
            self._tick_group_failed(members)
            return collected
        for (rec, strm, value, _ingest, offer), res in zip(
            members, results, strict=True
        ):
            if res.error is not None:
                if res.state_lost:
                    # Donation already invalidated the buffers: the
                    # pre-tick accumulation is unrecoverable in
                    # place. Rebuild a fresh state (the private
                    # fallback re-adds THIS window's batch) and
                    # surface the loss instead of stepping a
                    # deleted array forever.
                    if offer.reset is not None:
                        offer.reset()
                    rec.job.note_state_lost()
                    rec.warning = (
                        "tick program failed after buffer donation; "
                        "accumulation reset (see service log)"
                    )
                    self._after_state_loss(rec)
                elif res.carry:
                    # The step+fold already ran on device: adopt the
                    # new state, mark the stream accumulated (a
                    # private re-add would double-count), and let
                    # finalize republish privately — this tick's
                    # window summaries read zero; the cumulative is
                    # intact.
                    try:
                        offer.consume(None, res.carry)
                        streams_done.setdefault(
                            rec.job.job_id, set()
                        ).add(strm)
                    except Exception:
                        logger.exception(
                            "tick carry adoption failed for %s",
                            rec.job.job_id,
                        )
                # Plan-time error (no carry): state untouched — the
                # member takes the full private accumulate + publish
                # path this window.
                continue
            try:
                offer.consume(res.outputs, res.carry)
            except Exception:
                logger.exception(
                    "tick consume failed for %s", rec.job.job_id
                )
                continue
            served.add(id(rec))
            streams_done.setdefault(rec.job.job_id, set()).add(strm)
            collected.append((rec, {strm: value}))
        return collected

    # -- pipelined ingest (core/ingest_pipeline.py, ADR 0111) --------------
    def open_window(self, data: Mapping[str, Any]):
        """Attach a fresh, caller-owned cache generation to this window's
        staged event values and return it.

        The pipelined ingest overlaps windows, so each in-flight window
        gets its own generation (window i+1 prestages while window i
        steps); the caller closes it after the window's publish. The
        serial path never calls this — ``process_jobs`` manages the
        cache-owned current generation itself.
        """
        generation = self._event_cache.new_generation()
        for name, value in data.items():
            if isinstance(value, StagedEvents):
                value.cache = generation.slot(name)
        return generation

    def prestage_window(
        self,
        data: Mapping[str, Any],
        *,
        pool=None,
    ) -> None:
        """Warm the window's stream slots ahead of the job fan-out.

        Runs on the pipeline's stage worker: for every event stream, ask
        each subscribed active job's workflow for its ingest offer (the
        same duck-typed ``event_ingest`` the fused-stepping planner uses
        — offers are side-effect free) and run the offered histogrammer's
        staging into the window's slot. When the step stage later runs
        ``process_jobs``, workflows hit the warm slot and the host
        flatten/partition + transfer cost has already overlapped the
        previous window's step. Offers sharing a key stage once; streams
        without offers (workflows with no ``event_ingest``) simply stage
        at step time — prestaging is an overlap optimization, never a
        correctness dependency. Failures are contained per offer: the
        slot drops a poisoned entry, so the step stage retries privately.
        """
        with self._lock:
            # ACTIVE jobs, plus SCHEDULED ones with no start gate: the
            # phase machine activates those on this very window (data
            # time always reaches a None start), so their staging is
            # needed — skipping them would cold-start every first
            # window. Time- or context-gated jobs stay out: their
            # activation depends on data the stage worker doesn't have,
            # and a wrong guess is a wasted transfer.
            records = [
                rec
                for rec in self._records.values()
                if not rec.needs_reset
                and (
                    rec.phase == _Phase.ACTIVE
                    or (
                        rec.phase == _Phase.SCHEDULED
                        and rec.job.schedule.start is None
                        and not rec.job.context_keys
                    )
                )
            ]
        staged_keys: set[tuple] = set()
        for name, value in data.items():
            if not isinstance(value, StagedEvents) or value.cache is None:
                continue
            for rec in records:
                if name not in rec.job.subscribed_streams:
                    continue
                ingest_fn = getattr(rec.job.workflow, "event_ingest", None)
                if ingest_fn is None:
                    continue
                try:
                    offer = ingest_fn(name, value)
                except Exception:
                    logger.exception(
                        "event_ingest failed during prestage for %s",
                        rec.job.job_id,
                    )
                    continue
                if offer is None:
                    continue
                stage = getattr(offer.hist, "stage_events", None)
                if stage is None:
                    continue
                key = (name, offer.key)
                if key in staged_keys:
                    continue
                staged_keys.add(key)
                # Warm the SLICE's key when a placement is active: the
                # step path stages per-slice, so a default-device
                # prestage would miss. Assignment is sticky and pure
                # table lookup — state moves stay on the step thread
                # (the stage worker must never mutate workflow state).
                stage_kwargs = {}
                if self._placement is not None:
                    try:
                        plc = self._placement.assign(
                            name, offer.key, offer.hist
                        )
                        if plc.device is not None:
                            stage_kwargs["device"] = plc.device
                    except Exception:
                        logger.debug(
                            "prestage placement probe failed",
                            exc_info=True,
                        )
                try:
                    stage(
                        offer.batch,
                        value.cache,
                        batch_tag=offer.batch_tag,
                        pool=pool,
                        **stage_kwargs,
                    )
                except Exception:
                    logger.exception(
                        "Prestage failed for stream %r (job %s); "
                        "step-time staging will retry",
                        name,
                        rec.job.job_id,
                    )

    def peek_pending_streams(self) -> set[str]:
        """Context streams still gating some job (the processor uses this
        to know which context to enrich; reference :503)."""
        with self._lock:
            out: set[str] = set()
            for rec in self._records.values():
                if rec.phase in (_Phase.SCHEDULED, _Phase.PENDING_CONTEXT):
                    out |= rec.job.context_keys
                    out |= rec.job.optional_context_keys
            return out

    # -- processing --------------------------------------------------------
    def process_jobs(
        self,
        data: Mapping[str, Any],
        *,
        context: Mapping[str, Any] | None = None,
        fresh_context: set[str] | None = None,
        start: Timestamp | None = None,
        end: Timestamp | None = None,
        prestaged: bool = False,
        publish: Callable[[list[JobResult]], None] | None = None,
    ) -> list[JobResult]:
        """One window: fire due resets, advance phases, open gates, fan
        per-job add over the thread pool, then serve every due job's
        publish from one combined device round trip per device and fan
        the finalize/serialization back out — per-job errors contained
        at every phase (ADR 0113).

        ``publish`` is the window's publisher. Given one, the results
        of a tick group leave through it as soon as the group is
        collected while a later group of the tick is still on the chip
        (ADR 0128); the return value is what has not left yet, for the
        caller to publish as it always has. Per job nothing changes:
        one result per closed window, in order. An exception of the
        publisher is raised from here once the window's work is done.
        Without one every result is returned.

        Fused-step groups whose every member is due take the
        tick-program fast path (ops/tick.py, ADR 0114): step
        AND publish ride one jitted dispatch + one fetch, so a
        steady-state tick is a single device round trip instead of the
        stage/step/publish triple. Groups that can't (extra streams in
        the window, no publish offer, ``tick_program=False``) keep the
        separate fused-step + combined-publish dispatches.

        ``prestaged`` marks a window whose staged-events values already
        carry slots from a caller-owned cache generation (the pipelined
        ingest: ``open_window`` + ``prestage_window`` ran on a stage
        worker). The cache-owned window lifecycle is skipped — the
        pipeline closes its generation after the window's publish, so an
        overlapped next window can never drop this one's staged arrays.

        ``fresh_context`` names the context streams that received data in
        THIS batch; active jobs get ``set_context`` only for those, so an
        unchanged cached motor position does not re-fire downstream
        recompute every window (reference avoids steady-state context
        refill for the same reason, :596-618). ``None`` means unknown —
        deliver everything (test shims).

        Per-job data is filtered to the streams the job subscribes to
        (reference ``_filter_data_for_job:726``): a job never sees — and
        never pays staging time for — another job's streams.
        """
        context = context or {}
        if self._chaos is not None:
            # Chaos site (ADR 0120): a slow-tick storm. BEFORE the
            # manager lock — the injected stall models slow device/host
            # work, not a lock convoy (and a sleep under the lock would
            # stall scrape-time collectors, the JGL023 class).
            self._chaos.maybe_delay("slow_tick")
        with self._lock:
            # Warm-up shape memory (ADR 0118): the padded batch size
            # each stream carries is the staged-signature dimension of
            # every tick-program key — commit-time warm-up compiles
            # against the shape the stream is actually running at.
            for name, value in data.items():
                if isinstance(value, StagedEvents):
                    self._stream_batch_shapes[name] = (
                        value.batch.padded_size
                    )
            if not prestaged:
                # New window generation: previous staged slots drop, and
                # this window's event batches get stream slots so every
                # consumer — workflow-private stepping and the fused
                # layer alike — stages each batch once per (stream,
                # layout).
                self._event_cache.begin_window()
                for name, value in data.items():
                    if isinstance(value, StagedEvents):
                        value.cache = self._event_cache.slot(name)
            if end is not None:
                self._fire_pending_resets(end)
                self._advance_to_time(end)
            graduated = self._open_context_gates(context)
            # Queue fresh context for later delivery. None = unknown
            # freshness (test shims): queue everything, restoring
            # every-window delivery.
            queued = set(context) if fresh_context is None else fresh_context
            if queued:
                for job_id, rec in self._records.items():
                    if rec.phase == _Phase.ACTIVE and job_id not in graduated:
                        rec.stale_context |= queued & (
                            rec.job.context_keys
                            | rec.job.optional_context_keys
                        )
            work: list[tuple[_JobRecord, dict[str, Any]]] = []
            for rec in self._records.values():
                if rec.phase != _Phase.ACTIVE:
                    continue
                if rec.needs_reset:
                    # Retry the failed run-transition reset; until it
                    # succeeds the job must not accumulate (old-run data
                    # is still in the workflow).
                    self._reset_record(rec)
                    if rec.needs_reset:
                        continue
                job_data = {
                    k: v
                    for k, v in data.items()
                    if k in rec.job.subscribed_streams
                }
                # Skip jobs with nothing to do: no fresh data and nothing
                # pending finalize. A finishing job is still ACTIVE here —
                # it leaves only after this pass — so the window that
                # carried it past its end time is flushed before stopping.
                # (Queued context survives the skip and is delivered before
                # the job's next add.)
                if job_data or rec.has_primary_data:
                    work.append((rec, job_data))
            fuse_groups = self._plan_fused_steps(work)
            if self._fleet is not None:
                work, fuse_groups = self._apply_fleet_filter(
                    work, fuse_groups
                )

        trace_id = TRACER.current()
        #: When the pool was handed the window's private jobs; None in
        #: the serial branch, where no job waits for a thread.
        fanned_out_at: float | None = None

        def run_accumulate(item: tuple[_JobRecord, dict[str, Any]]) -> None:
            # Two aggregates a job a window, on whichever thread runs
            # it: ``accumulate`` is this whole body; minus the job's
            # ``h2d``, ``q_step`` and ``stage_wait`` it is what an add
            # costs outside its spans. ``pool_queue`` is what the job
            # waited for a free pool thread.
            started = time.perf_counter()
            rec, job_data = item
            skip_streams = fused_streams.get(rec.job.job_id, frozenset())
            with TRACER.bind(trace_id):  # a pool thread has none of its own
                if fanned_out_at is not None:
                    TRACER.observe("pool_queue", started - fanned_out_at)
                accumulate(rec, job_data, skip_streams)
                TRACER.observe("accumulate", time.perf_counter() - started)

        def accumulate(
            rec: _JobRecord, job_data: dict[str, Any], skip_streams
        ) -> None:
            job = rec.job
            # Deliver pending context in its own try: a failure keeps the
            # names queued (retried next window) and does not block this
            # window's accumulation.
            context_warning = ""
            if rec.stale_context:
                # Only the names actually present in this window's context
                # are delivered (and de-queued on success); the rest stay
                # queued for a later window rather than being dropped.
                deliverable = {
                    k for k in rec.stale_context if k in context
                }
                try:
                    if deliverable:
                        job.set_context(
                            {k: context[k] for k in deliverable}
                        )
                    rec.stale_context -= deliverable
                except Exception as err:
                    context_warning = f"{type(err).__name__}: {err}"
                    logger.exception(
                        "Job %s failed applying context", job.job_id
                    )
            # Accumulate: a failure here is a warning — the job may still
            # be able to finalize previously accumulated data. A successful
            # add must not mask an unresolved context failure.
            try:
                touched = job.add(
                    job_data,
                    start=start,
                    end=end,
                    skip_accumulate=skip_streams,
                )
                if touched and any(k in job_data for k in job.primary_streams):
                    rec.has_primary_data = True
                rec.warning = context_warning
            except Exception as err:
                rec.warning = f"{type(err).__name__}: {err}"
                logger.exception("Job %s failed accumulating", job.job_id)

        def run_finalize(rec: _JobRecord) -> JobResult | None:
            # Finalize: a failure here is an error; has_primary_data stays
            # set so the next window retries.
            try:
                result = rec.job.get()
                rec.error = ""
                rec.has_primary_data = False
                if rec.job.none_outputs:
                    rec.warning = (
                        "outputs returned None: "
                        + ", ".join(rec.job.none_outputs)
                    )
                return result
            except Exception as err:
                rec.error = f"{type(err).__name__}: {err}"
                logger.exception("Job %s failed finalizing", rec.job.job_id)
                return None

        def finalize(due: list[_JobRecord]) -> list[JobResult]:
            # One finalize span per group handed over ahead and one for
            # the rest of the window (ADR 0116, ADR 0128), recorded from
            # THIS thread (the step worker carries the window's bound
            # trace id; the pool threads inside wouldn't).
            with TRACER.span("finalize"):
                if self._executor is not None and len(due) > 1:
                    results = list(self._executor.map(run_finalize, due))
                else:
                    results = [run_finalize(rec) for rec in due]
            return [r for r in results if r is not None]

        # Results leave per tick group (ADR 0128): the members a group's
        # collect served run here what the rest of the window runs for
        # them below (the add is bookkeeping: the program stepped all
        # of a tick-served member's window, and it has no queued
        # context), then go to the window's publisher while the chip
        # works on the groups behind. A publisher that raises is
        # remembered and raised when the window is done: nothing more
        # is handed over ahead, every pending group is still collected
        # and every state adopted.
        ahead: set[int] = set()
        publish_failure: Exception | None = None

        def publish_ahead(
            collected: list[tuple[_JobRecord, dict[str, Any]]]
        ) -> None:
            nonlocal publish_failure
            if publish_failure is not None:
                return
            for rec, stepped in collected:
                accumulate(rec, stepped, frozenset(stepped))
                ahead.add(id(rec))
            results = finalize(
                [rec for rec, _ in collected if rec.has_primary_data]
            )
            if results:
                JOB_PUBLISHES.inc(len(results), when="ahead")
                try:
                    publish(results)
                except Exception as err:
                    publish_failure = err

        # Tick fast path (outside the lock, same as the fan-out): groups
        # whose every member is due step AND publish in ONE dispatch
        # (ops/tick.py, ADR 0114). Remaining groups of >= 2 jobs sharing
        # a (stream, fuse-key) advance all their states in ONE fused
        # dispatch from ONE cached staging.
        tick_served: set[int] = set()
        tick_streams: dict[JobId, set[str]] = {}
        if self._tick_combiner is not None:
            fuse_groups, tick_groups = self._split_tick_groups(
                work, fuse_groups
            )
            tick_served, tick_streams = self._run_tick_programs(
                tick_groups, None if publish is None else publish_ahead
            )
        fused_streams = self._run_fused_steps(fuse_groups)
        for rec, job_data in work:
            if job_data:
                JOB_WINDOWS.inc(
                    path="tick"
                    if rec.job.job_id in tick_streams
                    else "fused"
                    if rec.job.job_id in fused_streams
                    else "private"
                )
        for job_id, streams in tick_streams.items():
            fused_streams.setdefault(job_id, set()).update(streams)
        work = [item for item in work if id(item[0]) not in ahead]

        if self._executor is not None and len(work) > 1:
            # The loop thread waits here under no span of its own (the
            # job threads' ``h2d`` / ``q_step`` are theirs): the wait
            # is an aggregate that counts toward this thread's
            # coverage, so ``unspanned`` stays what no phase explains.
            fanned_out_at = time.perf_counter()
            with TRACER.aggregate("accumulate_wait", covers=True):
                list(self._executor.map(run_accumulate, work))
        else:
            for item in work:
                run_accumulate(item)

        # Every accumulated state is final for this window: jobs due a
        # publish finalize below, prefetched through ONE combined device
        # round trip per device.
        due = [rec for rec, _ in work if rec.has_primary_data]
        results: list[JobResult] = []
        if due:
            # Tick-served records already published inside their tick
            # program; combining them again would dispatch a second
            # publish over the already-folded state.
            self._run_combined_publish(
                [rec for rec in due if id(rec) not in tick_served]
            )
            results = finalize(due)

        with self._lock:
            for rec in list(self._records.values()):
                if rec.finishing and rec.phase in (
                    _Phase.ACTIVE,
                    _Phase.PENDING_CONTEXT,
                    # A job stopped before it ever activated (beam-off:
                    # nothing advanced it out of SCHEDULED) has nothing
                    # to flush — it must still complete its stop.
                    _Phase.SCHEDULED,
                ):
                    rec.phase = _Phase.STOPPED
                    # The final window just flushed above: free the
                    # device-resident accumulator now instead of pinning
                    # it until an operator removes the stopped record.
                    rec.job.release()
        if not prestaged:
            # Drop this window's staged references: device memory frees
            # once the last in-flight kernel completes, and next window's
            # batches can never alias a stale generation. (Pipelined
            # windows: the pipeline closes its own generation after the
            # publish instead.)
            self._event_cache.end_window()
        if publish_failure is not None:
            raise publish_failure
        if results:
            JOB_PUBLISHES.inc(len(results), when="end")
        return results

    # graft: protocol=fleet (ADR 0124: the per-group owns() consult
    # below is the modeled filter of the single-owner invariant)
    def _apply_fleet_filter(
        self,
        work: list[tuple["_JobRecord", dict[str, Any]]],
        fuse_groups: dict[tuple, list],
    ) -> tuple[list, dict[tuple, list]]:
        """Drop the groups a peer replica owns (ADR 0121; caller holds
        the manager lock).

        Ownership is decided at GROUP granularity: a job riding a fused
        group follows its ``(stream, fuse-key)`` rendezvous hash — the
        exact key ADR 0115 places on mesh slices — and an ungrouped job
        follows its primary stream with a None fuse tag. A filtered job
        keeps an EMPTY work entry when it has accumulation pending
        (``has_primary_data``): a group that just moved away must still
        flush what this replica already folded in, which is what makes
        a rebalance a drain + replay instead of data loss."""
        fleet = self._fleet
        member_owned: dict[tuple[int, str], bool] = {}
        kept_groups: dict[tuple, list] = {}
        for (stream, fkey), members in fuse_groups.items():
            owned = fleet.owns(stream, fkey)
            if owned:
                kept_groups[(stream, fkey)] = members
            for rec, member_stream, _value, _offer in members:
                member_owned[(id(rec), member_stream)] = owned
        new_work: list[tuple[_JobRecord, dict[str, Any]]] = []
        for rec, job_data in work:
            grouped = [
                s for s in job_data if (id(rec), s) in member_owned
            ]
            if grouped:
                owned = any(
                    member_owned[(id(rec), s)] for s in grouped
                )
            elif job_data:
                # Ungrouped work keys by the job's FIXED anchor stream
                # — its first declared primary (or, for primary-less
                # jobs, its first subscribed stream) — NOT whichever
                # streams happened to arrive this window: a window
                # carrying only auxiliary data must land on the same
                # replica as every other window of the job, or the
                # partition stops being sticky and aux updates
                # accumulate on an orphan copy.
                anchor = sorted(
                    rec.job.primary_streams
                    or rec.job.subscribed_streams
                )
                owned = (
                    fleet.owns(anchor[0], None) if anchor else True
                )
            else:
                owned = True  # pure flush entry: always local
            if owned:
                new_work.append((rec, job_data))
            elif rec.has_primary_data:
                new_work.append((rec, {}))
        return new_work, kept_groups

    def _plan_fused_steps(
        self, work: list[tuple[_JobRecord, dict[str, Any]]]
    ) -> dict[tuple, list]:
        """Group fusable (job, stream, staged) offers by (stream, fuse key).

        A job is eligible when it has no queued context (fused stepping
        runs before the per-job context delivery in ``run_one``, so a
        pending position/geometry update must keep the job on the private
        path this window to preserve context-before-accumulate ordering)
        and its workflow offers an ``event_ingest`` for the value. At most
        one stream fuses per job per window — a second StagedEvents value
        on the same workflow would race its own state capture.
        """
        groups: dict[tuple, list] = {}
        for rec, job_data in work:
            if rec.stale_context:
                continue
            ingest_fn = getattr(rec.job.workflow, "event_ingest", None)
            if ingest_fn is None:
                continue
            for stream, value in job_data.items():
                if not isinstance(value, StagedEvents):
                    continue
                try:
                    offer = ingest_fn(stream, value)
                except Exception:
                    logger.exception(
                        "event_ingest failed for %s", rec.job.job_id
                    )
                    offer = None
                if offer is None:
                    continue
                groups.setdefault((stream, offer.key), []).append(
                    (rec, stream, value, offer)
                )
                break
        return groups

    def _run_fused_steps(
        self, groups: dict[tuple, list]
    ) -> dict[JobId, set[str]]:
        """Execute every group of >= 2 offers with one fused dispatch.

        Returns job_id -> streams accumulated out-of-band (``Job.add``
        skips them). Failure containment: a group whose fused step raises
        at TRACE time (buffers untouched) is logged and left to the
        private per-job path — state setters only run after a successful
        dispatch, so nothing half-applies and the fallback cannot
        double-count. A RUNTIME failure (e.g. HBM OOM allocating the K
        fused outputs) is harder: ``step_many`` donates every state, so
        the old buffers may already be invalidated — each member whose
        state was consumed gets a fresh zeroed state and a visible
        warning instead of stepping a deleted array forever. Singleton
        groups stay private: a K=1 fused program would compile a second
        identical kernel for no dispatch saving.
        """
        from ..ops.publish import METRICS

        fused: dict[JobId, set[str]] = {}
        for (stream, _key), members in groups.items():
            if len(members) < 2:
                continue
            rec0, _stream0, value0, offer0 = members[0]
            # Same sticky slice as the tick path (a group the tick
            # refuses for one window runs here; it must not alternate
            # devices between windows — that would re-stage the wire
            # and re-commit every state per window).
            plc = self._group_placement((stream, _key), members)
            device = None if plc is None else plc.device
            # device is None for un-placed groups AND for bespoke
            # histogrammers the placement pinned to the default slice
            # (DevicePlacement probes for device-aware staging), so the
            # kwarg is only ever forwarded to implementations that
            # accept it.
            step_kwargs = {} if device is None else {"device": device}
            states = tuple(m[3].get_state() for m in members)
            try:
                new_states = offer0.hist.step_many(
                    states,
                    offer0.batch,
                    cache=value0.cache,
                    batch_tag=offer0.batch_tag,
                    **step_kwargs,
                )
                # One separate step dispatch (the tick program folds
                # this into the publish execute instead): the bench
                # ``--tick`` dispatch-count decomposition reads it.
                METRICS.record(step_executes=1)
            except Exception:
                logger.exception(
                    "Fused step failed for stream %r (%d jobs); "
                    "falling back to per-job accumulation",
                    stream,
                    len(members),
                )
                for (rec, _strm, _value, offer), state in zip(
                    members, states, strict=True
                ):
                    if self._state_consumed(state):
                        # Donation already invalidated the buffers: the
                        # pre-step accumulation is unrecoverable in
                        # place. Reset to a fresh state (the private
                        # fallback then re-adds THIS window's batch) and
                        # surface the loss instead of erroring on a
                        # deleted array every window from here on.
                        offer.set_state(offer.hist.init_state())
                        rec.job.note_state_lost()
                        rec.warning = (
                            "fused step failed after buffer donation; "
                            "accumulation reset (see service log)"
                        )
                        self._after_state_loss(rec)
                continue
            for (rec, strm, _value, offer), new_state in zip(
                members, new_states, strict=True
            ):
                offer.set_state(new_state)
                fused.setdefault(rec.job.job_id, set()).add(strm)
        return fused

    @staticmethod
    def _state_consumed(state) -> bool:
        """True when any leaf buffer of a (donated) state pytree has been
        invalidated by a dispatch that subsequently failed."""
        for leaf in state:
            deleted = getattr(leaf, "is_deleted", None)
            try:
                if deleted is not None and deleted():
                    return True
            except Exception:  # pragma: no cover - defensive
                return True
        return False

    def event_cache_stats(self) -> dict[str, int | float]:
        """Stage-once cache counters since the last metrics drain
        (hits/misses/bytes_staged/hit_rate) — the 30 s metrics line and
        the multi-job bench read these."""
        return self._event_cache.drain_stats()

    def event_cache_cumulative_stats(self) -> dict[str, int | float]:
        """Monotone stage-once cache totals since construction — the
        telemetry collector's read (ADR 0116), independent of the 30 s
        drain above."""
        return self._event_cache.cumulative_stats()

    # -- introspection -----------------------------------------------------
    def has_finishing_jobs(self) -> bool:
        """True while any job awaits its final flush — the processor runs
        an empty window on idle ticks so stops complete without beam.
        Already-stopped records keep their ``finishing`` flag but need
        nothing further."""
        with self._lock:
            return any(
                rec.finishing and rec.phase is not _Phase.STOPPED
                for rec in self._records.values()
            )

    def job_statuses(self) -> list[JobStatus]:
        with self._lock:
            return [
                JobStatus(
                    source_name=jid.source_name,
                    job_number=jid.job_number,
                    workflow_id=str(rec.job.workflow_id),
                    state=rec.state,
                    message=rec.error or rec.warning,
                    has_primary_data=rec.has_primary_data,
                    params=rec.job.params,
                )
                for jid, rec in self._records.items()
            ]

    @property
    def n_jobs(self) -> int:
        with self._lock:
            return len(self._records)

    def subscribed_streams(self) -> set[str]:
        with self._lock:
            out: set[str] = set()
            for rec in self._records.values():
                out |= rec.job.subscribed_streams
            return out

    def shutdown(self) -> None:
        # Crash-recovery dump: a restarted service restores mid-run
        # accumulation instead of starting from zero.
        self.dump_snapshots(reason="shutdown")
        if self._executor is not None:
            self._executor.shutdown(wait=False)

import uuid

import numpy as np
import pytest

from esslivedata_tpu.config import JobId, JobSchedule, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.job_manager import JobCommand, JobFactory, JobManager
from esslivedata_tpu.core.job import JobState
from esslivedata_tpu.core.message import RunStart
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.telemetry import REGISTRY, TRACER
from esslivedata_tpu.utils import DataArray, Variable
from esslivedata_tpu.workflows import WorkflowFactory


class CountingWorkflow:
    """Accumulates floats per stream; counts lifecycle calls."""

    def __init__(self):
        self.total = 0.0
        self.finalize_calls = 0
        self.clear_calls = 0
        self.context: dict = {}

    def accumulate(self, data):
        for v in data.values():
            self.total += v

    def finalize(self):
        self.finalize_calls += 1
        return {
            "total": DataArray(
                Variable(np.asarray(self.total), (), "counts"), name="total"
            )
        }

    def clear(self):
        self.clear_calls += 1
        self.total = 0.0

    def set_context(self, ctx):
        self.context.update(ctx)


@pytest.fixture
def registry():
    reg = WorkflowFactory()
    spec = WorkflowSpec(
        instrument="dummy", name="count", source_names=["bank0", "bank1"]
    )
    handle = reg.register_spec(spec)
    handle.attach_factory(lambda *, source_name, params: CountingWorkflow())

    gated_spec = WorkflowSpec(
        instrument="dummy",
        name="gated",
        source_names=["bank0"],
        context_keys=["motor_x"],
    )
    reg.register_spec(gated_spec).attach_factory(
        lambda *, source_name, params: CountingWorkflow()
    )
    return reg


@pytest.fixture
def manager(registry):
    return JobManager(job_factory=JobFactory(registry), job_threads=1)


def start_config(registry, name="count", source="bank0", **schedule):
    spec = next(s for s in registry.specs_for_instrument("dummy") if s.name == name)
    return WorkflowConfig(
        identifier=spec.identifier,
        job_id=JobId(source_name=source),
        schedule=JobSchedule(**schedule) if schedule else JobSchedule(),
    )


T = Timestamp.from_ns


class TestScheduling:
    def test_schedule_and_process(self, registry, manager):
        manager.schedule_job(start_config(registry))
        results = manager.process_jobs(
            {"bank0": 5.0}, start=T(0), end=T(100)
        )
        assert len(results) == 1
        assert float(results[0].outputs["total"].values) == 5.0
        assert results[0].outputs["total"].coords["end_time"].value == 100

    def test_duplicate_job_rejected(self, registry, manager):
        config = start_config(registry)
        manager.schedule_job(config)
        with pytest.raises(ValueError, match="already exists"):
            manager.schedule_job(config)

    def test_data_time_activation(self, registry, manager):
        manager.schedule_job(start_config(registry, start_time_ns=1000))
        # window ends before start_time: job not yet active
        assert manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(500)) == []
        results = manager.process_jobs({"bank0": 2.0}, start=T(900), end=T(1500))
        assert len(results) == 1

    def test_end_time_finishes_job(self, registry, manager):
        manager.schedule_job(start_config(registry, end_time_ns=1000))
        manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(1500))
        [status] = manager.job_statuses()
        assert status.state == JobState.STOPPED
        assert manager.process_jobs({"bank0": 1.0}, start=T(1500), end=T(2000)) == []

    def test_no_result_without_primary_data(self, registry, manager):
        manager.schedule_job(start_config(registry))
        assert manager.process_jobs({"other": 1.0}, start=T(0), end=T(10)) == []


class TestContextGating:
    def test_gated_until_context_arrives(self, registry, manager):
        manager.schedule_job(start_config(registry, name="gated"))
        results = manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(10))
        assert results == []
        [status] = manager.job_statuses()
        assert status.state == JobState.PENDING_CONTEXT
        assert manager.peek_pending_streams() == {"motor_x"}

        results = manager.process_jobs(
            {"bank0": 2.0}, context={"motor_x": 3.5}, start=T(10), end=T(20)
        )
        assert len(results) == 1
        [status] = manager.job_statuses()
        assert status.state == JobState.ACTIVE

    def test_context_delivered_to_workflow(self, registry, manager):
        manager.schedule_job(start_config(registry, name="gated"))
        manager.process_jobs(
            {"bank0": 1.0}, context={"motor_x": 7.0}, start=T(0), end=T(10)
        )
        rec = next(iter(manager._records.values()))
        assert rec.job.workflow.context == {"motor_x": 7.0}


class TestRunTransitions:
    def test_run_start_resets(self, registry, manager):
        manager.schedule_job(start_config(registry))
        manager.process_jobs({"bank0": 5.0}, start=T(0), end=T(10))
        manager.handle_run_transition(
            RunStart(run_name="r2", start_time=T(20))
        )
        results = manager.process_jobs({"bank0": 1.0}, start=T(20), end=T(30))
        assert float(results[0].outputs["total"].values) == 1.0  # reset happened
        rec = next(iter(manager._records.values()))
        assert rec.job.workflow.clear_calls == 1


class TestCommands:
    def test_stop(self, registry, manager):
        config = start_config(registry)
        manager.schedule_job(config)
        manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(10))
        manager.handle_command(
            JobCommand(
                action="stop",
                source_name="bank0",
                job_number=config.job_id.job_number,
            )
        )
        manager.process_jobs({"bank0": 1.0}, start=T(10), end=T(20))
        [status] = manager.job_statuses()
        assert status.state == JobState.STOPPED

    def test_remove(self, registry, manager):
        config = start_config(registry)
        manager.schedule_job(config)
        manager.handle_command(
            JobCommand(
                action="remove",
                source_name="bank0",
                job_number=config.job_id.job_number,
            )
        )
        assert manager.n_jobs == 0

    def test_unknown_job_is_tolerated(self, manager):
        # Routine on the shared commands topic: another service owns the
        # job. Zero acted-on jobs, no exception, and the caller (dispatcher)
        # stays silent so exactly one service across the fleet replies.
        count = manager.handle_command(
            JobCommand(action="stop", source_name="zz", job_number=uuid.uuid4())
        )
        assert count == 0

    def test_known_job_command_reports_one_acted_on(self, registry, manager):
        config = start_config(registry)
        manager.schedule_job(config)
        count = manager.handle_command(
            JobCommand(
                action="stop",
                source_name="bank0",
                job_number=config.job_id.job_number,
            )
        )
        assert count == 1


class TestErrorContainment:
    def test_failing_job_does_not_kill_others(self, registry, manager):
        class ExplodingWorkflow(CountingWorkflow):
            def finalize(self):
                raise RuntimeError("device OOM")

        spec = WorkflowSpec(instrument="dummy", name="boom", source_names=["bank1"])
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: ExplodingWorkflow()
        )
        manager.schedule_job(start_config(registry))
        manager.schedule_job(start_config(registry, name="boom", source="bank1"))
        results = manager.process_jobs(
            {"bank0": 1.0, "bank1": 2.0}, start=T(0), end=T(10)
        )
        assert len(results) == 1  # healthy job still produced
        states = {s.workflow_id: s.state for s in manager.job_statuses()}
        assert JobState.ERROR in states.values()
        assert JobState.ACTIVE in states.values()


class TestThreadFanOut:
    def test_parallel_results_match(self, registry):
        manager = JobManager(job_factory=JobFactory(registry), job_threads=4)
        for source in ("bank0", "bank1"):
            manager.schedule_job(start_config(registry, source=source))
        results = manager.process_jobs(
            {"bank0": 1.0, "bank1": 2.0}, start=T(0), end=T(10)
        )
        totals = sorted(float(r.outputs["total"].values) for r in results)
        assert totals == [1.0, 2.0]
        manager.shutdown()


def span_totals(name: str) -> tuple[float, int]:
    family = REGISTRY.get("livedata_tick_span_seconds")
    return family.sum(span=name), family.count(span=name)


class TestAccumulatePhaseAggregates:
    """The pool phase from inside (``telemetry/trace.py``): the loop
    thread's wait, each job's turn and what it queued for a thread."""

    NAMES = ("accumulate_wait", "accumulate", "pool_queue")

    @pytest.fixture(autouse=True)
    def tracer_on(self, monkeypatch):
        monkeypatch.setattr(TRACER, "enabled", True)

    def counts(self):
        return [span_totals(name)[1] for name in self.NAMES]

    def test_two_private_jobs_on_a_two_thread_pool(self, registry):
        manager = JobManager(job_factory=JobFactory(registry), job_threads=2)
        for source in ("bank0", "bank1"):
            manager.schedule_job(start_config(registry, source=source))
        trace_id = TRACER.new_trace()
        ring_before = len(TRACER.spans())
        for window in range(2):
            before, sums = self.counts(), [span_totals(n)[0] for n in self.NAMES]
            with TRACER.bind(trace_id):
                manager.process_jobs(
                    {"bank0": 1.0, "bank1": 2.0},
                    start=T(10 * window),
                    end=T(10 * window + 10),
                )
            wait, accumulate, queued = (
                span_totals(n)[0] - was for n, was in zip(self.NAMES, sums)
            )
            assert [now - was for now, was in zip(self.counts(), before)] == [1, 2, 2]
            # each job's turn, and its queueing, lie inside the loop thread's wait
            assert 0.0 <= queued and 0.0 < accumulate <= 2 * wait
        # the ring got the window's ``finalize`` spans and no new name
        assert {s.name for s in TRACER.spans()[ring_before:]} == {"finalize"}
        manager.shutdown()

    def test_the_wait_counts_toward_the_loop_threads_coverage(self, registry):
        manager = JobManager(job_factory=JobFactory(registry), job_threads=2)
        for source in ("bank0", "bank1"):
            manager.schedule_job(start_config(registry, source=source))
        trace_id = TRACER.new_trace()
        wait0, fin0, unspanned0 = (
            span_totals(n)[0] for n in ("accumulate_wait", "finalize", "unspanned")
        )
        with TRACER.bind(trace_id):
            manager.process_jobs({"bank0": 1.0, "bank1": 2.0}, start=T(0), end=T(10))
        covered = (span_totals("accumulate_wait")[0] - wait0) + (
            span_totals("finalize")[0] - fin0
        )
        TRACER.finish_tick(trace_id, covered + 0.003, tiled=True)
        assert span_totals("unspanned")[0] - unspanned0 == pytest.approx(0.003, abs=1e-9)
        manager.shutdown()

    @pytest.mark.parametrize("threads, sources", [(2, ("bank0",)), (1, ("bank0", "bank1"))])
    def test_the_serial_branch_has_no_wait_and_no_queue(self, registry, threads, sources):
        """One job in the window, or no pool: the leaf spans are the
        loop thread's own, and ``accumulate`` alone is observed."""
        manager = JobManager(job_factory=JobFactory(registry), job_threads=threads)
        for source in sources:
            manager.schedule_job(start_config(registry, source=source))
        before = self.counts()
        manager.process_jobs({"bank0": 1.0, "bank1": 2.0}, start=T(0), end=T(10))
        assert [now - was for now, was in zip(self.counts(), before)] == [0, len(sources), 0]
        manager.shutdown()


def get_workflow(manager, source="bank0"):
    [rec] = [
        r
        for jid, r in manager._records.items()
        if jid.source_name == source
    ]
    return rec.job.workflow


class TestDeferredResets:
    """Run-transition resets fire on DATA time, not arrival order
    (reference run_transition_test.py scenario semantics)."""

    def run_start(self, manager, at_ns, stop_ns=None):
        manager.handle_run_transition(
            RunStart(
                run_name="r1",
                start_time=T(at_ns),
                stop_time=None if stop_ns is None else T(stop_ns),
            )
        )

    def test_reset_does_not_fire_before_scheduled_time(
        self, registry, manager
    ):
        manager.schedule_job(start_config(registry))
        manager.process_jobs({"bank0": 5.0}, start=T(0), end=T(10))
        self.run_start(manager, at_ns=1000)
        manager.process_jobs({"bank0": 1.0}, start=T(10), end=T(20))
        assert get_workflow(manager).clear_calls == 0
        assert get_workflow(manager).total == 6.0

    def test_reset_fires_when_data_reaches_scheduled_time(
        self, registry, manager
    ):
        manager.schedule_job(start_config(registry))
        manager.process_jobs({"bank0": 5.0}, start=T(0), end=T(10))
        self.run_start(manager, at_ns=1000)
        manager.process_jobs({"bank0": 1.0}, start=T(990), end=T(1100))
        wf = get_workflow(manager)
        assert wf.clear_calls == 1
        # The reset applies before the window is accumulated.
        assert wf.total == 1.0

    def test_reset_fires_on_run_stop(self, registry, manager):
        from esslivedata_tpu.core.message import RunStop

        manager.schedule_job(start_config(registry))
        manager.process_jobs({"bank0": 5.0}, start=T(0), end=T(10))
        manager.handle_run_transition(
            RunStop(run_name="r1", stop_time=T(500))
        )
        manager.process_jobs({"bank0": 2.0}, start=T(400), end=T(600))
        assert get_workflow(manager).clear_calls == 1

    def test_past_reset_time_fires_on_next_data(self, registry, manager):
        manager.schedule_job(start_config(registry))
        manager.process_jobs({"bank0": 5.0}, start=T(0), end=T(1000))
        self.run_start(manager, at_ns=500)  # already in the data past
        manager.process_jobs({"bank0": 1.0}, start=T(1000), end=T(1100))
        assert get_workflow(manager).clear_calls == 1

    def test_run_start_with_stop_time_schedules_two_resets(
        self, registry, manager
    ):
        manager.schedule_job(start_config(registry))
        self.run_start(manager, at_ns=100, stop_ns=1000)
        manager.process_jobs({"bank0": 1.0}, start=T(50), end=T(200))
        assert get_workflow(manager).clear_calls == 1
        manager.process_jobs({"bank0": 1.0}, start=T(900), end=T(1100))
        assert get_workflow(manager).clear_calls == 2

    def test_multiple_pending_resets_collapse_within_batch(
        self, registry, manager
    ):
        manager.schedule_job(start_config(registry))
        self.run_start(manager, at_ns=100)
        self.run_start(manager, at_ns=200)
        self.run_start(manager, at_ns=300)
        manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(1000))
        # All three were due in one window: one reset, not three.
        assert get_workflow(manager).clear_calls == 1

    def test_pending_resets_persist_without_data(self, registry, manager):
        manager.schedule_job(start_config(registry))
        self.run_start(manager, at_ns=500)
        manager.process_jobs({}, start=None, end=None)  # no window closed
        manager.process_jobs({"bank0": 1.0}, start=T(400), end=T(600))
        assert get_workflow(manager).clear_calls == 1

    def test_skips_jobs_with_flag_disabled(self, registry, manager):
        spec = WorkflowSpec(
            instrument="dummy",
            name="sticky",
            source_names=["bank1"],
            reset_on_run_transition=False,
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: CountingWorkflow()
        )
        manager.schedule_job(start_config(registry))
        manager.schedule_job(
            start_config(registry, name="sticky", source="bank1")
        )
        manager.process_jobs(
            {"bank0": 1.0, "bank1": 2.0}, start=T(0), end=T(10)
        )
        self.run_start(manager, at_ns=100)
        manager.process_jobs(
            {"bank0": 1.0, "bank1": 2.0}, start=T(90), end=T(200)
        )
        assert get_workflow(manager, "bank0").clear_calls == 1
        assert get_workflow(manager, "bank1").clear_calls == 0


class TestPerJobFiltering:
    def test_job_sees_only_subscribed_streams(self, registry, manager):
        seen: dict[str, list] = {"streams": []}

        class RecordingWorkflow(CountingWorkflow):
            def accumulate(self, data):
                seen["streams"].append(set(data))
                super().accumulate(data)

        spec = WorkflowSpec(
            instrument="dummy", name="rec", source_names=["bank0"]
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: RecordingWorkflow()
        )
        manager.schedule_job(start_config(registry, name="rec"))
        manager.process_jobs(
            {"bank0": 1.0, "bank1": 2.0, "unrelated": 3.0},
            start=T(0),
            end=T(10),
        )
        assert seen["streams"] == [{"bank0"}]

    def test_idle_job_not_finalized_without_new_data(self, registry, manager):
        manager.schedule_job(start_config(registry))
        manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(10))
        wf = get_workflow(manager)
        assert wf.finalize_calls == 1
        # Window with data for OTHER streams only: no result, no finalize.
        results = manager.process_jobs({"zz": 1.0}, start=T(10), end=T(20))
        assert results == []
        assert wf.finalize_calls == 1


class TestErrorSplit:
    def test_finalize_error_retries_next_window(self, registry, manager):
        class FlakyWorkflow(CountingWorkflow):
            def finalize(self):
                if self.finalize_calls == 0:
                    self.finalize_calls += 1
                    raise RuntimeError("transient")
                return super().finalize()

        spec = WorkflowSpec(
            instrument="dummy", name="flaky", source_names=["bank0"]
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: FlakyWorkflow()
        )
        manager.schedule_job(start_config(registry, name="flaky"))
        assert manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(10)) == []
        [status] = manager.job_statuses()
        assert status.state == JobState.ERROR
        # No new primary data, but has_primary_data is sticky after the
        # failed finalize: the next window retries and recovers.
        results = manager.process_jobs({}, start=T(10), end=T(20))
        assert len(results) == 1
        [status] = manager.job_statuses()
        assert status.state == JobState.ACTIVE

    def test_accumulate_error_is_warning_and_old_data_still_finalizes(
        self, registry, manager
    ):
        class BadAddWorkflow(CountingWorkflow):
            def accumulate(self, data):
                if any(v < 0 for v in data.values()):
                    raise ValueError("negative counts")
                super().accumulate(data)

        spec = WorkflowSpec(
            instrument="dummy", name="badadd", source_names=["bank0"]
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: BadAddWorkflow()
        )
        manager.schedule_job(start_config(registry, name="badadd"))
        manager.process_jobs({"bank0": 1.0}, start=T(0), end=T(10))
        # Poisoned window: add fails -> warning, not error; nothing pending
        # so no result this window.
        results = manager.process_jobs({"bank0": -1.0}, start=T(10), end=T(20))
        assert results == []
        [status] = manager.job_statuses()
        assert status.state == JobState.WARNING
        # Healthy data clears the warning.
        results = manager.process_jobs({"bank0": 2.0}, start=T(20), end=T(30))
        assert len(results) == 1
        [status] = manager.job_statuses()
        assert status.state == JobState.ACTIVE


class TestFreshContextDelivery:
    def test_unchanged_context_not_redelivered(self, registry, manager):
        calls: list[dict] = []

        class CtxWorkflow(CountingWorkflow):
            def set_context(self, ctx):
                calls.append(dict(ctx))
                super().set_context(ctx)

        spec = WorkflowSpec(
            instrument="dummy",
            name="ctx",
            source_names=["bank0"],
            context_keys=["motor_x"],
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: CtxWorkflow()
        )
        manager.schedule_job(start_config(registry, name="ctx"))
        # Gate opens: full context delivered once.
        manager.process_jobs(
            {"bank0": 1.0},
            context={"motor_x": 5.0},
            fresh_context={"motor_x"},
            start=T(0),
            end=T(10),
        )
        assert calls == [{"motor_x": 5.0}]
        # Cached, unchanged context: not redelivered to the active job.
        manager.process_jobs(
            {"bank0": 1.0},
            context={"motor_x": 5.0},
            fresh_context=set(),
            start=T(10),
            end=T(20),
        )
        assert calls == [{"motor_x": 5.0}]
        # A fresh sample is delivered.
        manager.process_jobs(
            {"bank0": 1.0},
            context={"motor_x": 6.0},
            fresh_context={"motor_x"},
            start=T(20),
            end=T(30),
        )
        assert calls == [{"motor_x": 5.0}, {"motor_x": 6.0}]

    def test_context_delivered_after_idle_window(self, registry, manager):
        # Beam-off gap: a window carries ONLY a context update; the idle job
        # (no data, nothing pending) is skipped, but the update must not be
        # lost — it is delivered before the job's next accumulate.
        calls: list[dict] = []

        class CtxWorkflow(CountingWorkflow):
            def set_context(self, ctx):
                calls.append(dict(ctx))
                super().set_context(ctx)

        spec = WorkflowSpec(
            instrument="dummy",
            name="ctx2",
            source_names=["bank0"],
            context_keys=["motor_x"],
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: CtxWorkflow()
        )
        manager.schedule_job(start_config(registry, name="ctx2"))
        manager.process_jobs(
            {"bank0": 1.0},
            context={"motor_x": 5.0},
            fresh_context={"motor_x"},
            start=T(0),
            end=T(10),
        )
        assert calls == [{"motor_x": 5.0}]
        # Context-only window: job idle, value queued.
        manager.process_jobs(
            {},
            context={"motor_x": 7.0},
            fresh_context={"motor_x"},
            start=T(10),
            end=T(20),
        )
        assert calls == [{"motor_x": 5.0}]
        # Data resumes: the queued update arrives before the add.
        manager.process_jobs(
            {"bank0": 1.0},
            context={"motor_x": 7.0},
            fresh_context=set(),
            start=T(20),
            end=T(30),
        )
        assert calls == [{"motor_x": 5.0}, {"motor_x": 7.0}]


class TestFaultContainment:
    """One misbehaving workflow must not take the batch (or other jobs)
    down with it — gate-context, reset, and stale-context delivery paths."""

    def test_failing_gate_set_context_contained(self, registry, manager):
        class BadContextWorkflow(CountingWorkflow):
            def set_context(self, ctx):
                raise ValueError("bad motor value")

        spec = WorkflowSpec(
            instrument="dummy",
            name="badctx",
            source_names=["bank0"],
            context_keys=["motor_x"],
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: BadContextWorkflow()
        )
        manager.schedule_job(start_config(registry, name="badctx"))
        manager.schedule_job(start_config(registry, name="count"))
        results = manager.process_jobs(
            {"bank0": 1.0}, context={"motor_x": 3.5}, start=T(0), end=T(10)
        )
        # The healthy job still produced output; the bad one stays gated
        # with a warning naming the failure.
        assert len(results) == 1
        bad = next(
            s for s in manager.job_statuses() if "badctx" in str(s.workflow_id)
        )
        assert bad.state == JobState.PENDING_CONTEXT
        assert "bad motor value" in bad.message

    def test_failing_clear_on_reset_contained(self, registry, manager):
        class BadClearWorkflow(CountingWorkflow):
            def clear(self):
                raise RuntimeError("device wedged")

        spec = WorkflowSpec(
            instrument="dummy", name="badclear", source_names=["bank0"]
        )
        registry.register_spec(spec).attach_factory(
            lambda *, source_name, params: BadClearWorkflow()
        )
        manager.schedule_job(start_config(registry, name="badclear"))
        manager.schedule_job(start_config(registry, name="count"))
        manager.process_jobs({"bank0": 5.0}, start=T(0), end=T(10))
        manager.handle_run_transition(RunStart(run_name="r2", start_time=T(20)))
        results = manager.process_jobs({"bank0": 1.0}, start=T(20), end=T(30))
        # The healthy job was reset and reprocessed; the wedged job is
        # excluded from processing (old-run data must not mix) and keeps
        # retrying its reset.
        count_rec = next(
            r
            for r in manager._records.values()
            if type(r.job.workflow) is CountingWorkflow
        )
        assert count_rec.job.workflow.clear_calls == 1
        assert len(results) == 1
        bad = next(
            s
            for s in manager.job_statuses()
            if "badclear" in str(s.workflow_id)
        )
        assert "Reset failed" in bad.message
        # Once the workflow recovers, the retry succeeds and processing
        # resumes with a clean state.
        bad_rec = next(
            r
            for r in manager._records.values()
            if type(r.job.workflow) is not CountingWorkflow
        )
        bad_rec.job.workflow.clear = lambda: None
        results = manager.process_jobs({"bank0": 2.0}, start=T(30), end=T(40))
        assert len(results) == 2

    def test_undelivered_stale_context_stays_queued(self, registry, manager):
        manager.schedule_job(start_config(registry, name="gated"))
        # Graduate the job with initial context.
        manager.process_jobs(
            {"bank0": 1.0}, context={"motor_x": 1.0}, start=T(0), end=T(10)
        )
        rec = next(iter(manager._records.values()))
        # Queue two names while the job is active; only motor_x will ever
        # appear in a later window's context.
        rec.stale_context |= {"motor_x", "motor_y"}
        manager.process_jobs(
            {"bank0": 1.0}, context={"motor_x": 2.0}, start=T(10), end=T(20)
        )
        assert rec.job.workflow.context["motor_x"] == 2.0
        # motor_y was not deliverable and must remain queued, not dropped.
        assert rec.stale_context == {"motor_y"}

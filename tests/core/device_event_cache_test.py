"""DeviceEventCache: stage-once semantics, window lifecycle, stats, and
the JobManager's fused stepping over it (ADR 0110)."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.device_event_cache import DeviceEventCache
from esslivedata_tpu.core.job_manager import JobCommand, JobFactory, JobManager
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.ops import EventBatch
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.telemetry import REGISTRY, TRACER
from esslivedata_tpu.workflows import WorkflowFactory
from esslivedata_tpu.workflows.detector_view import (
    DetectorViewWorkflow,
    project_logical,
)

T = Timestamp.from_ns


class TestSlotSemantics:
    def test_stage_runs_once_per_key(self):
        cache = DeviceEventCache()
        cache.begin_window()
        slot = cache.slot("det")
        calls = []
        out1 = slot.get_or_stage("k", lambda: calls.append(1) or "staged")
        out2 = slot.get_or_stage("k", lambda: calls.append(2) or "other")
        assert out1 == out2 == "staged"
        assert calls == [1]
        assert cache.stats()["hits"] == 1
        assert cache.stats()["misses"] == 1

    def test_distinct_keys_stage_separately(self):
        cache = DeviceEventCache()
        cache.begin_window()
        slot = cache.slot("det")
        assert slot.get_or_stage(("a",), lambda: 1) == 1
        assert slot.get_or_stage(("b",), lambda: 2) == 2
        assert cache.stats()["misses"] == 2

    def test_window_boundary_drops_entries(self):
        cache = DeviceEventCache()
        cache.begin_window()
        slot = cache.slot("det")
        slot.get_or_stage("k", lambda: "gen1")
        cache.end_window()
        # The closed slot degrades to a passthrough: a late consumer can
        # never read a stale generation, and nothing new is retained.
        assert slot.get_or_stage("k", lambda: "late") == "late"
        assert "k" not in slot
        cache.begin_window()
        fresh = cache.slot("det")
        assert fresh is not slot
        assert fresh.get_or_stage("k", lambda: "gen2") == "gen2"

    def test_bytes_staged_counts_array_tuples(self):
        cache = DeviceEventCache()
        cache.begin_window()
        slot = cache.slot("det")
        a = np.zeros(100, np.int32)
        b = np.zeros(50, np.float32)
        slot.get_or_stage("pair", lambda: (a, b))
        assert cache.stats()["bytes_staged"] == a.nbytes + b.nbytes

    def test_drain_resets_counters(self):
        cache = DeviceEventCache()
        cache.begin_window()
        cache.slot("s").get_or_stage("k", lambda: np.zeros(4))
        assert cache.drain_stats()["misses"] == 1
        assert cache.stats() == {
            "hits": 0,
            "misses": 0,
            "bytes_staged": 0,
            "staging_s": 0.0,
            "hit_rate": 0.0,
        }

    def test_concurrent_consumers_share_one_staging(self):
        cache = DeviceEventCache()
        cache.begin_window()
        slot = cache.slot("det")
        calls = []
        barrier = threading.Barrier(4)
        results = []

        def consume():
            barrier.wait()
            results.append(
                slot.get_or_stage("k", lambda: calls.append(1) or object())
            )

        threads = [threading.Thread(target=consume) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(calls) == 1
        assert all(r is results[0] for r in results)


class TestStageWait:
    """``stage_wait``: what a stage-once hit waited for the thread that
    stages the entry (BIFROST's second job, for the first's ``h2d``)."""

    @staticmethod
    def totals() -> tuple[float, int]:
        family = REGISTRY.get("livedata_tick_span_seconds")
        return family.sum(span="stage_wait"), family.count(span="stage_wait")

    def test_a_hit_waits_out_the_owner_and_a_late_hit_waits_nothing(self, monkeypatch):
        monkeypatch.setattr(TRACER, "enabled", True)
        cache = DeviceEventCache()
        cache.begin_window()
        slot = cache.slot("det")
        staging, finish = threading.Event(), threading.Event()

        def stage():
            staging.set()
            assert finish.wait(30)
            return "staged"

        owner = threading.Thread(target=slot.get_or_stage, args=("k", stage))
        owner.start()
        assert staging.wait(30)
        before = self.totals()
        remaining = 0.05
        releaser = threading.Timer(remaining, finish.set)
        asked = time.perf_counter()
        releaser.start()
        assert slot.get_or_stage("k", lambda: "never") == "staged"
        stood = time.perf_counter() - asked
        owner.join(timeout=30)
        assert not owner.is_alive()
        waited, count = self.totals()
        assert count == before[1] + 1  # the owner observes none: it is a miss
        assert remaining * 0.9 <= waited - before[0] <= stood
        # the entry is ready: one more hit, one more observation, of ~0
        assert slot.get_or_stage("k", lambda: "never") == "staged"
        late, count = self.totals()
        assert count == before[1] + 2
        assert 0.0 <= late - waited < 0.02
        assert cache.stats()["hits"] == 2 and cache.stats()["misses"] == 1


def _staged(pid: np.ndarray, toa: np.ndarray) -> StagedEvents:
    return StagedEvents(
        batch=EventBatch.from_arrays(pid, toa),
        first_timestamp=None,
        last_timestamp=None,
        n_chunks=1,
    )


@pytest.fixture
def detector_manager():
    det = np.arange(64).reshape(8, 8)
    reg = WorkflowFactory()
    spec = WorkflowSpec(instrument="dummy", name="dv", source_names=["det0"])
    reg.register_spec(spec).attach_factory(
        lambda *, source_name, params: DetectorViewWorkflow(
            projection=project_logical(det)
        )
    )
    return (
        JobManager(job_factory=JobFactory(reg), job_threads=2),
        spec,
        det,
    )


class TestManagedStageOnce:
    def test_k_jobs_one_stream_stage_once(self, detector_manager):
        mgr, spec, det = detector_manager
        for _ in range(3):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        rng = np.random.default_rng(0)
        staged = _staged(
            rng.integers(0, 64, 5000).astype(np.int64),
            rng.uniform(0, 7e7, 5000).astype(np.float32),
        )
        results = mgr.process_jobs({"det0": staged}, start=T(0), end=T(100))
        assert len(results) == 3
        stats = mgr.event_cache_stats()
        # ONE staging for the whole window, however many jobs consumed it
        # (the fused dispatch is the single consumer of the staged array).
        assert stats["misses"] == 1
        imgs = [np.asarray(r.outputs["image_current"].values) for r in results]
        np.testing.assert_array_equal(imgs[0], imgs[1])
        np.testing.assert_array_equal(imgs[0], imgs[2])
        assert imgs[0].sum() == 5000

    def test_fused_matches_private_workflow(self, detector_manager):
        mgr, spec, det = detector_manager
        for _ in range(2):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        reference = DetectorViewWorkflow(projection=project_logical(det))
        rng = np.random.default_rng(7)
        for w in range(3):
            staged = _staged(
                rng.integers(-3, 70, 4000).astype(np.int64),
                rng.uniform(-1e6, 8e7, 4000).astype(np.float32),
            )
            results = mgr.process_jobs(
                {"det0": staged}, start=T(w), end=T(w + 1)
            )
            reference.accumulate({"det0": staged})
            ref_out = reference.finalize()
            for result in results:
                for name, da in ref_out.items():
                    np.testing.assert_array_equal(
                        np.asarray(result.outputs[name].values),
                        np.asarray(da.values),
                        err_msg=f"output {name} diverged in window {w}",
                    )

    def test_remove_command_invalidates_cache(self, detector_manager):
        mgr, spec, det = detector_manager
        mgr.schedule_job(
            WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name="det0")
            )
        )
        # Smoke: the invalidation hook must not disturb processing.
        assert mgr.handle_command(JobCommand(action="remove")) == 1
        assert mgr.process_jobs({}, end=T(10)) == []


class TestWindowGenerations:
    """Caller-owned generations (pipelined ingest, ADR 0111): overlapped
    windows must never alias each other's slots, and a closed
    generation degrades to passthrough."""

    def test_generations_are_independent(self):
        cache = DeviceEventCache()
        gen_a = cache.new_generation()
        gen_b = cache.new_generation()
        a = gen_a.slot("s").get_or_stage("k", lambda: np.arange(3))
        b = gen_b.slot("s").get_or_stage("k", lambda: np.arange(3) * 2)
        np.testing.assert_array_equal(a, [0, 1, 2])
        np.testing.assert_array_equal(b, [0, 2, 4])
        # Closing one generation leaves the other's slots warm.
        gen_a.close()
        again = gen_b.slot("s").get_or_stage("k", lambda: np.arange(3) * 9)
        np.testing.assert_array_equal(again, b)

    def test_closed_generation_is_passthrough(self):
        cache = DeviceEventCache()
        gen = cache.new_generation()
        gen.close()
        out = gen.slot("s").get_or_stage("k", lambda: np.arange(2))
        np.testing.assert_array_equal(out, [0, 1])
        # Nothing retained: a second call re-stages.
        out2 = gen.slot("s").get_or_stage("k", lambda: np.arange(2) + 5)
        np.testing.assert_array_equal(out2, [5, 6])

    def test_begin_window_does_not_touch_caller_generations(self):
        cache = DeviceEventCache()
        gen = cache.new_generation()
        gen.slot("s").get_or_stage("k", lambda: np.arange(4))
        cache.begin_window()  # serial path churns the current generation
        hit = gen.slot("s").get_or_stage("k", lambda: np.arange(4) * 7)
        np.testing.assert_array_equal(hit, [0, 1, 2, 3])


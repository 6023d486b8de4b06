"""Cross-job publish combining (ADR 0113): parity, containment, statics.

The PublishCombiner inverts publish ownership (job-private round trips
-> one execute + one packed fetch per device per tick) and the
static/dynamic split serves layout-constant outputs from a host cache.
Neither may change a single byte of the da00 wire output, and a failure
in one member must never poison the others — pinned here through the
REAL JobManager path (extends the cache_parity_test pattern).
"""

from __future__ import annotations

import numpy as np

from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.job_manager import JobFactory, JobManager
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
from esslivedata_tpu.kafka.wire import encode_da00
from esslivedata_tpu.ops import EventBatch
from esslivedata_tpu.ops.publish import (
    METRICS,
    PackedPublisher,
    PublishCombiner,
    PublishRequest,
)
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.workflows.detector_view import (
    DetectorViewWorkflow,
    project_logical,
)
from esslivedata_tpu.workflows.monitor_workflow import MonitorWorkflow

T = Timestamp.from_ns


def _staged(pid, toa) -> StagedEvents:
    return StagedEvents(
        batch=EventBatch.from_arrays(
            np.asarray(pid), np.asarray(toa, np.float32)
        ),
        first_timestamp=None,
        last_timestamp=None,
        n_chunks=1,
    )


def _windows(rng, n_windows, n_events, id_lo, id_hi):
    return [
        (
            rng.integers(id_lo, id_hi, n_events).astype(np.int64),
            rng.uniform(-1e6, 8e7, n_events).astype(np.float32),
        )
        for _ in range(n_windows)
    ]


def _make_manager(
    make_workflows, stream="det0", *, combine_publish=True, job_threads=2
):
    """A JobManager with one job per workflow factory in
    ``make_workflows``; returns (manager, created workflow instances).

    ``tick_program=False``: this suite pins the ADR 0113 PublishCombiner
    path — with the ADR 0114 tick program on (the default), tick-eligible
    groups would route around the combiner and these tests would stop
    covering the production escape hatch (``--no-tick-program``) and the
    fallback every non-tick-eligible group takes. The tick path has its
    own suite (tick_program_test.py)."""
    from esslivedata_tpu.workflows import WorkflowFactory

    created = []
    reg = WorkflowFactory()
    identifiers = []
    for i, make in enumerate(make_workflows):
        spec = WorkflowSpec(
            instrument="test", name=f"combine{i}", source_names=[stream]
        )

        def factory(*, source_name, params, _make=make):
            wf = _make()
            created.append(wf)
            return wf

        reg.register_spec(spec).attach_factory(factory)
        identifiers.append(spec.identifier)
    mgr = JobManager(
        job_factory=JobFactory(reg),
        job_threads=job_threads,
        combine_publish=combine_publish,
        tick_program=False,
    )
    for identifier in identifiers:
        mgr.schedule_job(
            WorkflowConfig(
                identifier=identifier, job_id=JobId(source_name=stream)
            )
        )
    return mgr, created


def _wire_bytes(result) -> list[bytes]:
    """da00 wire encoding of every output of one JobResult, at a fixed
    timestamp and keyed by output name (the full ResultKey embeds the
    job uuid, which legitimately differs between managers) — the
    byte-identity oracle."""
    return [
        encode_da00(name, 12345, dataarray_to_da00(da))
        for name, da in result.outputs.items()
    ]


class TestCombinedVsPerJobParity:
    def test_byte_identical_da00_wire_output(self):
        det = np.arange(144).reshape(12, 12)
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det)),
            lambda: DetectorViewWorkflow(projection=project_logical(det)),
            lambda: MonitorWorkflow(),
            lambda: MonitorWorkflow(),
        ]
        combined, _ = _make_manager(makes)
        private, _ = _make_manager(makes, combine_publish=False)
        rng = np.random.default_rng(31)
        windows = _windows(rng, 4, 3000, -5, 150)
        for w, (pid, toa) in enumerate(windows):
            data = {"det0": _staged(pid, toa)}
            data_p = {"det0": _staged(pid, toa)}
            res_c = combined.process_jobs(data, start=T(0), end=T(w + 1))
            res_p = private.process_jobs(data_p, start=T(0), end=T(w + 1))
            assert len(res_c) == len(res_p) == 4
            for rc, rp in zip(res_c, res_p):
                assert rc.workflow_id == rp.workflow_id
                assert list(rc.outputs) == list(rp.outputs)
                for bc, bp in zip(_wire_bytes(rc), _wire_bytes(rp)):
                    assert bc == bp, (
                        f"window {w}: combined da00 wire != per-job wire"
                    )
        combined.shutdown()
        private.shutdown()

    def test_one_round_trip_per_tick(self):
        det = np.arange(144).reshape(12, 12)
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det))
        ] * 3
        mgr, _ = _make_manager(makes)
        rng = np.random.default_rng(32)
        windows = _windows(rng, 4, 2000, -5, 150)
        # Warm: static fetch + both program variants compile.
        for w in range(2):
            pid, toa = windows[w]
            assert len(
                mgr.process_jobs(
                    {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
                )
            ) == 3
        METRICS.drain()
        for w in (2, 3):
            pid, toa = windows[w]
            res = mgr.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
            assert len(res) == 3
        m = METRICS.drain()
        assert m["executes"] == 2 and m["fetches"] == 2  # one per tick
        assert m["combined_jobs"] == 6  # 3 jobs x 2 ticks
        assert m["static_bytes"] == 0  # statics served from host cache
        mgr.shutdown()


class TestPerJobErrorContainment:
    def test_bad_offer_does_not_poison_the_group(self):
        det = np.arange(144).reshape(12, 12)
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det))
        ] * 3
        mgr, created = _make_manager(makes)
        # Job 1's offer raises: it must fall back to the private publish
        # while jobs 0 and 2 still combine — and all three still publish.
        def bad_offer():
            raise RuntimeError("offer exploded")

        created[1].publish_offer = bad_offer
        rng = np.random.default_rng(33)
        pid, toa = _windows(rng, 1, 2000, -5, 150)[0]
        res = mgr.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(1)
        )
        assert len(res) == 3
        statuses = {s.state for s in mgr.job_statuses()}
        assert "error" not in {str(s) for s in statuses}
        mgr.shutdown()

    def test_bad_unpack_contained_per_member(self):
        """Combiner level: a corrupted member spec fails only that
        member; the other member's outputs and carry are intact."""
        import jax.numpy as jnp

        def make(n):
            def program(state):
                return {"win": state, "cum": state * 2}, state + 1

            return PackedPublisher(program)

        good, bad = make(4), make(4)
        s_good, s_bad = jnp.zeros(4), jnp.ones(4)
        # Poison bad's cached spec: the unpack reshape cannot satisfy it.
        sig = bad._signature((s_bad,))
        bad._spec_by_sig[(sig, frozenset())] = (
            [("win", (3,), 5), ("cum", (4,), 4)],
            (),
        )
        combiner = PublishCombiner()
        res = combiner.publish(
            [
                PublishRequest(good, (s_good,)),
                PublishRequest(bad, (s_bad,)),
            ]
        )
        assert res[0].error is None
        np.testing.assert_array_equal(
            res[0].outputs["win"], np.zeros(4, np.float32)
        )
        assert res[1].error is not None and not res[1].state_lost
        assert res[1].carry  # the folded carry survives for adoption

    def test_trace_failure_contained_at_plan_time(self):
        """A publish program that raises at abstract-evaluation time
        (bad restored state, first-publish workflow bug) errors ONLY its
        member — the rest of the tick still combines, and nothing
        escapes toward the step worker."""
        import jax.numpy as jnp

        def good_program(state):
            return {"win": state}, state + 1

        def bad_program(state):
            raise ValueError("trace-time explosion")

        good = PackedPublisher(good_program)
        bad = PackedPublisher(bad_program)
        combiner = PublishCombiner()
        res = combiner.publish(
            [
                PublishRequest(bad, (jnp.ones(4),)),
                PublishRequest(good, (jnp.zeros(4),)),
            ]
        )
        assert res[0].error is not None and not res[0].state_lost
        assert res[1].error is None
        np.testing.assert_array_equal(
            res[1].outputs["win"], np.zeros(4, np.float32)
        )

    def test_finalize_failure_is_per_job(self):
        det = np.arange(144).reshape(12, 12)
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det))
        ] * 2
        mgr, created = _make_manager(makes)

        def boom():
            raise ValueError("finalize exploded")

        created[1].finalize = boom
        rng = np.random.default_rng(34)
        pid, toa = _windows(rng, 1, 2000, -5, 150)[0]
        res = mgr.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(1)
        )
        assert len(res) == 1  # job 0 published
        states = [str(s.state) for s in mgr.job_statuses()]
        assert states.count("error") == 1
        mgr.shutdown()


class TestStaticCache:
    def test_static_fetched_once_then_served_from_cache(self):
        det = np.arange(144).reshape(12, 12)
        wf = DetectorViewWorkflow(projection=project_logical(det))
        rng = np.random.default_rng(35)
        pid, toa = _windows(rng, 1, 2000, -5, 150)[0]
        METRICS.drain()
        wf.accumulate({"det0": _staged(pid, toa)})
        wf.finalize()
        first = METRICS.drain()
        assert first["static_bytes"] > 0  # the zero ROI blocks, once
        wf.accumulate({"det0": _staged(pid, toa)})
        out = wf.finalize()
        second = METRICS.drain()
        assert second["static_bytes"] == 0
        # Served-from-cache statics are still present and correct.
        np.testing.assert_array_equal(
            np.asarray(out["spectrum_current"].values).sum(),
            np.asarray(out["counts_current"].values),
        )

    def test_invalidation_on_layout_digest_change(self):
        det = np.arange(144).reshape(12, 12)
        wf = DetectorViewWorkflow(projection=project_logical(det))
        rng = np.random.default_rng(36)
        pid, toa = _windows(rng, 1, 2000, -5, 150)[0]
        wf.accumulate({"det0": _staged(pid, toa)})
        wf.finalize()
        old_digest = wf.histogrammer.layout_digest
        # Live-geometry move: same shape, permuted LUT -> new digest.
        table = project_logical(det)
        perm = np.random.default_rng(37).permutation(144)
        table.lut[0] = table.lut[0][perm]
        assert wf.swap_projection(table)
        assert wf.histogrammer.layout_digest != old_digest
        METRICS.drain()
        wf.accumulate({"det0": _staged(pid, toa)})
        wf.finalize()
        m = METRICS.drain()
        assert m["static_bytes"] > 0  # refetched under the new digest

    def test_rois_flip_statics_dynamic(self):
        from esslivedata_tpu.config.models import RectangleROI

        det = np.arange(144).reshape(12, 12)
        wf = DetectorViewWorkflow(projection=project_logical(det))
        assert wf._publish.static_keys
        wf.set_rois(
            {"roi_0": RectangleROI(x_min=0, x_max=5, y_min=0, y_max=5)}
        )
        assert not wf._publish.static_keys  # spectra now carry data
        rng = np.random.default_rng(38)
        pid, toa = _windows(rng, 1, 2000, -5, 150)[0]
        METRICS.drain()
        wf.accumulate({"det0": _staged(pid, toa)})
        out = wf.finalize()
        m = METRICS.drain()
        assert m["static_bytes"] == 0  # everything rides the dynamic pack
        assert "roi_spectra" in out
        wf.set_rois({})
        assert wf._publish.static_keys  # zero blocks are static again


class TestPendingAccumulationFlushes:
    def _mgr(self):
        det = np.arange(144).reshape(12, 12)
        return _make_manager(
            [lambda: DetectorViewWorkflow(projection=project_logical(det))],
            job_threads=1,
        )

    def test_idle_flush_publishes_immediately(self):
        mgr, created = self._mgr()
        rng = np.random.default_rng(40)
        pid, toa = _windows(rng, 1, 1000, 0, 144)[0]
        # The window's finalize fails once: its accumulation stays
        # pending (``has_primary_data``) with the job in error.
        wf = created[0]
        real_finalize = wf.finalize

        def failing_finalize():
            wf.finalize = real_finalize
            raise RuntimeError("sink hiccup")

        wf.finalize = failing_finalize
        assert mgr.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(1)
        ) == []
        # Idle tick (no data): the pending accumulation must flush — a
        # stop during beam-off cannot wait for the next data window.
        res = mgr.process_jobs({})
        assert len(res) == 1
        ref, _ = self._mgr()
        want = ref.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(1)
        )[0].outputs["counts_cumulative"].values
        assert res[0].outputs["counts_cumulative"].values == want > 0
        assert mgr.process_jobs({}) == []  # nothing pending any more
        ref.shutdown()
        mgr.shutdown()

    def test_finishing_job_forces_the_tick(self):
        from esslivedata_tpu.core.job_manager import JobCommand

        mgr, _ = self._mgr()
        rng = np.random.default_rng(41)
        windows = _windows(rng, 2, 1000, 0, 144)
        assert len(mgr.process_jobs(
            {"det0": _staged(*windows[0])}, start=T(0), end=T(1)
        )) == 1
        assert mgr.handle_command(JobCommand(action="stop")) == 1
        res = mgr.process_jobs(
            {"det0": _staged(*windows[1])}, start=T(0), end=T(2)
        )
        assert len(res) == 1  # the window that carried the stop flushed
        assert not mgr.has_finishing_jobs()
        mgr.shutdown()

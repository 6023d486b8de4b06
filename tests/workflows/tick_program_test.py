"""One-dispatch tick programs (ADR 0114): parity, metrics, containment.

The TickCombiner fuses the fused event step and the combined packed
publish into ONE jitted dispatch + ONE fetch per (stream, fuse-key)
group. That may not change a single byte of the da00 wire output vs the
separate-dispatch path, must actually collapse the dispatch count, and
must contain failures per member exactly like the combiner it subsumes
— pinned here through the REAL JobManager path (extends the
publish_combine_test pattern).
"""

from __future__ import annotations

import time

import jax
import numpy as np
import pytest

from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.job_manager import JobFactory, JobManager
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
from esslivedata_tpu.kafka.wire import encode_da00
from esslivedata_tpu.ops import EventBatch
from esslivedata_tpu.ops.publish import METRICS
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.workflows.detector_view import (
    DetectorViewParams,
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
    make_workflows,
    stream="det0",
    *,
    streams=None,
    combine_publish=True,
    tick_program=True,
    job_threads=2,
):
    """One job per workflow, all on ``stream`` or each on its own of
    ``streams``."""
    from esslivedata_tpu.workflows import WorkflowFactory

    created = []
    reg = WorkflowFactory()
    jobs = []
    for i, make in enumerate(make_workflows):
        job_stream = stream if streams is None else streams[i]
        spec = WorkflowSpec(
            instrument="test", name=f"tick{i}", source_names=[job_stream]
        )

        def factory(*, source_name, params, _make=make):
            wf = _make()
            created.append(wf)
            return wf

        reg.register_spec(spec).attach_factory(factory)
        jobs.append((spec.identifier, job_stream))
    mgr = JobManager(
        job_factory=JobFactory(reg),
        job_threads=job_threads,
        combine_publish=combine_publish,
        tick_program=tick_program,
    )
    for identifier, job_stream in jobs:
        mgr.schedule_job(
            WorkflowConfig(
                identifier=identifier,
                job_id=JobId(source_name=job_stream),
            )
        )
    return mgr, created


def _wire_bytes(result) -> list[bytes]:
    return [
        encode_da00(name, 12345, dataarray_to_da00(da))
        for name, da in result.outputs.items()
    ]


def _det():
    return np.arange(144).reshape(12, 12)


class TestTickVsThreeDispatchParity:
    def test_byte_identical_da00_wire_output(self):
        """Two tick groups (detector views + row0-clamped monitors) vs
        the separate fused-step + combined-publish path vs the fully
        private path: every da00 byte identical, every window."""
        det = _det()
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det)),
            lambda: DetectorViewWorkflow(projection=project_logical(det)),
            lambda: MonitorWorkflow(),
            lambda: MonitorWorkflow(),
        ]
        tick, _ = _make_manager(makes)
        combined, _ = _make_manager(makes, tick_program=False)
        private, _ = _make_manager(
            makes, combine_publish=False, tick_program=False
        )
        rng = np.random.default_rng(51)
        windows = _windows(rng, 4, 3000, -5, 150)
        for w, (pid, toa) in enumerate(windows):
            res_t = tick.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
            res_c = combined.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
            res_p = private.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
            assert len(res_t) == len(res_c) == len(res_p) == 4
            for rt, rc, rp in zip(res_t, res_c, res_p):
                assert rt.workflow_id == rc.workflow_id == rp.workflow_id
                assert list(rt.outputs) == list(rc.outputs) == list(rp.outputs)
                bt, bc, bp = (
                    _wire_bytes(rt), _wire_bytes(rc), _wire_bytes(rp)
                )
                assert bt == bc, f"window {w}: tick wire != combined wire"
                assert bt == bp, f"window {w}: tick wire != private wire"
        for mgr in (tick, combined, private):
            mgr.shutdown()

    def test_one_dispatch_per_tick(self):
        """Steady state at K=3 same-layout jobs: exactly one execute +
        one fetch per tick, ZERO separate step dispatches, every window
        served by a tick program, statics from the host cache."""
        det = _det()
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det))
        ] * 3
        mgr, _ = _make_manager(makes)
        rng = np.random.default_rng(52)
        windows = _windows(rng, 4, 2000, -5, 150)
        # Warm: static fetch + both tick-program variants compile.
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
        assert m["step_executes"] == 0  # the step rode the tick program
        assert m["tick_publishes"] == 2 and m["tick_jobs"] == 6
        assert m["static_bytes"] == 0  # statics served from host cache
        mgr.shutdown()

    def test_tick_disabled_keeps_separate_dispatches(self):
        det = _det()
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det))
        ] * 3
        mgr, _ = _make_manager(makes, tick_program=False)
        rng = np.random.default_rng(53)
        windows = _windows(rng, 3, 2000, -5, 150)
        for w in range(2):
            pid, toa = windows[w]
            mgr.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
        METRICS.drain()
        pid, toa = windows[2]
        mgr.process_jobs({"det0": _staged(pid, toa)}, start=T(0), end=T(3))
        m = METRICS.drain()
        assert m["tick_publishes"] == 0
        assert m["step_executes"] == 1  # the separate fused step
        assert m["executes"] == 1 and m["fetches"] == 1
        mgr.shutdown()

class TestContextOrdering:
    def test_fresh_context_windows_bypass_the_tick(self):
        """A window that carries a fresh context update for a job never
        ticks (the stale-context guard is inherited from the fused-step
        planner): context applies before accumulate and publish, so a
        position move clears accumulation identically on the tick and
        separate-dispatch paths — bit-for-bit."""
        from esslivedata_tpu.config import WorkflowSpec
        from esslivedata_tpu.utils.labeled import DataArray, Variable
        from esslivedata_tpu.workflows import WorkflowFactory
        from esslivedata_tpu.workflows.monitor_workflow import MonitorParams

        def make_mgr(tick):
            reg = WorkflowFactory()
            spec = WorkflowSpec(
                instrument="test",
                name=f"monctx{int(tick)}",
                source_names=["mon0"],
                optional_context_keys=("mon_pos",),
            )

            def fac(*, source_name, params):
                return MonitorWorkflow(
                    params=MonitorParams(position_tolerance=0.1),
                    position_stream="mon_pos",
                )

            reg.register_spec(spec).attach_factory(fac)
            mgr = JobManager(
                job_factory=JobFactory(reg), job_threads=1,
                tick_program=tick,
            )
            for _ in range(2):
                mgr.schedule_job(
                    WorkflowConfig(
                        identifier=spec.identifier,
                        job_id=JobId(source_name="mon0"),
                    )
                )
            return mgr

        def pos_sample(value):
            return DataArray(
                Variable(np.asarray([value]), ("time",), "mm"),
                coords={"time": Variable(np.asarray([0]), ("time",), "ns")},
            )

        outs = {}
        ticked = {}
        for tick in (True, False):
            rng = np.random.default_rng(62)  # identical windows per run
            mgr = make_mgr(tick)
            counts = []
            METRICS.drain()
            for w in range(5):
                pid = rng.integers(0, 4, 500).astype(np.int64)
                toa = rng.uniform(0, 7e7, 500).astype(np.float32)
                ctx, fresh = {}, set()
                if w == 1:  # anchor position
                    ctx, fresh = {"mon_pos": pos_sample(0.0)}, {"mon_pos"}
                if w == 3:  # MOVE beyond tolerance -> must clear
                    ctx, fresh = {"mon_pos": pos_sample(99.0)}, {"mon_pos"}
                res = mgr.process_jobs(
                    {"mon0": _staged(pid, toa)},
                    context=ctx,
                    fresh_context=fresh,
                    start=T(0),
                    end=T(w + 1),
                )
                counts.append(
                    [
                        float(r.outputs["counts_cumulative"].values)
                        for r in res
                    ]
                )
            outs[tick] = counts
            ticked[tick] = METRICS.drain()["tick_publishes"]
            mgr.shutdown()
        assert outs[True] == outs[False]
        # The move window published the CLEARED accumulation: context
        # was delivered before accumulate and publish.
        assert outs[True][3] == [500.0, 500.0]
        # Windows 1 and 3 carried queued context and stayed off the
        # tick; the other three ticked.
        assert ticked[True] == 3 and ticked[False] == 0


class TestStaticOutputs:
    def test_static_fetched_once_then_served_from_cache(self):
        det = _det()
        mgr, _ = _make_manager(
            [lambda: DetectorViewWorkflow(projection=project_logical(det))]
            * 2,
        )
        rng = np.random.default_rng(55)
        windows = _windows(rng, 3, 2000, -5, 150)
        METRICS.drain()
        pid, toa = windows[0]
        mgr.process_jobs({"det0": _staged(pid, toa)}, start=T(0), end=T(1))
        first = METRICS.drain()
        assert first["tick_publishes"] == 1
        assert first["static_bytes"] > 0  # the zero ROI blocks, once
        for w in (1, 2):
            pid, toa = windows[w]
            mgr.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
        later = METRICS.drain()
        assert later["tick_publishes"] == 2
        assert later["static_bytes"] == 0
        mgr.shutdown()

    def test_layout_digest_swap_refetches_statics(self):
        """A live-geometry LUT swap re-keys the tick program (the fuse
        key carries the layout digest) and misses the static cache under
        the new token — statics refetch exactly once."""
        det = _det()
        mgr, created = _make_manager(
            [lambda: DetectorViewWorkflow(projection=project_logical(det))]
            * 2,
        )
        rng = np.random.default_rng(56)
        windows = _windows(rng, 3, 2000, -5, 150)
        pid, toa = windows[0]
        mgr.process_jobs({"det0": _staged(pid, toa)}, start=T(0), end=T(1))
        old_digest = created[0].histogrammer.layout_digest
        perm = np.random.default_rng(57).permutation(144)
        for wf in created:
            table = project_logical(det)
            table.lut[0] = table.lut[0][perm]
            assert wf.swap_projection(table)
        assert created[0].histogrammer.layout_digest != old_digest
        METRICS.drain()
        pid, toa = windows[1]
        res = mgr.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(2)
        )
        assert len(res) == 2
        m = METRICS.drain()
        assert m["tick_publishes"] == 1  # the swapped layout still ticks
        assert m["static_bytes"] > 0  # refetched under the new digest
        pid, toa = windows[2]
        mgr.process_jobs({"det0": _staged(pid, toa)}, start=T(0), end=T(3))
        assert METRICS.drain()["static_bytes"] == 0
        mgr.shutdown()


class TestWireFormats:
    @pytest.mark.parametrize(
        ("toa_bins", "compact"), [(100, True), (64, False)]
    )
    def test_either_wire_stays_bit_identical(self, toa_bins, compact):
        """The partitioned wire is chosen at construction: uint16 block
        offsets where a block of bins fits them (100 TOA bins: blocks
        of 51 200), int32 where it does not (64: blocks of 65 536).
        On either, the tick program's counts are bit-identical to the
        separate-dispatch reference."""
        det = _det()

        def make():
            return DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(
                    histogram_method="pallas2d", toa_bins=toa_bins
                ),
            )

        tick, created_t = _make_manager([make] * 2)
        ref, created_r = _make_manager([make] * 2, tick_program=False)
        for wf in (*created_t, *created_r):
            assert wf.histogrammer.fuse_key[1] == "pallas2d"
            assert wf.histogrammer.partition_key[-1] is compact
        rng = np.random.default_rng(58)
        METRICS.drain()
        for w, (pid, toa) in enumerate(_windows(rng, 3, 1000, 0, 144)):
            res_t = tick.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
            res_r = ref.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
            assert len(res_t) == len(res_r) == 2
            for rt, rr in zip(res_t, res_r):
                for bt, br in zip(_wire_bytes(rt), _wire_bytes(rr)):
                    assert bt == br, f"window {w}: the wires disagree"
        assert METRICS.drain()["tick_publishes"] == 3
        states = {str(s.state) for s in tick.job_statuses()}
        assert "error" not in states
        tick.shutdown()
        ref.shutdown()


class TestMxuCount:
    @pytest.mark.parametrize("replicas", [1, 4], ids=["flat_wire", "replica_lut"])
    def test_the_tick_program_counts_as_a_private_step_and_as_the_scatter(
        self, monkeypatch, replicas
    ):
        """A view built where ``auto`` observes a TPU takes the MXU count
        (ADR 0131): on the flat wire, and on a replica LUT's device path
        (four replicas, entries off screen). Its tick program's da00
        bytes equal the separate-dispatch path's, window by window, and
        the scatter's views."""
        import dataclasses
        from unittest import mock

        from esslivedata_tpu.ops import pallas_hist2d

        monkeypatch.setattr(pallas_hist2d, "COUNT_BPB", 2048)
        monkeypatch.setattr(pallas_hist2d, "COUNT_CHUNK", 256)
        monkeypatch.setattr(pallas_hist2d, "MAX_MXU_BINS", 2048)
        det = _det()
        table = project_logical(det)
        if replicas > 1:
            rng = np.random.default_rng(60)
            lut = np.stack(
                [table.lut[0]] + [rng.permutation(table.lut[0]) for _ in range(3)]
            )
            lut[:, rng.random(lut.shape[1]) < 0.1] = -1
            table = dataclasses.replace(table, lut=lut.astype(np.int32))

        def make(backend):
            def build():
                # only the construction observes the TPU: the kernel's
                # interpret mode is read at trace time, on the CPU
                with mock.patch.object(jax, "default_backend", lambda: backend):
                    return DetectorViewWorkflow(projection=table)

            return build

        tick, created_t = _make_manager([make("tpu")])
        ref, created_r = _make_manager([make("tpu")], tick_program=False)
        scat, created_s = _make_manager([make("cpu")])
        for wf in (*created_t, *created_r):
            assert wf.histogrammer.fuse_key[1] == "mxu"
        assert created_s[0].histogrammer.fuse_key[1] == "scatter"
        rng = np.random.default_rng(61)
        METRICS.drain()
        for w, (pid, toa) in enumerate(_windows(rng, 3, 1000, -3, 150)):
            res = [
                m.process_jobs({"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1))
                for m in (tick, ref, scat)
            ]
            assert all(len(r) == 1 for r in res)
            wires = [_wire_bytes(r[0]) for r in res]
            assert wires[0] == wires[1], f"window {w}: tick and private disagree"
            assert wires[0] == wires[2], f"window {w}: mxu and scatter disagree"
        assert METRICS.drain()["tick_publishes"] == 6
        for m in (tick, ref, scat):
            m.shutdown()


class TestContainment:
    def test_state_lost_on_post_donation_dispatch_failure(self):
        """A dispatch that fails AFTER consuming the donated states
        resets exactly the affected group's members (fresh zeroed
        accumulation, job still publishes) and recovers on the next
        window; the other tick group is untouched."""
        det = _det()
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det)),
            lambda: DetectorViewWorkflow(projection=project_logical(det)),
            lambda: MonitorWorkflow(),
            lambda: MonitorWorkflow(),
        ]
        mgr, _ = _make_manager(makes)
        rng = np.random.default_rng(59)
        windows = _windows(rng, 4, 1000, 1, 144)
        # Two warm windows: both tick-program variants (static-inclusive
        # and dynamic-only) compile, so the poisoned entries below are
        # the ones the failure window actually hits.
        for w in range(2):
            pid, toa = windows[w]
            res = mgr.process_jobs(
                {"det0": _staged(pid, toa)}, start=T(0), end=T(w + 1)
            )
            assert len(res) == 4
        w1_monitor_cum = float(res[2].outputs["counts_cumulative"].values)

        # Poison ONLY the detector-view group's cached tick programs
        # (group key tag "" — the monitors' carry the row0-clamp tag):
        # each runs the real dispatch, consuming the donated states,
        # then raises — the post-donation failure mode.
        combiner = mgr._tick_combiner
        detector_keys = [
            key for key in combiner._programs if key[1][-1] == ""
        ]
        assert detector_keys
        saved = {k: combiner._programs[k] for k in detector_keys}

        def poison(fn):
            def boom(*args):
                fn(*args)
                raise RuntimeError("post-donation boom")

            return boom

        for k in detector_keys:
            combiner._programs[k] = poison(combiner._programs[k])
        pid, toa = windows[2]
        res = mgr.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(3)
        )
        # Every job still publishes: the detector members fell back to
        # the private path over FRESH states (cumulative == this window
        # only — the pre-failure accumulation was consumed), the
        # monitors ticked normally (cumulative keeps both windows).
        assert len(res) == 4
        det_cur = float(res[0].outputs["counts_current"].values)
        det_cum = float(res[0].outputs["counts_cumulative"].values)
        assert det_cum == det_cur  # reset: windows 0-1 are gone
        mon_cum = float(res[2].outputs["counts_cumulative"].values)
        assert mon_cum > w1_monitor_cum  # other group unaffected
        states = {str(s.state) for s in mgr.job_statuses()}
        assert "error" not in states

        # Recovery: restore the programs; the next window ticks again
        # and accumulates on top of the rebuilt state.
        combiner._programs.update(saved)
        METRICS.drain()
        pid, toa = windows[3]
        res = mgr.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(4)
        )
        assert len(res) == 4
        m = METRICS.drain()
        assert m["tick_publishes"] == 2  # both groups tick again
        det_cum3 = float(res[0].outputs["counts_cumulative"].values)
        assert det_cum3 > det_cum
        mgr.shutdown()

    def test_member_plan_failure_falls_back_privately(self):
        """A member whose publish program fails abstract evaluation
        drops out of the tick; it still accumulates and publishes via
        its private path while the rest of the group ticks."""
        det = _det()
        makes = [
            lambda: DetectorViewWorkflow(projection=project_logical(det))
        ] * 3
        mgr, created = _make_manager(makes)

        def bad_offer():
            raise RuntimeError("offer exploded")

        created[1].publish_offer = bad_offer
        rng = np.random.default_rng(60)
        pid, toa = _windows(rng, 1, 1000, 0, 144)[0]
        res = mgr.process_jobs(
            {"det0": _staged(pid, toa)}, start=T(0), end=T(1)
        )
        assert len(res) == 3
        states = {str(s.state) for s in mgr.job_statuses()}
        assert "error" not in states
        mgr.shutdown()


class TestProgramNamesAndScopes:
    """What the device trace calls the tick program and its phases
    (ADR 0116): a stable name per workflow family in place of
    ``jit_tick``, and ``jax.named_scope`` on the step, the fold, each
    member's reductions and the pack. Op metadata only: the parity
    tests above hold with them in."""

    def lowered_programs(self, makes, monkeypatch) -> dict[str, str]:
        from esslivedata_tpu.ops.tick import TickCombiner

        texts: dict[str, str] = {}
        build = TickCombiner._build

        def recording_build(self, hist, n_staged, members):
            fn = build(self, hist, n_staged, members)

            def run(*args):
                texts[fn.__name__] = fn.lower(*args).as_text(debug_info=True)
                return fn(*args)

            return run

        monkeypatch.setattr(TickCombiner, "_build", recording_build)
        mgr, _ = _make_manager(makes)
        rng = np.random.default_rng(53)
        (pid, toa), = _windows(rng, 1, 500, -5, 150)
        mgr.process_jobs({"det0": _staged(pid, toa)}, start=T(0), end=T(1))
        mgr.shutdown()
        return texts

    def test_tick_program_is_named_for_its_family(self, monkeypatch):
        det = _det()
        texts = self.lowered_programs(
            [
                lambda: DetectorViewWorkflow(projection=project_logical(det)),
                lambda: DetectorViewWorkflow(projection=project_logical(det)),
                lambda: MonitorWorkflow(),
            ],
            monkeypatch,
        )
        assert set(texts) == {"tick_detector_view", "tick_monitor"}
        for name, text in texts.items():
            assert f"jit({name})" in text

    def test_phases_are_scoped_in_the_lowered_program(self, monkeypatch):
        det = _det()
        texts = self.lowered_programs(
            [lambda: DetectorViewWorkflow(projection=project_logical(det))],
            monkeypatch,
        )
        text = texts["tick_detector_view"]
        for scope in ("scatter", "publish_reduce", "fold", "pack"):
            assert f"/{scope}/" in text, f"no op carries the scope {scope!r}"
        # The fold runs inside the member's publish program.
        assert "publish_reduce/fold/" in text

    def test_program_name_joins_the_families_of_a_mixed_group(self):
        from esslivedata_tpu.ops.publish import PackedPublisher, program_name

        pubs = [
            PackedPublisher(lambda s: ({}, s), name="monitor"),
            PackedPublisher(lambda s: ({}, s), name="detector_view"),
            PackedPublisher(lambda s: ({}, s), name="monitor"),
            PackedPublisher(lambda s: ({}, s)),
        ]
        assert (
            program_name("tick", pubs) == "tick_detector_view_monitor_publish"
        )
        assert program_name("publish", pubs[:1]) == "publish_monitor"


def _make_group_manager(n_groups):
    """``n_groups`` detector views, each on a stream of its own: one
    tick group each (the shape of NMX's three panels and DREAM's
    seven views)."""
    det = _det()
    return _make_manager(
        [lambda: DetectorViewWorkflow(projection=project_logical(det))]
        * n_groups,
        streams=[f"det{g}" for g in range(n_groups)],
    )


def _group_windows(seed, n_windows, n_groups):
    rng = np.random.default_rng(seed)
    return [
        {
            f"det{g}": _windows(rng, 1, 800, -5, 150)[0]
            for g in range(n_groups)
        }
        for _ in range(n_windows)
    ]


def _process(mgr, window, w):
    return mgr.process_jobs(
        {stream: _staged(pid, toa) for stream, (pid, toa) in window.items()},
        start=T(0),
        end=T(w + 1),
    )


def _group_counts() -> dict[str, float]:
    from esslivedata_tpu.telemetry.registry import REGISTRY

    family = REGISTRY.get("livedata_tick_groups_total")
    return {
        how: family.value(dispatched=how) for how in ("ahead", "alone")
    }


def _counts_added(before) -> dict[str, float]:
    after = _group_counts()
    return {how: after[how] - before[how] for how in after}


def _record_calls(combiner, calls):
    """Wrap the combiner's two halves so that ``calls`` reads the order
    the manager made them in: ("dispatch" | "collect", group number in
    dispatch order)."""
    dispatch, collect = combiner.dispatch, combiner.collect
    numbers: dict[int, int] = {}

    def recording_dispatch(*args, **kwargs):
        pending = dispatch(*args, **kwargs)
        numbers[id(pending)] = sum(c[0] == "dispatch" for c in calls)
        calls.append(("dispatch", numbers[id(pending)]))
        return pending

    def recording_collect(pending):
        calls.append(("collect", numbers[id(pending)]))
        return collect(pending)

    combiner.dispatch = recording_dispatch
    combiner.collect = recording_collect


def _collect_at_dispatch(combiner):
    """The order of before: each group's program is waited for and
    fetched before the next group is staged."""
    dispatch, collect = combiner.dispatch, combiner.collect
    done: dict[int, list] = {}

    def dispatch_and_wait(*args, **kwargs):
        pending = dispatch(*args, **kwargs)
        done[id(pending)] = collect(pending)
        return pending

    combiner.dispatch = dispatch_and_wait
    combiner.collect = lambda pending: done.pop(id(pending))


class TestGroupsArePipelined:
    """Every group of a tick is dispatched before the first is
    collected (``JobManager._run_tick_programs``): the order on the
    host moves, no result does."""

    @pytest.mark.parametrize("n_groups", [1, 3, 7])
    def test_every_dispatch_precedes_the_first_fetch(self, n_groups):
        from esslivedata_tpu.telemetry.registry import REGISTRY
        from esslivedata_tpu.telemetry.trace import TRACER

        mgr, _ = _make_group_manager(n_groups)
        windows = _group_windows(70, 3, n_groups)
        for w in range(2):  # both program variants compile
            assert len(_process(mgr, windows[w], w)) == n_groups
        spans_family = REGISTRY.get("livedata_tick_span_seconds")
        unspanned_before = spans_family.sum(span="unspanned")
        counts_before = _group_counts()
        was_enabled = TRACER.enabled
        TRACER.enabled = True
        trace_id = TRACER.new_trace()
        try:
            with TRACER.bind(trace_id):
                t0 = time.perf_counter()
                assert len(_process(mgr, windows[2], 2)) == n_groups
                TRACER.finish_tick(
                    trace_id, time.perf_counter() - t0, tiled=True
                )
            spans = [s for s in TRACER.spans() if s.trace_id == trace_id]
        finally:
            TRACER.enabled = was_enabled
            mgr.shutdown()
        executes = [s for s in spans if s.name == "tick_execute"]
        fetches = [s for s in spans if s.name == "fetch"]
        assert len(executes) == len(fetches) == n_groups
        first_fetch = min(s.start_s for s in fetches)
        assert all(
            s.start_s + s.duration_s <= first_fetch for s in executes
        )
        # Stage i+1 lies between dispatch i and dispatch i+1, not
        # behind fetch i: every flatten starts before the first fetch.
        flattens = [s for s in spans if s.name == "flatten"]
        assert len(flattens) == n_groups
        assert all(s.start_s < first_fetch for s in flattens)
        # The loop thread's spans still tile the tick: none overlaps
        # another, and what they leave uncovered is positive.
        loop = sorted(
            (s for s in spans if s.thread == executes[0].thread),
            key=lambda s: s.start_s,
        )
        for earlier, later in zip(loop, loop[1:]):
            assert (
                earlier.start_s + earlier.duration_s <= later.start_s
            ), f"{earlier.name} overlaps {later.name}"
        assert spans_family.sum(span="unspanned") > unspanned_before
        assert _counts_added(counts_before) == {
            "ahead": n_groups - 1, "alone": 1
        }

    @pytest.mark.parametrize("n_groups", [1, 3, 7])
    def test_bit_identical_to_group_by_group(self, n_groups):
        """Every output and every carried state equals what
        ``collect(dispatch(...))`` gives group by group."""
        piped, piped_wfs = _make_group_manager(n_groups)
        serial, serial_wfs = _make_group_manager(n_groups)
        _collect_at_dispatch(serial._tick_combiner)
        for w, window in enumerate(_group_windows(71, 4, n_groups)):
            res_p = _process(piped, window, w)
            res_s = _process(serial, window, w)
            assert len(res_p) == len(res_s) == n_groups
            for rp, rs in zip(res_p, res_s):
                assert list(rp.outputs) == list(rs.outputs)
                assert _wire_bytes(rp) == _wire_bytes(rs), f"window {w}"
            for wp, ws in zip(piped_wfs, serial_wfs, strict=True):
                for lp, ls in zip(
                    jax.tree_util.tree_leaves(wp._state),
                    jax.tree_util.tree_leaves(ws._state),
                    strict=True,
                ):
                    assert np.array_equal(
                        np.asarray(lp), np.asarray(ls)
                    ), f"window {w}: a carried state differs"
        assert METRICS.drain()["tick_publishes"] >= 4 * n_groups
        piped.shutdown()
        serial.shutdown()

    def test_a_compile_round_is_serial_and_counted_alone(self):
        mgr, _ = _make_group_manager(3)
        calls: list = []
        _record_calls(mgr._tick_combiner, calls)
        windows = _group_windows(72, 3, 3)
        one_by_one = [
            (half, g) for g in range(3) for half in ("dispatch", "collect")
        ]
        for w in range(2):  # static-inclusive, then dynamic-only
            before = _group_counts()
            calls.clear()
            _process(mgr, windows[w], w)
            assert calls == one_by_one, f"window {w}"
            assert _counts_added(before) == {"ahead": 0, "alone": 3}
        before = _group_counts()
        calls.clear()
        _process(mgr, windows[2], 2)
        assert calls == [
            *(("dispatch", g) for g in range(3)),
            *(("collect", g) for g in range(3)),
        ]
        assert _counts_added(before) == {"ahead": 2, "alone": 1}
        mgr.shutdown()

    def test_a_record_in_two_groups_waits_for_its_first_collect(self):
        """None today (``_split_tick_groups`` admits only single-stream
        members), so the manager is handed such a tick directly: the
        first group's collect adopts the state that the second's
        dispatch would donate."""
        from esslivedata_tpu.ops.publish import CombinedPublish

        mgr, _ = _make_group_manager(2)
        windows = _group_windows(73, 1, 2)
        groups: list = []
        run = mgr._run_tick_programs
        mgr._run_tick_programs = lambda tick_groups, hand_over=None: (
            groups.extend(tick_groups) or run(tick_groups, hand_over)
        )
        _process(mgr, windows[0], 0)
        assert len(groups) == 2

        calls: list = []

        class Pending:
            compiled = False

        class Stub:
            def dispatch(self, hist, key, staged, requests, **_kwargs):
                calls.append("dispatch")
                return Pending()

            def collect(self, pending):
                calls.append("collect")
                # A plan-time error: the manager books nothing.
                return [CombinedPublish(None, (), error=RuntimeError("x"))]

        mgr._tick_combiner = Stub()
        before = _group_counts()
        run([groups[0], groups[1], groups[0]])
        assert calls == [
            "dispatch", "dispatch", "collect", "collect",
            "dispatch", "collect",
        ]
        assert _counts_added(before) == {"ahead": 1, "alone": 2}
        mgr.shutdown()


class TestGroupsPublishAhead:
    """A group's results go to the window's publisher right after its
    collect while a later group is still uncollected (ADR 0128): the
    moment on the host moves, no result and no state does. The order,
    the fallbacks and the loops: ``publish_ahead_test.py``."""

    @pytest.mark.parametrize("n_groups", [1, 3, 7])
    def test_bit_identical_to_end_of_window(self, n_groups):
        early, early_wfs = _make_group_manager(n_groups)
        late, late_wfs = _make_group_manager(n_groups)
        for w, window in enumerate(_group_windows(76, 4, n_groups)):
            handed: list = []
            rest = early.process_jobs(
                {s: _staged(pid, toa) for s, (pid, toa) in window.items()},
                start=T(0),
                end=T(w + 1),
                publish=handed.extend,
            )
            res_l = _process(late, window, w)
            # Windows 0 and 1 are compile rounds, collected on the spot.
            assert len(handed) == (n_groups - 1 if w >= 2 else 0)
            assert len(handed) + len(rest) == len(res_l) == n_groups
            for re, rl in zip([*handed, *rest], res_l):
                assert re.job_id.source_name == rl.job_id.source_name
                assert list(re.outputs) == list(rl.outputs)
                assert _wire_bytes(re) == _wire_bytes(rl), f"window {w}"
            for we, wl in zip(early_wfs, late_wfs, strict=True):
                for le, ll in zip(
                    jax.tree_util.tree_leaves(we._state),
                    jax.tree_util.tree_leaves(wl._state),
                    strict=True,
                ):
                    assert np.array_equal(
                        np.asarray(le), np.asarray(ll)
                    ), f"window {w}: a carried state differs"
        early.shutdown()
        late.shutdown()


class TestPipelinedContainment:
    """A failure in group 2 of 3 leaves groups 1 and 3 served and
    resets only the members whose buffers were consumed."""

    def warm(self, seed):
        mgr, _ = _make_group_manager(3)
        windows = _group_windows(seed, 4, 3)
        for w in range(2):
            res = _process(mgr, windows[w], w)
        return mgr, windows, [
            float(r.outputs["counts_cumulative"].values) for r in res
        ]

    def assert_only_group_two_was_reset(self, mgr, windows, cum_before):
        METRICS.drain()
        res = _process(mgr, windows[2], 2)
        assert len(res) == 3  # every job still publishes
        assert METRICS.drain()["tick_publishes"] == 2  # groups 1 and 3
        cur = [float(r.outputs["counts_current"].values) for r in res]
        cum = [float(r.outputs["counts_cumulative"].values) for r in res]
        assert cum[1] == cur[1]  # reset: windows 0-1 are gone
        for g in (0, 2):
            assert cum[g] == cum_before[g] + cur[g]  # untouched
        assert "error" not in {str(s.state) for s in mgr.job_statuses()}
        return cum

    def test_chaos_after_the_second_dispatch(self):
        from esslivedata_tpu.harness.chaos import ChaosSchedule, ChaosSpec

        mgr, windows, cum_before = self.warm(74)
        calls: list = []
        _record_calls(mgr._tick_combiner, calls)
        mgr.set_chaos(
            ChaosSchedule(ChaosSpec(at={"tick_dispatch": frozenset({1})}))
        )
        cum = self.assert_only_group_two_was_reset(mgr, windows, cum_before)
        # Group 2's handle is dropped; 1 and 3 were in flight together.
        assert calls == [
            ("dispatch", 0), ("dispatch", 1), ("dispatch", 2),
            ("collect", 0), ("collect", 2),
        ]
        # Recovery: every group ticks again on its cached program.
        res = _process(mgr, windows[3], 3)
        assert METRICS.drain()["tick_publishes"] == 3
        assert float(res[1].outputs["counts_cumulative"].values) > cum[1]
        mgr.shutdown()

    def test_a_program_that_fails_at_the_collect(self):
        """What an asynchronous failure of the program looks like: the
        dispatch returns, the wait raises."""
        mgr, windows, cum_before = self.warm(75)
        combiner = mgr._tick_combiner

        class Poisoned:
            def copy_to_host_async(self):
                pass

            def block_until_ready(self):
                raise RuntimeError("asynchronous boom")

        steady = [k for k in combiner._programs if not k[3][0][3]]
        assert len(steady) == 3  # the dynamic-only variant of each group
        victim = steady[1]
        fn = combiner._programs[victim]

        def poisoned(*args):
            _packed, statics, carries = fn(*args)  # donates the states
            return Poisoned(), statics, carries

        combiner._programs[victim] = poisoned
        cum = self.assert_only_group_two_was_reset(mgr, windows, cum_before)
        assert victim not in combiner._programs  # evicted
        res = _process(mgr, windows[3], 3)  # group 2 compiles afresh
        assert METRICS.drain()["tick_publishes"] == 3
        assert float(res[1].outputs["counts_cumulative"].values) > cum[1]
        mgr.shutdown()

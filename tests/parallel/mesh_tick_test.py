"""Mesh serving tier (ADR 0115): parity, placement, per-slice contracts.

The mesh tick program may not change a single byte of the da00 wire
output vs the single-device tick program OR the pre-tick combined path
(ADR 0113), must keep a steady-state tick at ONE execute + ONE fetch per
mesh slice, and must contain post-donation failures per slice — pinned
through the REAL JobManager path on the 8-virtual-device CPU mesh (the
tick_program_test pattern, scaled out).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from esslivedata_tpu.config import JobId, WorkflowConfig, WorkflowSpec
from esslivedata_tpu.core.job_manager import JobFactory, JobManager
from esslivedata_tpu.core.timestamp import Timestamp
from esslivedata_tpu.kafka.da00_compat import dataarray_to_da00
from esslivedata_tpu.kafka.wire import encode_da00
from esslivedata_tpu.ops import EventBatch
from esslivedata_tpu.ops.publish import METRICS
from esslivedata_tpu.parallel import ShardedHistogrammer, make_mesh
from esslivedata_tpu.parallel.mesh_tick import (
    DevicePlacement,
    MeshTickCombiner,
)
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.workflows import WorkflowFactory
from esslivedata_tpu.workflows.multibank import (
    MultiBankParams,
    MultiBankViewWorkflow,
)

T = Timestamp.from_ns

N_BANKS = 8
N_PIXELS = N_BANKS * 64
BANKS = {
    f"bank{i}": np.arange(i * 64, (i + 1) * 64) for i in range(N_BANKS)
}


@pytest.fixture(scope="module")
def devices():
    d = jax.devices()
    if len(d) < 8:
        pytest.skip("needs 8 virtual devices (conftest sets CPU x8)")
    return d


def _staged(seed: int, n: int = 8192) -> StagedEvents:
    rng = np.random.default_rng(seed)
    return StagedEvents(
        batch=EventBatch.from_arrays(
            rng.integers(0, N_PIXELS, n).astype(np.int64),
            rng.uniform(-1e6, 7e7, n).astype(np.float32),
        ),
        first_timestamp=None,
        last_timestamp=None,
        n_chunks=1,
    )


_UNIQ = [0]


def _make_manager(
    mesh,
    *,
    exchange: str = "auto",
    k: int = 2,
    tick_program: bool = True,
    placement=None,
):
    _UNIQ[0] += 1
    reg = WorkflowFactory()
    spec = WorkflowSpec(
        instrument="test", name=f"meshmb{_UNIQ[0]}", source_names=["det0"]
    )
    reg.register_spec(spec).attach_factory(
        lambda *, source_name, params: MultiBankViewWorkflow(
            bank_detector_numbers=BANKS,
            params=MultiBankParams(
                toa_bins=16,
                use_mesh=mesh is not None,
                mesh_exchange=exchange,
            ),
            mesh=mesh,
        )
    )
    mgr = JobManager(
        job_factory=JobFactory(reg),
        job_threads=2,
        tick_program=tick_program,
        placement=placement,
    )
    for _ in range(k):
        mgr.schedule_job(
            WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name="det0")
            )
        )
    return mgr


def _run_windows(mgr, n_windows: int, *, k: int = 2, warm: int = 2):
    for w in range(warm):
        res = mgr.process_jobs(
            {"det0": _staged(w)}, start=T(0), end=T(w + 1)
        )
        assert len(res) == k
    METRICS.drain()
    wires = []
    for i in range(n_windows):
        res = mgr.process_jobs(
            {"det0": _staged(i)}, start=T(0), end=T(10 + i)
        )
        assert len(res) == k
        wires.append(
            [
                encode_da00(name, 12345, dataarray_to_da00(da))
                for r in res
                for name, da in r.outputs.items()
            ]
        )
    return wires, METRICS.drain()


class TestMeshSingleDeviceParity:
    @pytest.mark.parametrize("exchange", ["delta_psum", "event_gather"])
    def test_byte_identical_da00_wire_output(self, devices, exchange):
        """Mesh tick program vs single-device tick program vs the
        pre-tick combined path (ADR 0113, ``tick_program=False``) on
        the 2x4 mesh: identical windows, byte-identical da00 wire, for
        BOTH exchange strategies."""
        mesh = make_mesh(8, data=2, bank=4)
        mesh_tick, m_tick = _run_windows(
            _make_manager(mesh, exchange=exchange), 3
        )
        single_tick, _ = _run_windows(_make_manager(None), 3)
        mesh_combined, m_comb = _run_windows(
            _make_manager(mesh, exchange=exchange, tick_program=False), 3
        )
        assert mesh_tick == single_tick
        assert mesh_tick == mesh_combined
        # The tick contract holds on the mesh: one execute + one fetch
        # per steady-state tick for the whole K-job group, zero
        # separate step dispatches; the combined path pays the extra
        # fused-step dispatch.
        assert m_tick["executes"] == 3
        assert m_tick["fetches"] == 3
        assert m_tick["step_executes"] == 0
        assert m_tick["tick_publishes"] == 3
        assert m_comb["step_executes"] == 3

    def test_mesh_combined_matches_per_job_reference(self, devices):
        """combine_publish=False (the per-job reference path) through
        the mesh kernel still produces the identical wire — the
        ``views_of`` replication seam does not depend on how publishes
        are batched."""
        mesh = make_mesh(8, data=1, bank=8)
        combined, _ = _run_windows(_make_manager(mesh), 2)
        reg_wires = []
        _UNIQ[0] += 1
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="test",
            name=f"meshref{_UNIQ[0]}",
            source_names=["det0"],
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: MultiBankViewWorkflow(
                bank_detector_numbers=BANKS,
                params=MultiBankParams(toa_bins=16),
                mesh=mesh,
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg), job_threads=2,
            combine_publish=False,
        )
        for _ in range(2):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="det0"),
                )
            )
        reg_wires, _ = _run_windows(mgr, 2)
        assert combined == reg_wires
        mgr.shutdown()


def test_mesh_from_spec_rejects_zero_axes(devices):
    """An operator typo like '--mesh 0,4' must fail the build loudly:
    make_mesh's data*bank == n_devices check passes at 0 == 0, so
    without validation an EMPTY mesh silently degrades serving."""
    from esslivedata_tpu.parallel import mesh_from_spec

    with pytest.raises(ValueError):
        mesh_from_spec("0,4")
    with pytest.raises(ValueError):
        mesh_from_spec("2,0")
    with pytest.raises(ValueError):
        mesh_from_spec("-2,4")
    assert mesh_from_spec("2,4").shape == {"data": 2, "bank": 4}


class TestPlacement:
    def test_slices_spread_round_robin_and_stick(self, devices):
        mesh = make_mesh(4, data=2, bank=2)
        placement = DevicePlacement(mesh)
        single = ShardedHistogrammer(  # mesh-sharded hist: whole mesh
            toa_edges=np.linspace(0.0, 7e7, 9), n_screen=8, mesh=mesh
        )
        s_mesh = placement.assign("s0", ("k0",), single)
        assert s_mesh.mesh is mesh
        assert s_mesh.combiner is not None
        assert s_mesh.label.startswith("mesh:")
        # Single-device groups round-robin over the mesh's devices and
        # re-assignment is sticky.
        from esslivedata_tpu.ops.histogram import EventHistogrammer

        def hist():
            return EventHistogrammer(
                toa_edges=np.linspace(0.0, 7e7, 5), n_screen=4
            )

        labels = [
            placement.assign(f"s{i}", ("kd",), hist()).label
            for i in range(1, 5)
        ]
        assert len(set(labels)) == 4
        again = placement.assign("s1", ("kd",), hist())
        assert again.label == labels[0]
        # The mesh group's combiner is shared per device set.
        other = placement.assign("s9", ("k9",), single)
        assert other.combiner is s_mesh.combiner
        # A bespoke duck-typed histogrammer without device-aware staging
        # pins to the DEFAULT placement (forwarding device= would
        # TypeError its staging every window).
        bespoke = placement.assign("s10", ("kb",), object())
        assert bespoke.label == "default"
        assert bespoke.device is None and bespoke.combiner is None

    def test_one_execute_one_fetch_per_slice_and_per_slice_rtt(
        self, devices
    ):
        """Two single-device tick groups on distinct slices + one
        whole-mesh group: every slice records exactly ONE execute + ONE
        fetch per steady-state tick, and the link monitor carries a
        per-slice RTT estimate for each (ADR 0115)."""
        from esslivedata_tpu.workflows.detector_view import (
            DetectorViewParams,
            DetectorViewWorkflow,
            project_logical,
        )

        mesh = make_mesh(8, data=2, bank=4)
        placement = DevicePlacement(mesh)
        det = np.arange(144).reshape(12, 12)
        _UNIQ[0] += 1
        reg = WorkflowFactory()
        idents = []
        for i, stream in enumerate(("s0", "s1")):
            spec = WorkflowSpec(
                instrument="test",
                name=f"dvp{_UNIQ[0]}_{i}",
                source_names=[stream],
            )
            reg.register_spec(spec).attach_factory(
                lambda *, source_name, params: DetectorViewWorkflow(
                    projection=project_logical(det),
                    params=DetectorViewParams(toa_bins=8),
                )
            )
            idents.append((spec.identifier, stream))
        mspec = WorkflowSpec(
            instrument="test", name=f"mbp{_UNIQ[0]}", source_names=["mb0"]
        )
        reg.register_spec(mspec).attach_factory(
            lambda *, source_name, params: MultiBankViewWorkflow(
                bank_detector_numbers=BANKS,
                params=MultiBankParams(toa_bins=16),
                mesh=mesh,
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg), job_threads=2,
            placement=placement,
        )
        for ident, stream in idents:
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=ident, job_id=JobId(source_name=stream)
                )
            )
        mgr.schedule_job(
            WorkflowConfig(
                identifier=mspec.identifier, job_id=JobId(source_name="mb0")
            )
        )

        def window(i):
            rng = np.random.default_rng(1000 + i)
            data = {
                s: StagedEvents(
                    batch=EventBatch.from_arrays(
                        rng.integers(0, 144, 4096).astype(np.int64),
                        rng.uniform(0, 7e7, 4096).astype(np.float32),
                    ),
                    first_timestamp=None,
                    last_timestamp=None,
                    n_chunks=1,
                )
                for s in ("s0", "s1")
            }
            data["mb0"] = _staged(1000 + i)
            return data

        for w in range(2):
            res = mgr.process_jobs(window(w), start=T(0), end=T(w + 1))
            assert len(res) == 3
        METRICS.drain()
        n = 3
        for i in range(n):
            res = mgr.process_jobs(window(i), start=T(0), end=T(10 + i))
            assert len(res) == 3
        m = METRICS.drain()
        slices = m["slices"]
        assert len(slices) == 3  # two device slices + the mesh slice
        mesh_labels = [k for k in slices if k.startswith("mesh:")]
        assert len(mesh_labels) == 1
        for label, counts in slices.items():
            assert counts["executes"] == n, (label, counts)
            assert counts["fetches"] == n, (label, counts)
            assert counts["tick_publishes"] == n, (label, counts)
        assert m["step_executes"] == 0
        mgr.shutdown()

    def test_fused_path_keeps_the_slice(self, devices):
        """Without the tick program every window runs the fused step
        and a separate publish — the group must keep its assigned slice
        so the wire stages once per slice, never alternating devices."""
        mesh = make_mesh(2, data=1, bank=2)
        placement = DevicePlacement(mesh)
        from esslivedata_tpu.workflows.detector_view import (
            DetectorViewParams,
            DetectorViewWorkflow,
            project_logical,
        )

        det = np.arange(64).reshape(8, 8)
        _UNIQ[0] += 1
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="test", name=f"dvc{_UNIQ[0]}", source_names=["s0"]
        )
        reg.register_spec(spec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(toa_bins=8),
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg), job_threads=2,
            placement=placement, tick_program=False,
        )
        for _ in range(2):
            mgr.schedule_job(
                WorkflowConfig(
                    identifier=spec.identifier,
                    job_id=JobId(source_name="s0"),
                )
            )

        def win(i):
            rng = np.random.default_rng(i)
            return {
                "s0": StagedEvents(
                    batch=EventBatch.from_arrays(
                        rng.integers(0, 64, 4096).astype(np.int64),
                        rng.uniform(0, 7e7, 4096).astype(np.float32),
                    ),
                    first_timestamp=None,
                    last_timestamp=None,
                    n_chunks=1,
                )
            }

        METRICS.drain()
        for i in range(6):
            assert len(
                mgr.process_jobs(win(i), start=T(0), end=T(i + 1))
            ) == 2
        m = METRICS.drain()
        assert m["step_executes"] == 6 and m["tick_publishes"] == 0
        assert len(placement.slices()) == 1
        (slice_,) = placement.slices().values()
        # Every member state stayed committed to the assigned slice
        # across every fused step and its separate publish.
        for rec in mgr._records.values():
            state = rec.job.workflow.state
            assert DevicePlacement.state_on(state, slice_.device)
        mgr.shutdown()

    def test_placed_singleton_private_path_stages_on_its_slice(
        self, devices
    ):
        """A placed SINGLETON group drops to the workflow-private
        accumulate on the windows its tick is refused (no fused group
        at K=1; here its publish offer is withheld two windows in
        three): the private step must stage onto the
        state's slice — default-device staging would hand the jitted
        step mixed-committed-device arguments, which real multi-chip
        backends reject (the JGL017 hazard; ``_state_slice_device``
        resolves it from the state)."""
        from esslivedata_tpu.workflows.detector_view import (
            DetectorViewParams,
            DetectorViewWorkflow,
            project_logical,
        )

        mesh = make_mesh(4, data=2, bank=2)
        placement = DevicePlacement(mesh)
        det = np.arange(64).reshape(8, 8)
        _UNIQ[0] += 1
        reg = WorkflowFactory()
        spec = WorkflowSpec(
            instrument="test", name=f"dvs{_UNIQ[0]}", source_names=["s0"]
        )
        created = []

        def factory(*, source_name, params):
            wf = DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(toa_bins=8),
            )
            created.append(wf)
            return wf

        reg.register_spec(spec).attach_factory(factory)
        mgr = JobManager(
            job_factory=JobFactory(reg), job_threads=1,
            placement=placement,
        )
        mgr.schedule_job(
            WorkflowConfig(
                identifier=spec.identifier, job_id=JobId(source_name="s0")
            )
        )
        (wf,) = created
        offer = wf.publish_offer
        window_no = [0]
        wf.publish_offer = lambda: (
            offer() if window_no[0] % 3 == 0 else None
        )

        def win(i, n=2048):
            rng = np.random.default_rng(3000 + i)
            return {
                "s0": StagedEvents(
                    batch=EventBatch.from_arrays(
                        rng.integers(0, 64, n).astype(np.int64),
                        rng.uniform(0, 7e7, n).astype(np.float32),
                    ),
                    first_timestamp=None,
                    last_timestamp=None,
                    n_chunks=1,
                )
            }

        results = []
        METRICS.drain()
        for i in range(6):
            window_no[0] = i
            results.extend(
                mgr.process_jobs(win(i), start=T(0), end=T(i + 1))
            )
        assert METRICS.drain()["tick_publishes"] == 2  # windows 0 and 3
        assert len(results) == 6
        (slice_,) = placement.slices().values()
        assert slice_.device is not None
        # The state stayed on its slice through the private windows (the
        # private accumulate ran there, it never bounced to default),
        # nothing errored, and the published cumulative carries every
        # window's events.
        assert DevicePlacement.state_on(created[0].state, slice_.device)
        states = {str(s.state) for s in mgr.job_statuses()}
        assert "error" not in states
        assert results
        cum = float(results[-1].outputs["counts_cumulative"].values)
        assert cum == 6 * 2048
        mgr.shutdown()


class TestReKeying:
    def test_layout_digest_swap_rekeys_staging_fusion_and_tick(
        self, devices
    ):
        """A live LUT swap re-fingerprints the layout: stage/fuse keys
        change, so staged wires can never be consumed by a program
        traced for the other table, and the next tick compiles a fresh
        program (``compiled`` on its handle)."""
        mesh = make_mesh(4, data=2, bank=2)
        edges = np.linspace(0.0, 7e7, 9)
        lut = (np.arange(64) % 8).astype(np.int32)
        h = ShardedHistogrammer(
            toa_edges=edges, n_screen=8, mesh=mesh, pixel_lut=lut
        )
        digest0, fuse0 = h.layout_digest, h.fuse_key
        assert h.swap_projection((lut + 1) % 8)
        assert h.layout_digest != digest0
        assert h.fuse_key != fuse0
        assert h.fuse_key[:-1] == fuse0[:-1]  # only the digest moved

        from esslivedata_tpu.ops.publish import (
            PackedPublisher,
            PublishRequest,
        )

        combiner = MeshTickCombiner(mesh)
        pub = PackedPublisher(
            lambda state: (
                {"total": h.views_of(state)[1].sum()},
                h.fold_window(state),
            )
        )
        batch = EventBatch.from_arrays(
            np.arange(64, dtype=np.int64) % 64,
            np.full(64, 1e6, np.float32),
        )
        staged = h.tick_staging(batch, None)

        def tick():
            pending = combiner.dispatch(
                h,
                ("g",) + h.fuse_key,
                staged,
                [PublishRequest(pub, (h.init_state(),))],
            )
            (res,) = combiner.collect(pending)
            assert res.error is None
            return pending.compiled

        assert tick()
        assert not tick()  # steady state: cache hit
        assert h.swap_projection((lut + 2) % 8)
        assert tick()  # digest moved -> re-keyed


class TestContainment:
    def test_post_donation_state_lost_contained_per_slice(self, devices):
        """A mesh tick dispatch failing AFTER consuming its donated
        states resets exactly the mesh slice's members (fresh zeroed
        accumulation, jobs still publish) and recovers next window; a
        single-device slice in the same service is untouched."""
        from esslivedata_tpu.workflows.detector_view import (
            DetectorViewParams,
            DetectorViewWorkflow,
            project_logical,
        )

        mesh = make_mesh(8, data=2, bank=4)
        placement = DevicePlacement(mesh)
        det = np.arange(144).reshape(12, 12)
        _UNIQ[0] += 1
        reg = WorkflowFactory()
        dspec = WorkflowSpec(
            instrument="test", name=f"dvx{_UNIQ[0]}", source_names=["s0"]
        )
        reg.register_spec(dspec).attach_factory(
            lambda *, source_name, params: DetectorViewWorkflow(
                projection=project_logical(det),
                params=DetectorViewParams(toa_bins=8),
            )
        )
        mspec = WorkflowSpec(
            instrument="test", name=f"mbx{_UNIQ[0]}", source_names=["mb0"]
        )
        reg.register_spec(mspec).attach_factory(
            lambda *, source_name, params: MultiBankViewWorkflow(
                bank_detector_numbers=BANKS,
                params=MultiBankParams(toa_bins=16),
                mesh=mesh,
            )
        )
        mgr = JobManager(
            job_factory=JobFactory(reg), job_threads=2,
            placement=placement,
        )
        mgr.schedule_job(
            WorkflowConfig(
                identifier=dspec.identifier, job_id=JobId(source_name="s0")
            )
        )
        mgr.schedule_job(
            WorkflowConfig(
                identifier=mspec.identifier, job_id=JobId(source_name="mb0")
            )
        )

        def window(i):
            rng = np.random.default_rng(2000 + i)
            return {
                "s0": StagedEvents(
                    batch=EventBatch.from_arrays(
                        rng.integers(0, 144, 4096).astype(np.int64),
                        rng.uniform(0, 7e7, 4096).astype(np.float32),
                    ),
                    first_timestamp=None,
                    last_timestamp=None,
                    n_chunks=1,
                ),
                "mb0": _staged(2000 + i),
            }

        for w in range(2):
            res = mgr.process_jobs(window(w), start=T(0), end=T(w + 1))
            assert len(res) == 2
        by_src = {r.job_id.source_name: r for r in res}
        det_cum_w1 = float(
            by_src["s0"].outputs["counts_cumulative"].values
        )

        # Poison the MESH slice's compiled tick programs only: run the
        # real dispatch (consuming the donated states), then raise —
        # the post-donation failure mode, scoped to one slice.
        mesh_slice = next(
            s for s in placement.slices().values() if s.mesh is not None
        )
        combiner = mesh_slice.combiner
        assert combiner._programs
        saved = dict(combiner._programs)

        def poison(fn):
            def boom(*args):
                fn(*args)
                raise RuntimeError("post-donation boom")

            return boom

        for key in list(combiner._programs):
            combiner._programs[key] = poison(combiner._programs[key])

        res = mgr.process_jobs(window(2), start=T(0), end=T(3))
        assert len(res) == 2
        by_src = {r.job_id.source_name: r for r in res}
        mb_cur = float(by_src["mb0"].outputs["counts_current"].values)
        mb_cum = float(by_src["mb0"].outputs["counts_cumulative"].values)
        # Mesh member reset: cumulative == this window only (the
        # pre-failure accumulation was consumed by the poisoned
        # dispatch), republished via the private fallback.
        assert mb_cum == mb_cur
        # The single-device slice is untouched and kept accumulating.
        det_cum = float(
            by_src["s0"].outputs["counts_cumulative"].values
        )
        assert det_cum > det_cum_w1
        states = {str(s.state) for s in mgr.job_statuses()}
        assert "error" not in states

        # Recovery: restored programs tick the mesh slice again.
        combiner._programs.clear()
        combiner._programs.update(saved)
        METRICS.drain()
        res = mgr.process_jobs(window(3), start=T(0), end=T(4))
        assert len(res) == 2
        m = METRICS.drain()
        mesh_label = mesh_slice.label
        assert m["slices"][mesh_label]["tick_publishes"] == 1
        by_src = {r.job_id.source_name: r for r in res}
        mb_cum2 = float(by_src["mb0"].outputs["counts_cumulative"].values)
        assert mb_cum2 > mb_cur
        mgr.shutdown()

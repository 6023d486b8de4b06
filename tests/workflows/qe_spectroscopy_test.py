"""Q–E rebinning: map physics, workflow conservation, registry wiring."""

import numpy as np
import pytest

from esslivedata_tpu.ops.event_batch import EventBatch
from esslivedata_tpu.ops.qhistogram import E_FROM_V2, K_FROM_V, build_qe_map
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.workflows.qe_spectroscopy import (
    QESpectroscopyParams,
    QESpectroscopyWorkflow,
)


def staged(pid, toa):
    return StagedEvents(
        batch=EventBatch.from_arrays(
            np.asarray(pid, np.int32), np.asarray(toa, np.float32)
        ),
        first_timestamp=None,
        last_timestamp=None,
        n_chunks=1,
    )


class TestQEMapPhysics:
    L1 = 162.0
    EF = 5.0  # meV
    L2 = 1.5  # m
    TWO_THETA = np.deg2rad(60.0)

    def _edges(self):
        toa_edges = np.linspace(8.0e7, 4.0e8, 3201)  # fine: 100 us bins
        q_edges = np.linspace(0.1, 3.0, 146)  # 0.02 1/angstrom bins
        e_edges = np.linspace(-3.0, 6.0, 181)  # 0.05 meV bins
        return toa_edges, q_edges, e_edges

    def _map(self):
        toa_edges, q_edges, e_edges = self._edges()
        qe_map = build_qe_map(
            two_theta=np.array([self.TWO_THETA]),
            ef_mev=np.array([self.EF]),
            l2=np.array([self.L2]),
            pixel_ids=np.array([0]),
            toa_edges=toa_edges,
            q_edges=q_edges,
            e_edges=e_edges,
            l1=self.L1,
        )
        return qe_map, toa_edges, q_edges, e_edges

    def test_elastic_arrival_lands_in_zero_energy_bin(self):
        qe_map, toa_edges, q_edges, e_edges = self._map()
        # Elastic: vi == vf, so t = l1/v + l2/v.
        v = np.sqrt(self.EF / E_FROM_V2)
        t_elastic_ns = (self.L1 + self.L2) / v * 1e9
        tb = np.searchsorted(toa_edges, t_elastic_ns) - 1
        flat = qe_map.table[0, tb]
        assert flat >= 0
        n_e = len(e_edges) - 1
        qb, eb = divmod(int(flat), n_e)
        de_lo, de_hi = e_edges[eb], e_edges[eb + 1]
        assert de_lo <= 0.0 <= de_hi or abs(de_lo) < 0.1
        # Elastic |Q| = 2 k sin(theta) with k = k(Ef).
        k = K_FROM_V * v
        q_expected = 2.0 * k * np.sin(self.TWO_THETA / 2.0)
        assert q_edges[qb] <= q_expected <= q_edges[qb + 1]

    def test_energy_gain_and_loss_sides(self):
        qe_map, toa_edges, q_edges, e_edges = self._map()
        n_e = len(e_edges) - 1
        v_f = np.sqrt(self.EF / E_FROM_V2)
        t2_ns = self.L2 / v_f * 1e9

        def de_of(toa_ns):
            tb = np.searchsorted(toa_edges, toa_ns) - 1
            flat = qe_map.table[0, tb]
            if flat < 0:
                return None
            eb = int(flat) % n_e
            return (e_edges[eb] + e_edges[eb + 1]) / 2.0

        # Faster arrival (shorter incident time) = higher Ei = energy loss
        # side (dE > 0); slower = energy gain side (dE < 0).
        v_fast = np.sqrt((self.EF + 3.0) / E_FROM_V2)
        t_fast = (self.L1 / v_fast) * 1e9 + t2_ns
        v_slow = np.sqrt((self.EF - 2.0) / E_FROM_V2)
        t_slow = (self.L1 / v_slow) * 1e9 + t2_ns
        assert de_of(t_fast) == pytest.approx(3.0, abs=0.1)
        assert de_of(t_slow) == pytest.approx(-2.0, abs=0.1)

    def test_arrivals_before_final_leg_are_dropped(self):
        qe_map, toa_edges, _, _ = self._map()
        # An "arrival" before even the fixed final leg could complete has
        # no physical incident time: t1 <= 0 must map to -1... the final
        # leg is ~1.5 ms, far below the window start, so instead check
        # out-of-range energies: the very first bins (extremely fast ->
        # huge Ei -> dE above e_max) are dropped.
        assert qe_map.table[0, 0] == -1

    def test_map_is_total_over_declared_pixels(self):
        qe_map, _, _, _ = self._map()
        # Undeclared pixel-id rows are all -1 (dropped).
        assert qe_map.table.shape[0] == 1


#: The default frame offset: an ev44 TOA is relative to its own pulse, so
#: a flight time of ~187 ms arrives as that minus two pulse periods.
FRAME_OFFSET_NS = QESpectroscopyParams().toa_offset_ns

class TestWorkflowIntegration:
    def _workflow(self):
        n_pix = 16
        return QESpectroscopyWorkflow(
            two_theta=np.full(n_pix, np.deg2rad(45.0)),
            ef_mev=np.full(n_pix, 4.0),
            l2=np.full(n_pix, 1.5),
            pixel_ids=np.arange(n_pix),
            params=QESpectroscopyParams(q_bins=20, e_bins=16),
            monitor_streams={"monitor_1"},
        )

    def test_events_bin_and_fold(self):
        wf = self._workflow()
        v = np.sqrt(4.0 / E_FROM_V2)
        t_elastic = (162.0 + 1.5) / v * 1e9 - FRAME_OFFSET_NS  # as on the wire
        rng = np.random.default_rng(0)
        pid = rng.integers(0, 16, 5000).astype(np.int32)
        toa = np.full(5000, t_elastic, dtype=np.float32)
        wf.accumulate({"detector": staged(pid, toa)})
        out = wf.finalize()
        total = float(np.asarray(out["sqw_current"].values).sum())
        assert total == 5000.0
        assert np.asarray(out["sqw_current"].values).shape == (20, 16)
        # Fold: window zero, cumulative persists.
        out2 = wf.finalize()
        assert float(np.asarray(out2["sqw_current"].values).sum()) == 0.0
        assert (
            float(np.asarray(out2["sqw_cumulative"].values).sum()) == 5000.0
        )

    def test_monitor_normalization(self):
        wf = self._workflow()
        v = np.sqrt(4.0 / E_FROM_V2)
        t_elastic = (162.0 + 1.5) / v * 1e9 - FRAME_OFFSET_NS  # as on the wire
        wf.accumulate(
            {
                "detector": staged(
                    np.zeros(100, np.int32), np.full(100, t_elastic)
                ),
                "monitor_1": staged(
                    np.zeros(50, np.int32), np.full(50, 1e6)
                ),
            }
        )
        out = wf.finalize()
        assert float(np.asarray(out["monitor_counts_current"].values)) == 50.0
        norm_total = float(np.asarray(out["sqw_normalized"].values).sum())
        assert norm_total == pytest.approx(100.0 / 50.0)


class TestRegistryWiring:
    def test_bifrost_qe_creates_through_registry(self):
        from esslivedata_tpu.config import JobId, WorkflowConfig
        from esslivedata_tpu.config.instrument import instrument_registry
        from esslivedata_tpu.workflows.workflow_factory import (
            workflow_registry,
        )

        instrument_registry["bifrost"].load_factories()
        from esslivedata_tpu.config.instruments.bifrost.specs import (
            MERGED_STREAM,
            QE_HANDLE,
        )

        config = WorkflowConfig(
            identifier=QE_HANDLE.workflow_id,
            job_id=JobId(source_name=MERGED_STREAM),
            params={"q_bins": 10, "e_bins": 8},
            aux_source_names={"monitor": "monitor_1"},
        )
        wf = workflow_registry.create(config)
        assert isinstance(wf, QESpectroscopyWorkflow)
        # The synthetic analyzer geometry covers every declared pixel.
        out = wf.finalize()
        assert np.asarray(out["sqw_current"].values).shape == (10, 8)

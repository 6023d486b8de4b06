"""Elastic Q-map + detector ratemeter (reference: bifrost specs
elastic_qmap:376, detector_ratemeter:350)."""

import numpy as np
import pytest

from esslivedata_tpu.ops.event_batch import EventBatch
from esslivedata_tpu.ops.qhistogram import (
    E_FROM_V2,
    K_FROM_V,
    build_elastic_q2d_map,
)
from esslivedata_tpu.preprocessors.event_data import StagedEvents
from esslivedata_tpu.workflows.elastic_qmap import (
    ElasticQAxis,
    ElasticQMapParams,
    ElasticQMapWorkflow,
)
from esslivedata_tpu.workflows.ratemeter import RatemeterParams, RatemeterWorkflow

L1 = 162.0
EF = 5.0  # meV


def staged(pid, toa):
    return StagedEvents(
        batch=EventBatch.from_arrays(
            np.asarray(pid, np.int32), np.asarray(toa, np.float32)
        ),
        first_timestamp=None,
        last_timestamp=None,
        n_chunks=1,
    )


def elastic_toa_ns(l2):
    """Arrival time of an exactly-elastic neutron (Ei = Ef)."""
    v = np.sqrt(EF / E_FROM_V2)
    return (L1 / v + l2 / v) * 1e9


class TestElasticQ2dMap:
    def make_map(self, two_theta_deg=60.0, azimuth_deg=0.0, **kw):
        toa_edges = np.linspace(8.0e7, 4.0e8, 3201)
        a_edges = np.linspace(-3.0, 3.0, 301)  # 0.02 per bin
        table = build_elastic_q2d_map(
            two_theta=np.array([np.deg2rad(two_theta_deg)]),
            azimuth=np.array([np.deg2rad(azimuth_deg)]),
            ef_mev=np.array([EF]),
            l2=np.array([1.5]),
            pixel_ids=np.array([7]),
            toa_edges=toa_edges,
            axis1=kw.get("axis1", "Qx"),
            axis1_edges=a_edges,
            axis2=kw.get("axis2", "Qz"),
            axis2_edges=a_edges,
            l1=L1,
            e_window_mev=kw.get("e_window_mev", 0.25),
        )
        return table, toa_edges, a_edges

    def toa_bin(self, toa_edges, t_ns):
        return int(np.searchsorted(toa_edges, t_ns, side="right")) - 1

    def test_elastic_bin_matches_analytic_q(self):
        table, toa_edges, a_edges = self.make_map()
        tb = self.toa_bin(toa_edges, elastic_toa_ns(1.5))
        flat = int(table.table[0, tb])
        assert flat >= 0
        n2 = len(a_edges) - 1
        b1, b2 = divmod(flat, n2)
        k = K_FROM_V * np.sqrt(EF / E_FROM_V2)
        qx = -k * np.sin(np.deg2rad(60.0))
        qz = k - k * np.cos(np.deg2rad(60.0))
        np.testing.assert_allclose(
            a_edges[b1] + 0.01, qx, atol=0.021
        )
        np.testing.assert_allclose(
            a_edges[b2] + 0.01, qz, atol=0.021
        )

    def test_inelastic_arrivals_dropped(self):
        table, toa_edges, _ = self.make_map(e_window_mev=0.1)
        # A neutron arriving 30% early is far off the elastic line.
        tb = self.toa_bin(toa_edges, elastic_toa_ns(1.5) * 0.7)
        assert table.table[0, tb] == -1
        # The elastic window covers a contiguous run of toa bins only.
        valid = (table.table[0] >= 0).nonzero()[0]
        assert valid.size > 0
        assert np.all(np.diff(valid) == 1)

    def test_azimuth_moves_qy(self):
        table, toa_edges, a_edges = self.make_map(
            azimuth_deg=30.0, axis1="Qy", axis2="Qz"
        )
        tb = self.toa_bin(toa_edges, elastic_toa_ns(1.5))
        flat = int(table.table[0, tb])
        assert flat >= 0
        n2 = len(a_edges) - 1
        b1 = flat // n2
        k = K_FROM_V * np.sqrt(EF / E_FROM_V2)
        qy = -k * np.sin(np.deg2rad(60.0)) * np.sin(np.deg2rad(30.0))
        assert abs((a_edges[b1] + 0.01) - qy) < 0.021


def wire_toa_ns(l2):
    """``elastic_toa_ns`` as an ev44 message carries it: relative to the
    pulse it arrives in, i.e. less the default frame offset."""
    return elastic_toa_ns(l2) - ElasticQMapParams().toa_offset_ns


class TestElasticQMapWorkflow:
    def make(self, **params):
        return ElasticQMapWorkflow(
            two_theta=np.deg2rad(np.array([30.0, 60.0, 90.0])),
            azimuth=np.zeros(3),
            ef_mev=np.full(3, EF),
            l2=np.full(3, 1.5),
            pixel_ids=np.array([1, 2, 3]),
            params=ElasticQMapParams(**params) if params else None,
            primary_stream="detector",
            monitor_streams={"monitor_1"},
        )

    def test_elastic_events_land(self):
        wf = self.make()
        t = wire_toa_ns(1.5)
        wf.accumulate({"detector": staged([1, 2, 3], [t, t, t])})
        out = wf.finalize()
        assert float(out["counts_current"].values) == 3.0
        assert out["qmap_current"].dims == ("Qx", "Qz")
        assert out["qmap_current"].values.sum() == 3.0

    def test_axes_must_differ(self):
        with pytest.raises(ValueError, match="different components"):
            ElasticQMapParams(
                axis1=ElasticQAxis(component="Qx"),
                axis2=ElasticQAxis(component="Qx"),
            )

    def test_window_folds(self):
        wf = self.make()
        t = wire_toa_ns(1.5)
        wf.accumulate({"detector": staged([2], [t])})
        wf.finalize()
        out = wf.finalize()
        assert out["qmap_current"].values.sum() == 0.0
        assert out["qmap_cumulative"].values.sum() == 1.0


class TestRatemeter:
    def geometry(self):
        # 2 arcs x 5 pixels; arc A at 2.7 meV ids 1-5, arc B at 5.0 ids 6-10.
        two_theta = np.deg2rad(
            np.array([10, 20, 30, 40, 50, 10, 20, 30, 40, 50], dtype=float)
        )
        ef = np.array([2.7] * 5 + [5.0] * 5)
        ids = np.arange(1, 11)
        return two_theta, ef, ids

    def make(self, **params):
        two_theta, ef, ids = self.geometry()
        return RatemeterWorkflow(
            two_theta=two_theta,
            ef_mev=ef,
            pixel_ids=ids,
            params=RatemeterParams(**params),
            primary_stream="detector",
        )

    def test_counts_only_selected_arc(self):
        wf = self.make(arc_ef_mev=5.0)
        wf.accumulate({"detector": staged([1, 6, 7, 10], [1e6] * 4)})
        out = wf.finalize()
        assert float(out["detector_region_counts"].values) == 3.0

    def test_pixel_range_along_arc(self):
        # Arc at 5.0 meV sorted by two_theta: ids 6,7,8,9,10. Range [1,3)
        # selects ids 7, 8.
        wf = self.make(arc_ef_mev=5.0, pixel_start=1, pixel_stop=3)
        assert wf.n_region_pixels == 2
        wf.accumulate({"detector": staged([6, 7, 8, 9], [1e6] * 4)})
        out = wf.finalize()
        assert float(out["detector_region_counts"].values) == 2.0

    def test_window_resets_cumulative_holds(self):
        wf = self.make(arc_ef_mev=2.7)
        wf.accumulate({"detector": staged([1, 2], [1e6, 2e6])})
        wf.finalize()
        out = wf.finalize()
        assert float(out["detector_region_counts"].values) == 0.0
        assert float(out["detector_region_counts_cumulative"].values) == 2.0

    def test_unknown_arc_rejected(self):
        with pytest.raises(ValueError, match="no pixels on an arc"):
            self.make(arc_ef_mev=9.9)

    def test_range_beyond_arc_rejected(self):
        with pytest.raises(ValueError, match="beyond the arc"):
            self.make(arc_ef_mev=5.0, pixel_start=5, pixel_stop=9)

    def test_invalid_range_rejected(self):
        with pytest.raises(ValueError, match="less than"):
            RatemeterParams(pixel_start=3, pixel_stop=3)


def test_bifrost_registry_wiring():
    from esslivedata_tpu.config.instrument import instrument_registry
    from esslivedata_tpu.config.instruments.bifrost.specs import (
        ELASTIC_QMAP_HANDLE,
        RATEMETER_HANDLE,
    )
    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    instrument_registry["bifrost"].load_factories()
    for handle in (ELASTIC_QMAP_HANDLE, RATEMETER_HANDLE):
        assert handle.workflow_id in workflow_registry


def test_ratemeter_counts_long_frame_arrivals():
    # BIFROST arrivals land ~1.7e8 ns after the pulse; the default
    # window must cover them (a [0, pulse) window would read 0 forever).
    t = TestRatemeter()
    wf = t.make(arc_ef_mev=5.0)
    wf.accumulate({"detector": staged([6, 7], [elastic_toa_ns(1.5)] * 2)})
    out = wf.finalize()
    assert float(out["detector_region_counts"].values) == 2.0

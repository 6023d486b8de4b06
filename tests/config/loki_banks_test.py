"""LOKI as deployed, in the package: nine straw-tube banks beside the
toy plane, declared at import and read only when a job needs them."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent.parent
TUBES = (56, 16, 12, 16, 12, 28, 32, 20, 32)  # upstream, per layer
BANKS = [f"loki_detector_{i}" for i in range(9)]


def test_importing_the_instrument_loads_no_bank_geometry():
    """A fresh interpreter: after the import no detector holds
    positions, no geometry file has been opened (h5py is not loaded)
    and none was synthesized into the data directory."""
    code = (
        "import os, sys, tempfile\n"
        "d = tempfile.mkdtemp(); os.environ['LIVEDATA_DATA_DIR'] = d\n"
        "from esslivedata_tpu.config.instruments.loki import INSTRUMENT\n"
        "assert len(INSTRUMENT.detectors) == 10, sorted(INSTRUMENT.detectors)\n"
        "assert not any(d.geometry_loaded for d in INSTRUMENT.detectors.values())\n"
        "assert 'h5py' not in sys.modules and os.listdir(d) == []\n"
        "print(repr(INSTRUMENT.detectors['loki_detector_0']))\n"
        "assert not INSTRUMENT.detectors['loki_detector_0'].geometry_loaded\n"
    )
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert "loki_detector_0" in done.stdout


def test_the_nine_banks_have_upstreams_names_and_pixel_counts():
    from esslivedata_tpu.config.instruments.loki.geometry import BANK_PIXELS
    from esslivedata_tpu.config.instruments.loki.specs import INSTRUMENT, SANS_IQ_HANDLE
    from esslivedata_tpu.config.nexus_plans import plan_for

    assert list(BANK_PIXELS) == BANKS
    assert list(BANK_PIXELS.values()) == [4 * t * 7 * 512 for t in TUBES]
    assert sum(BANK_PIXELS.values()) == 3_211_264 and BANK_PIXELS["loki_detector_0"] == 802_816
    assert INSTRUMENT.detector_names == ["larmor_detector", *BANKS]  # the toy stays, and first
    banks = {b.name: b for b in plan_for("loki").banks}
    first = 1
    for name in BANKS:  # ids: one bank after another from 1
        plan = banks[name]
        assert (plan.first_id, plan.source, plan.topic) == (first, name, "loki_detector")
        assert int(np.prod(plan.shape)) == BANK_PIXELS[name] and plan.panel is not None
        assert INSTRUMENT.detectors[name].source_name == name
        first += BANK_PIXELS[name]
    assert first - 1 == 3_211_264
    from esslivedata_tpu.workflows.workflow_factory import workflow_registry

    spec = workflow_registry[SANS_IQ_HANDLE.workflow_id]
    assert set(BANKS) <= set(spec.source_names)
    assert {"monitor_1", "monitor_2"} <= set(spec.aux_source_names["transmission_monitor"])


@pytest.mark.parametrize("bank", ["loki_detector_4", "loki_detector_7"])
def test_a_banks_geometry_is_read_on_first_use_from_the_artifact(bank, monkeypatch, tmp_path):
    from esslivedata_tpu.config.instrument import DetectorConfig
    from esslivedata_tpu.config.instruments.loki.geometry import BANK_PIXELS, bank_geometry
    from esslivedata_tpu.config.nexus_plans import plan_for
    from esslivedata_tpu.config.nexus_synthesis import straw_positions

    monkeypatch.setenv("LIVEDATA_DATA_DIR", str(tmp_path))
    calls = []

    def loader():
        calls.append(bank)
        return bank_geometry(bank)

    det = DetectorConfig(name=bank, source_name=bank, geometry_loader=loader)
    assert not det.geometry_loaded and not calls
    plan = next(b for b in plan_for("loki").banks if b.name == bank)
    assert det.pixel_ids[0] == plan.first_id and det.pixel_ids.size == BANK_PIXELS[bank]
    assert np.array_equal(det.pixel_ids, np.arange(plan.first_id, plan.first_id + det.pixel_ids.size))
    assert np.array_equal(det.positions, straw_positions(plan.shape, plan.panel))
    assert det.geometry_loaded and calls == [bank]  # once, for both fields
    # the panel stands where the plan says, straws along the stated axis
    along = int(np.argmax(np.abs(plan.panel.along)))
    assert det.positions[:, along].max() - det.positions[:, along].min() == pytest.approx(511 / 512)
    assert det.positions.mean(axis=0) == pytest.approx(plan.panel.centre, abs=0.02)


def test_a_detector_needs_a_layout_positions_or_a_loader():
    from esslivedata_tpu.config.instrument import DetectorConfig

    with pytest.raises(ValueError, match="need a layout or positions"):
        DetectorConfig(name="d", source_name="d")
    eager = DetectorConfig(name="d", source_name="d", positions=np.zeros((2, 3)), pixel_ids=np.arange(2))
    assert eager.geometry_loaded and eager.pixel_ids.tolist() == [0, 1]

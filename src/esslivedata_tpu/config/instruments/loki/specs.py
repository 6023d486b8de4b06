"""LOKI instrument declaration + spec registration.

Geometry comes from the date-resolved NeXus artifact
(config/geometry_store.py; loki/geometry.py loads positions + pixel ids
from the file), and the f144 stream catalog is the generated registry
scanned from the same artifact (streams_parsed.py, ADR 0009) — the same
two pipelines a real deployment feeds with downloaded ESS files.
"""

from __future__ import annotations

from functools import partial

from ....config.instrument import (
    DetectorConfig,
    Instrument,
    MonitorConfig,
    instrument_registry,
)
from ....config.workflow_spec import OutputSpec, WorkflowSpec
from ....workflows.detector_view.workflow import DetectorViewParams
from ....workflows.monitor_workflow import MonitorParams
from ....workflows.sans import SansIQParams
from ....workflows.wavelength_spectrum import WavelengthSpectrumParams
from ....workflows.workflow_factory import workflow_registry
from .._common import (
    detector_view_outputs,
    register_parsed_catalog,
    register_timeseries_spec,
)
from .geometry import BANK_PIXELS, bank_geometry

from .streams_parsed import PARSED_STREAMS

INSTRUMENT = Instrument(
    name="loki",
    _factories_module="esslivedata_tpu.config.instruments.loki.factories",
)

INSTRUMENT.add_detector(
    DetectorConfig(
        name="larmor_detector",
        source_name="loki_rear_detector",
        geometry_loader=partial(bank_geometry, "larmor_detector"),
        projection="xy_plane",
        resolution=(256, 256),
        noise_sigma=0.002,
        n_replica=4,
    )
)
# The nine straw-tube banks as deployed (3 211 264 pixels), beside the
# toy plane: positions and ids are read when a job on a bank starts.
# Their 2-D view is the xy-plane projection with position noise: a
# straw pixel is 2 mm x ~8 mm against screen bins of ~4-6 mm, so each
# pixel is antialiased by four replicas of sigma 4 mm (upstream's pixel
# noise as remembered: no network; benchmark/configs/loki_banks.json
# lists it under ``assumed``).
for _bank in BANK_PIXELS:
    INSTRUMENT.add_detector(
        DetectorConfig(
            name=_bank,
            source_name=_bank,
            geometry_loader=partial(bank_geometry, _bank),
            projection="xy_plane",
            resolution=(256, 256),
            noise_sigma=0.004,
            n_replica=4,
        )
    )
INSTRUMENT.add_monitor(MonitorConfig(name="monitor_1", source_name="loki_mon_1"))
INSTRUMENT.add_monitor(MonitorConfig(name="monitor_2", source_name="loki_mon_2"))
INSTRUMENT.add_log("sample_stage_x", "loki_mtr_sx")
INSTRUMENT.add_log("sample_temperature", "loki_temp_1")
register_parsed_catalog(INSTRUMENT, PARSED_STREAMS)
instrument_registry.register(INSTRUMENT)

DETECTOR_VIEW_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="loki",
        namespace="detector_view",
        name="rear_view",
        title="Rear bank 2-D view",
        source_names=["larmor_detector"],
        params_model=DetectorViewParams,
        outputs={
            **detector_view_outputs(),  # incl. the ROI readbacks
            "roi_spectra": OutputSpec(title="ROI spectra (window)"),
            "roi_spectra_cumulative": OutputSpec(
                title="ROI spectra (since start)", view="since_start"
            ),
        },
    )
)

# The deployed view: one job per bank, every bank on its own xy plane
# (``rear_view`` above stays on the toy plane for the package's tests).
XY_PROJECTION_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="loki",
        namespace="detector_view",
        name="xy_projection",
        title="Bank 2-D view (xy plane, pixel noise)",
        source_names=list(BANK_PIXELS),
        params_model=DetectorViewParams,
        outputs=detector_view_outputs(),  # incl. the ROI readbacks
    )
)

MONITOR_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="loki",
        namespace="monitor_data",
        name="histogram",
        title="Monitor TOA histogram",
        source_names=INSTRUMENT.monitor_names,
        params_model=MonitorParams,
        outputs={
            "current": OutputSpec(title="Monitor (window)"),
            "cumulative": OutputSpec(title="Monitor (since start)", view="since_start"),
            "counts_current": OutputSpec(title="Counts (window)"),
            "counts_cumulative": OutputSpec(
                title="Counts (since start)", view="since_start"
            ),
        },
        device_outputs={"counts_cumulative": "monitor_counts_{source_name}"},
    )
)

SANS_IQ_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="loki",
        namespace="sans",
        name="iq",
        title="Monitor-normalized I(Q)",
        source_names=INSTRUMENT.detector_names,
        aux_source_names={
            "monitor": INSTRUMENT.monitor_names,
            "transmission_monitor": INSTRUMENT.monitor_names,
        },
        params_model=SansIQParams,
        outputs={
            "iq_current": OutputSpec(title="I(Q) (window)"),
            "iq_cumulative": OutputSpec(title="I(Q) (since start)", view="since_start"),
            "counts_q_current": OutputSpec(title="Q counts (window)"),
            "monitor_counts_current": OutputSpec(title="Monitor counts"),
            "transmission_current": OutputSpec(title="Transmission fraction"),
        },
    )
)

WAVELENGTH_SPECTRUM_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="loki",
        namespace="sans",
        name="wavelength_spectrum",
        title="Detector wavelength spectrum",
        source_names=INSTRUMENT.detector_names,
        service="data_reduction",
        aux_source_names={"monitor": INSTRUMENT.monitor_names},
        params_model=WavelengthSpectrumParams,
        outputs={
            "wavelength_current": OutputSpec(title="I(lambda) (window)"),
            "wavelength_cumulative": OutputSpec(
                title="I(lambda) (since start)", view="since_start"
            ),
            "wavelength_normalized": OutputSpec(
                title="I(lambda) / monitor", view="since_start"
            ),
            "counts_current": OutputSpec(title="Events binned"),
        },
    )
)

TIMESERIES_HANDLE = register_timeseries_spec(INSTRUMENT)

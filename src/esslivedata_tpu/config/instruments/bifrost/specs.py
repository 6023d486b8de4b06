"""BIFROST declaration: 45 analyzer triplets merged into one logical stream.

The deployed instrument (upstream ``config/instruments/bifrost/specs.py``,
as remembered: no network here) has 5 analyzer arcs (final energies 2.7,
3.2, 3.8, 4.4, 5.0 meV) x 9 channels = 45 triplets of 3 position-sensitive
tubes x 100 pixels = 13 500 pixels. Every triplet is its own ev44 source
on the detector topic, and ``merge_detectors`` routes all of them onto one
logical stream that the spectroscopy workflows reduce. Q-E per-analyzer
rebinning (the full spectrometer physics) runs on the Q kernel family via
a precompiled (pixel, toa) -> (Q, E)-bin map: see QE_HANDLE below and
workflows/qe_spectroscopy.py.

Declared departure from upstream: there the detector ids fold as
arc x tube x channel x pixel, so a triplet's ids are three runs of 100;
here a triplet is one contiguous block of 300 (triplet ``a * 9 + c``
holds ids ``1 + 300 (a * 9 + c)`` onwards, tube-major). The merged id
space is 1..13 500 either way.
"""

from __future__ import annotations

import numpy as np

from ....config.instrument import (
    DetectorConfig,
    Instrument,
    MonitorConfig,
    instrument_registry,
)
from ....config.workflow_spec import OutputSpec, WorkflowSpec
from ....workflows.elastic_qmap import ElasticQMapParams
from ....workflows.multibank import MultiBankParams
from ....workflows.qe_spectroscopy import QESpectroscopyParams
from ....workflows.ratemeter import RatemeterParams
from ....workflows.workflow_factory import workflow_registry
from .._common import register_monitor_spec, register_parsed_catalog

#: Analyzer final energy of each arc (meV): the energy axis.
ARC_EF_MEV = (2.7, 3.2, 3.8, 4.4, 5.0)
N_ARCS = len(ARC_EF_MEV)
#: Channels (wedges) per arc: the angle axis.
N_CHANNELS = 9
N_TRIPLETS = N_ARCS * N_CHANNELS
TUBES_PER_TRIPLET, PIXELS_PER_TUBE = 3, 100
PIXELS_PER_TRIPLET = TUBES_PER_TRIPLET * PIXELS_PER_TUBE
N_PIXELS = N_TRIPLETS * PIXELS_PER_TRIPLET

from .streams_parsed import PARSED_STREAMS

INSTRUMENT = Instrument(
    name="bifrost",
    merge_detectors=True,
    _factories_module="esslivedata_tpu.config.instruments.bifrost.factories",
)

#: triplet -> its [tube, pixel] detector numbers, arc-major.
BANK_DETECTOR_NUMBERS: dict[str, np.ndarray] = {}
for arc in range(N_ARCS):
    for channel in range(N_CHANNELS):
        start = 1 + (arc * N_CHANNELS + channel) * PIXELS_PER_TRIPLET
        det = np.arange(start, start + PIXELS_PER_TRIPLET).reshape(
            TUBES_PER_TRIPLET, PIXELS_PER_TUBE
        )
        name = f"triplet_{arc}_{channel}"
        BANK_DETECTOR_NUMBERS[name] = det
        INSTRUMENT.add_detector(
            DetectorConfig(
                name=name,
                source_name=f"bifrost_{name}",
                detector_number=det,
                projection="logical",
            )
        )
register_parsed_catalog(INSTRUMENT, PARSED_STREAMS)
INSTRUMENT.add_monitor(
    MonitorConfig(name="monitor_1", source_name="bifrost_mon_1")
)
instrument_registry.register(INSTRUMENT)

# The merged stream name all triplets adapt onto (merge_detectors routing).
MERGED_STREAM = "detector"

MULTIBANK_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="bifrost",
        namespace="spectrometer",
        name="bank_overview",
        title="45-triplet overview (mesh-shardable)",
        source_names=[MERGED_STREAM],
        # Consumes detector events: hosted by the detector service even
        # though its display namespace is 'spectrometer'.
        service="detector_data",
        params_model=MultiBankParams,
        outputs={
            "bank_spectra_current": OutputSpec(title="Per-bank TOA spectra"),
            "bank_spectra_cumulative": OutputSpec(
                title="Per-bank TOA spectra (since start)", view="since_start"
            ),
            "bank_counts_current": OutputSpec(title="Per-bank counts"),
            "bank_counts_cumulative": OutputSpec(
                title="Per-bank counts (since start)", view="since_start"
            ),
            "counts_current": OutputSpec(title="Total counts (window)"),
            "counts_cumulative": OutputSpec(
                title="Total counts (since start)", view="since_start"
            ),
        },
    )
)

MONITOR_HANDLE = register_monitor_spec(INSTRUMENT)


#: The placeholder geometry's numbers (``analyzer_geometry``).
CHANNEL_TWO_THETA_DEG = (15.0, 150.0)  # first and last wedge centre
CHANNEL_HALF_SPREAD_DEG = 4.0  # along a tube, either side of the centre
TUBE_AZIMUTH_DEG = (-2.0, 0.0, 2.0)
ARC_L2_M = (1.2, 0.25)  # innermost arc's secondary path, and the step an arc


def analyzer_geometry() -> dict[str, np.ndarray]:
    """Synthetic per-pixel analyzer geometry for the 45-triplet layout,
    in the order of the detector numbers.

    Placeholder physics in the spirit of the instrument (a deployment
    regenerates it from the facility geometry file): the scattering
    angle goes by channel, the nine wedges fanning over 15-150 degrees
    with a tube's 100 pixels spreading +-4 degrees inside the wedge;
    a triplet's three tubes sit -2, 0 and +2 degrees out of the
    scattering plane, which gives the elastic Qy axis its structure;
    the final energy and the secondary flight path (sample -> analyzer
    -> detector) go by arc, the path growing with the analyzer radius.
    """
    arc, channel, tube, pixel = np.unravel_index(
        np.arange(N_PIXELS),
        (N_ARCS, N_CHANNELS, TUBES_PER_TRIPLET, PIXELS_PER_TUBE),
    )
    first, last = CHANNEL_TWO_THETA_DEG
    centre = first + channel * ((last - first) / (N_CHANNELS - 1))
    along = np.linspace(
        -CHANNEL_HALF_SPREAD_DEG, CHANNEL_HALF_SPREAD_DEG, PIXELS_PER_TUBE
    )
    return {
        "two_theta": np.deg2rad(centre + along[pixel]),
        "azimuth": np.deg2rad(np.asarray(TUBE_AZIMUTH_DEG)[tube]),
        "ef_mev": np.asarray(ARC_EF_MEV)[arc],
        "l2": ARC_L2_M[0] + ARC_L2_M[1] * arc,
        "pixel_ids": np.concatenate(
            [det.reshape(-1) for det in BANK_DETECTOR_NUMBERS.values()]
        ).astype(np.int64),
    }


QE_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="bifrost",
        namespace="spectrometer",
        name="qe_map",
        title="S(Q, E) map (indirect-geometry rebinning)",
        source_names=[MERGED_STREAM],
        service="data_reduction",
        aux_source_names={"monitor": ["monitor_1"]},
        params_model=QESpectroscopyParams,
        outputs={
            "sqw_current": OutputSpec(title="S(Q, E) — window"),
            "sqw_cumulative": OutputSpec(
                title="S(Q, E) — since start", view="since_start"
            ),
            "sqw_normalized": OutputSpec(
                title="S(Q, E) / monitor", view="since_start"
            ),
            "counts_current": OutputSpec(title="Events binned"),
            "monitor_counts_current": OutputSpec(title="Monitor counts"),
        },
    )
)


ELASTIC_QMAP_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="bifrost",
        namespace="spectrometer",
        name="elastic_qmap",
        title="Elastic Q map",
        source_names=[MERGED_STREAM],
        service="data_reduction",
        aux_source_names={"monitor": ["monitor_1"]},
        params_model=ElasticQMapParams,
        outputs={
            "qmap_current": OutputSpec(title="Elastic Q map — window"),
            "qmap_cumulative": OutputSpec(
                title="Elastic Q map — since start", view="since_start"
            ),
            "qmap_normalized": OutputSpec(
                title="Elastic Q map / monitor", view="since_start"
            ),
            "counts_current": OutputSpec(title="Elastic events binned"),
        },
    )
)

RATEMETER_HANDLE = workflow_registry.register_spec(
    WorkflowSpec(
        instrument="bifrost",
        namespace="spectrometer",
        name="detector_ratemeter",
        title="Detector ratemeter",
        source_names=[MERGED_STREAM],
        service="detector_data",
        params_model=RatemeterParams,
        outputs={
            "detector_region_counts": OutputSpec(
                title="Detector region counts (window)"
            ),
            "detector_region_counts_cumulative": OutputSpec(
                title="Detector region counts (since start)",
                view="since_start",
            ),
        },
    )
)

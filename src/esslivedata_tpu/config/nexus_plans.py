"""Declarative NeXus-artifact plans for every built-in instrument.

Each plan describes the instrument's NeXus file at the fidelity the
framework consumes: detector banks (geometry or logical layout), monitors,
choppers, motorised devices and sample-environment logs, each carrying the
file-writer stream declaration (topic/source/writer_module). Source names
of banks and monitors match the instrument's ``specs.py`` so the generated
stream registry and the hand-declared event routing agree.

These plans stand in for real ESS geometry files (which a deployment
fetches into ``LIVEDATA_DATA_DIR``; reference
preprocessors/detector_data.py:66-127): the synthesized file has the same
structure, so swapping a real artifact in requires no code change.
Group paths and EPICS PV spellings in the plans are deliberately this
codebase's own *placeholders*, not transcriptions of facility names: a
deployment installs the real geometry file
(``scripts/fetch_geometry.py install``) and regenerates the registries
from it (``scripts/generate_instrument_artifacts.py`` /
``python -m esslivedata_tpu.config.nexus_streams``), which restores the
facility's actual paths and sources end to end.

PV naming follows the EPICS motor-record convention (``<base>.RBV`` /
``.VAL`` / ``.DMOV``) that stream.name_streams device detection keys on.
"""

from __future__ import annotations

from .nexus_synthesis import (
    BankPlan,
    ChopperPlan,
    DevicePlan,
    InstrumentNexusPlan,
    LogPlan,
    MonitorPlan,
    StrawPanel,
)

__all__ = ["NEXUS_PLANS", "plan_for"]


def _slit(group: str, pv_base: str, topic: str) -> tuple[DevicePlan, ...]:
    """A 4-axis slit: horizontal/vertical gap + centre."""
    return tuple(
        DevicePlan(
            group=f"{group}/{axis}",
            pv=f"{pv_base}-{tag}-01:Mtr",
            topic=topic,
        )
        for axis, tag in (
            ("x_gap", "SlGapX"),
            ("y_gap", "SlGapY"),
            ("x_center", "SlCenX"),
            ("y_center", "SlCenY"),
        )
    )


def _stage(
    group: str, pv_base: str, topic: str, axes: tuple[tuple[str, str, str], ...]
) -> tuple[DevicePlan, ...]:
    """A multi-axis stage; axes = (group_leaf, pv_tag, units)."""
    return tuple(
        DevicePlan(
            group=f"{group}/{leaf}",
            pv=f"{pv_base}-{tag}-01:Mtr",
            topic=topic,
            units=units,
        )
        for leaf, tag, units in axes
    )


_XYZ_OMEGA = (
    ("x", "LinX", "mm"),
    ("y", "LinY", "mm"),
    ("z", "LinZ", "mm"),
    ("omega", "RotZ", "deg"),
)


def _sample_env(instrument: str, n_temp: int = 2) -> tuple[LogPlan, ...]:
    """Typical sample-environment block: temperatures, pressure, field."""
    topic = f"{instrument}_sample_env"
    logs = [
        LogPlan(
            group=f"sample/temperature_{i}",
            source=f"{instrument.upper()}-SE:Tmp-TIC-{100 + i}",
            topic=topic,
            units="K",
        )
        for i in range(1, n_temp + 1)
    ]
    logs.append(
        LogPlan(
            group="sample/pressure",
            source=f"{instrument.upper()}-SE:Prs-PIC-101",
            topic=topic,
            units="bar",
        )
    )
    logs.append(
        LogPlan(
            group="sample/magnetic_field",
            source=f"{instrument.upper()}-SE:Mag-PSU-101",
            topic=topic,
            units="T",
        )
    )
    return tuple(logs)


def _vacuum(instrument: str, n: int = 4) -> tuple[LogPlan, ...]:
    """Vacuum gauges on an *unauthorized* topic: these exercise
    ``filter_authorized_streams`` (the ``_vacuum`` topic has no PROD ACL
    grant, so registry consumers must drop them)."""
    return tuple(
        LogPlan(
            group=f"vacuum/gauge_{i}",
            source=f"{instrument.upper()}-Vac:VGP-{i:03d}",
            topic=f"{instrument}_vacuum",
            units="mbar",
        )
        for i in range(1, n + 1)
    )


#: LOKI as deployed (upstream config/instruments/loki/specs.py, from
#: memory): nine straw-tube banks ``loki_detector_0`` .. ``_8`` of
#: 4 layers x this many tubes x 7 straws x 512 pixels, ids one bank
#: after another from 1: 3 211 264 pixels, 802 816 of them in bank 0.
_LOKI_BANK_TUBES = (56, 16, 12, 16, 12, 28, 32, 20, 32)
_LOKI_LAYERS, _LOKI_STRAWS, _LOKI_PIXELS = 4, 7, 512

_X, _Y, _Z = (1.0, 0.0, 0.0), (0.0, 1.0, 0.0), (0.0, 0.0, 1.0)
#: Where each bank's panel stands (synthesized; a real artifact in the
#: data directory replaces it): centre in m and the direction of its
#: straws. Bank 0 is the rear panel, 5 m downstream on the beam; banks
#: 1-4 frame the beam 3.0 / 3.2 m from the sample (top, left, bottom,
#: right), banks 5-8 1.5 / 1.7 m from it. Tubes stack across the
#: straws, layers along the beam.
_LOKI_PANELS = (
    ((0.0, 0.0, 5.0), _X),
    ((0.0, 0.45, 3.0), _X),
    ((0.4, 0.0, 3.2), _Y),
    ((0.0, -0.45, 3.0), _X),
    ((-0.4, 0.0, 3.2), _Y),
    ((0.0, 0.65, 1.5), _X),
    ((0.7, 0.0, 1.7), _Y),
    ((0.0, -0.65, 1.5), _X),
    ((-0.7, 0.0, 1.7), _Y),
)


def _loki_banks() -> tuple[BankPlan, ...]:
    """The nine banks, ids consecutive from 1. Straws are 1 m long
    (512 pixels of 1/512 m); tubes 28.4 mm apart, layers 24.6 mm, the
    six outer straws 7.8 mm off the tube's axis."""
    banks, first_id = [], 1
    for i, (tubes, (centre, along)) in enumerate(
        zip(_LOKI_BANK_TUBES, _LOKI_PANELS, strict=True)
    ):
        shape = (_LOKI_LAYERS, tubes, _LOKI_STRAWS, _LOKI_PIXELS)
        banks.append(
            BankPlan(
                name=f"loki_detector_{i}",
                source=f"loki_detector_{i}",
                topic="loki_detector",
                shape=shape,
                first_id=first_id,
                panel=StrawPanel(
                    centre=centre,
                    along=along,
                    across=_Y if along == _X else _X,
                    normal=_Z,
                    pixel_pitch=1.0 / _LOKI_PIXELS,
                    tube_pitch=0.0284,
                    layer_pitch=0.0246,
                    straw_radius=0.0078,
                ),
            )
        )
        first_id += shape[0] * shape[1] * shape[2] * shape[3]
    return tuple(banks)


_LOKI = InstrumentNexusPlan(
    name="loki",
    title="LOKI small-angle scattering",
    banks=(
        # the toy plane the detector view and the package's tests run on;
        # its ids overlap bank 0's (another source on the same topic)
        BankPlan(
            name="larmor_detector",
            source="loki_rear_detector",
            topic="loki_detector",
            shape=(256, 256),
            extent=(1.0, 1.0),
            z=5.0,
        ),
        *_loki_banks(),
    ),
    monitors=tuple(
        MonitorPlan(
            name=f"beam_monitor_{i}",
            source=f"loki_mon_{i}",
            topic="loki_monitor",
            z=-2.0 - i,
            positioner_pv=f"LOKI-BMon{i}:MC-LinZ-01:Mtr",
            positioner_topic="loki_motion",
        )
        for i in range(5)
    ),
    choppers=(
        ChopperPlan(name="bandwidth_chopper_1", pv="LOKI-Chop:BWC-01", topic="loki_choppers"),
        ChopperPlan(name="bandwidth_chopper_2", pv="LOKI-Chop:BWC-02", topic="loki_choppers"),
    ),
    devices=(
        *_slit("collimation_slit_1", "LOKI-ColSl1:MC", "loki_motion"),
        *_slit("collimation_slit_2", "LOKI-ColSl2:MC", "loki_motion"),
        *_slit("collimation_slit_3", "LOKI-ColSl3:MC", "loki_motion"),
        *_slit("sample_slit", "LOKI-SmplSl:MC", "loki_motion"),
        *_stage("sample_stage", "LOKI-Smpl:MC", "loki_motion", _XYZ_OMEGA),
        DevicePlan(
            group="detector_carriage/z",
            pv="LOKI-DetCar:MC-LinZ-01:Mtr",
            topic="loki_motion",
            units="m",
        ),
        DevicePlan(
            group="transmission_flag/state",
            pv="LOKI-TrFlag:MC-RotY-01:Mtr",
            topic="loki_motion",
            units="deg",
            with_target=False,
        ),
    ),
    logs=(
        *_sample_env("loki", n_temp=4),
        *_vacuum("loki"),
        *(
            LogPlan(
                group=f"detector_env/bank_temperature_{i}",
                source=f"LOKI-Det:Tmp-TIC-{200 + i}",
                topic="loki_sample_env",
                units="K",
            )
            for i in range(1, 10)
        ),
    ),
)


_DREAM_BANKS = {
    # (flattened shapes matching specs.BANK_SIZES products; the file keeps
    # the full N-d layout so logical views can index named axes)
    "mantle_detector": (32, 5, 6, 256, 2),
    "endcap_backward_detector": (16, 16, 11, 28, 2),
    "endcap_forward_detector": (16, 16, 5, 28, 2),
    "high_resolution_detector": (32, 16, 3, 20, 2),
    "sans_detector": (32, 16, 3, 10, 2),
}

_DREAM = InstrumentNexusPlan(
    name="dream",
    title="DREAM powder diffractometer",
    banks=(),  # filled by _with_contiguous_bank_ids below
    monitors=(
        MonitorPlan(
            name="monitor_bunker",
            source="dream_mon_bunker",
            topic="dream_monitor",
            z=-18.0,
        ),
        MonitorPlan(
            name="monitor_cave",
            source="dream_mon_cave",
            topic="dream_monitor",
            z=-1.5,
            positioner_pv="DREAM-MonC:MC-LinZ-01:Mtr",
            positioner_topic="dream_motion",
        ),
    ),
    choppers=(
        ChopperPlan(name="pulse_shaping_chopper1", pv="pulse_shaping_chopper1", topic="dream_choppers"),
        ChopperPlan(name="pulse_shaping_chopper2", pv="pulse_shaping_chopper2", topic="dream_choppers"),
        ChopperPlan(name="band_chopper", pv="band_chopper", topic="dream_choppers"),
        ChopperPlan(name="overlap_chopper", pv="overlap_chopper", topic="dream_choppers"),
        ChopperPlan(name="T0_chopper", pv="T0_chopper", topic="dream_choppers"),
    ),
    devices=(
        *_slit("divergence_slit", "DREAM-DivSl:MC", "dream_motion"),
        *_stage("sample_stage", "DREAM-Smpl:MC", "dream_motion", _XYZ_OMEGA),
        *_stage(
            "collimator",
            "DREAM-Coll:MC",
            "dream_motion",
            (("rotation", "RotZ", "deg"), ("z", "LinZ", "mm")),
        ),
        DevicePlan(
            group="polarizer/state",
            pv="DREAM-Pol:MC-LinX-01:Mtr",
            topic="dream_motion",
        ),
    ),
    logs=(
        *_sample_env("dream", n_temp=3),
        *_vacuum("dream", n=3),
    ),
)


def _with_contiguous_bank_ids(
    plan: InstrumentNexusPlan, banks: dict[str, tuple[int, ...]]
) -> InstrumentNexusPlan:
    """Rebuild a plan's banks with contiguous first_ids, matching the
    ``arange``-per-bank layout the instrument specs declare."""
    import dataclasses

    import numpy as np

    out = []
    offset = 1
    for name, shape in banks.items():
        out.append(
            BankPlan(
                name=name,
                source=f"{plan.name}_{name}",
                topic=f"{plan.name}_detector",
                shape=shape,
                first_id=offset,
                logical=True,
            )
        )
        offset += int(np.prod(shape))
    return dataclasses.replace(plan, banks=tuple(out))


_DREAM = _with_contiguous_bank_ids(_DREAM, _DREAM_BANKS)


_BIFROST = _with_contiguous_bank_ids(
    InstrumentNexusPlan(
        name="bifrost",
        title="BIFROST indirect-geometry spectrometer",
        monitors=(
            MonitorPlan(
                name="monitor_1",
                source="bifrost_mon_1",
                topic="bifrost_monitor",
                z=-2.0,
            ),
        ),
        choppers=(
            ChopperPlan(name="pulse_shaping_chopper", pv="BIFR-Chop:PSC-01", topic="bifrost_choppers"),
            ChopperPlan(name="frame_overlap_chopper", pv="BIFR-Chop:FOC-01", topic="bifrost_choppers"),
        ),
        devices=(
            *_stage("sample_stage", "BIFR-Smpl:MC", "bifrost_motion", _XYZ_OMEGA),
            *(
                DevicePlan(
                    group=f"analyzer_{i}/goniometer",
                    pv=f"BIFR-Ana{i}:MC-RotX-01:Mtr",
                    topic="bifrost_motion",
                    units="deg",
                )
                for i in range(1, 10)
            ),
        ),
        logs=(
            *_sample_env("bifrost"),
            *(
                LogPlan(
                    group=f"analyzer_env/temperature_{i}",
                    source=f"BIFR-Ana:Tmp-TIC-{i:03d}",
                    topic="bifrost_sample_env",
                    units="K",
                )
                for i in range(1, 10)
            ),
        ),
    ),
    # 5 arcs x 9 channels of 3 tubes x 100 pixels, arc-major, as
    # instruments/bifrost/specs.py declares them
    {
        f"triplet_{arc}_{channel}": (3, 100)
        for arc in range(5)
        for channel in range(9)
    },
)


_ESTIA = InstrumentNexusPlan(
    name="estia",
    title="ESTIA reflectometer",
    banks=(
        BankPlan(
            name="multiblade_detector",
            source="estia_multiblade",
            topic="estia_detector",
            shape=(48, 32, 64),
            logical=True,
        ),
    ),
    monitors=(
        MonitorPlan(
            name="cbm1", source="estia_cbm1", topic="estia_monitor", z=-1.0
        ),
    ),
    choppers=(
        ChopperPlan(name="chopper_1", pv="ESTIA-Chop:C1", topic="estia_choppers"),
        ChopperPlan(name="chopper_2", pv="ESTIA-Chop:C2", topic="estia_choppers"),
    ),
    devices=(
        *_slit("slit_1", "ESTIA-Sl1:MC", "estia_motion"),
        *_slit("slit_2", "ESTIA-Sl2:MC", "estia_motion"),
        *_stage(
            "sample_stage",
            "ESTIA-Smpl:MC",
            "estia_motion",
            (*_XYZ_OMEGA, ("chi", "RotX", "deg")),
        ),
        DevicePlan(
            group="detector_arm/two_theta",
            pv="ESTIA-DetArm:MC-RotZ-01:Mtr",
            topic="estia_motion",
            units="deg",
        ),
    ),
    logs=_sample_env("estia"),
)


_NMX = _with_contiguous_bank_ids(
    InstrumentNexusPlan(
        name="nmx",
        title="NMX macromolecular diffractometer",
        monitors=(
            MonitorPlan(name="monitor1", source="nmx_mon_1", topic="nmx_monitor", z=-3.0),
            MonitorPlan(name="monitor2", source="nmx_mon_2", topic="nmx_monitor", z=-0.5),
        ),
        choppers=(
            ChopperPlan(name="chopper_1", pv="NMX-Chop:C1", topic="nmx_choppers"),
        ),
        devices=(
            *_stage("sample_stage", "NMX-Smpl:MC", "nmx_motion", _XYZ_OMEGA),
            *(
                d
                for i in range(3)
                for d in _stage(
                    f"detector_panel_{i}",
                    f"NMX-Det{i}:MC",
                    "nmx_motion",
                    (("distance", "LinZ", "m"), ("rotation", "RotZ", "deg")),
                )
            ),
        ),
        logs=_sample_env("nmx"),
    ),
    {f"detector_panel_{i}": (1280, 1280) for i in range(3)},
)


def _blade_slit(group: str, pv_base: str, topic: str) -> tuple[DevicePlan, ...]:
    """A 6-axis collimation slit: gap/centre per direction plus the two
    individually motorized vertical blades (the ym/yp pattern imaging
    beamlines use for asymmetric collimation)."""
    return (
        *_slit(group, pv_base, topic),
        DevicePlan(group=f"{group}/ym", pv=f"{pv_base}-BldYm-01:Mtr", topic=topic),
        DevicePlan(group=f"{group}/yp", pv=f"{pv_base}-BldYp-01:Mtr", topic=topic),
    )


# ODIN is the cardinality proof: the registry pipeline (synthesis ->
# parse -> authorization filter -> naming -> device detection -> route
# derivation) runs at the reference's real scale (~280 f144 streams:
# 10 choppers, ~66 motorized axes, sample-env/vacuum/beam logs).
_ODIN = InstrumentNexusPlan(
    name="odin",
    title="ODIN imaging beamline",
    banks=(
        BankPlan(
            name="timepix3",
            source="odin_timepix3",
            topic="odin_detector",
            shape=(512, 512),
            logical=True,
        ),
    ),
    monitors=(
        MonitorPlan(name="monitor1", source="odin_mon_1", topic="odin_monitor", z=-10.0),
        MonitorPlan(name="monitor2", source="odin_mon_2", topic="odin_monitor", z=-0.2),
    ),
    choppers=(
        # WFM pair + band-pass pair + five frame-overlap choppers + T0:
        # the reference ODIN cascade's composition.
        *(
            ChopperPlan(name=f"wfm_chopper_{i}", pv=f"ODIN-Chop:WFM-{i:02d}", topic="odin_choppers")
            for i in (1, 2)
        ),
        *(
            ChopperPlan(name=f"bpc_chopper_{i}", pv=f"ODIN-Chop:BPC-{i:02d}", topic="odin_choppers")
            for i in (1, 2)
        ),
        *(
            ChopperPlan(name=f"foc_chopper_{i}", pv=f"ODIN-Chop:FOC-{i:02d}", topic="odin_choppers")
            for i in range(1, 6)
        ),
        ChopperPlan(name="t0_chopper", pv="ODIN-Chop:T0-01", topic="odin_choppers"),
    ),
    devices=(
        *_stage(
            "sample_stage",
            "ODIN-Smpl:MC",
            "odin_motion",
            (
                *_XYZ_OMEGA,
                ("phi", "RotX", "deg"),
                ("tilt", "RotY", "deg"),
            ),
        ),
        DevicePlan(
            group="heavy_shutter",
            pv="ODIN-Shtr:MC-Lin-01:Mtr",
            topic="odin_motion",
        ),
        # Two camera boxes, each with its own optics axes.
        *(
            plan
            for i in (1, 2)
            for plan in _stage(
                f"camera{i}",
                f"ODIN-Cam{i}:MC",
                "odin_motion",
                (
                    ("distance", "LinZ", "mm"),
                    ("focus", "LinF", "mm"),
                    ("rotation", "Rot", "deg"),
                ),
            )
        ),
        # ANC piezo cluster at the sample position.
        DevicePlan(group="anc_goniometer", pv="ODIN-ANC:MC-Gon-01:Mtr", topic="odin_motion", units="deg"),
        DevicePlan(group="anc_rotary", pv="ODIN-ANC:MC-Rot-01:Mtr", topic="odin_motion", units="deg"),
        DevicePlan(group="anc_linear_1", pv="ODIN-ANC:MC-Lin-01:Mtr", topic="odin_motion"),
        DevicePlan(group="anc_linear_2", pv="ODIN-ANC:MC-Lin-02:Mtr", topic="odin_motion"),
        # Four 6-axis collimation slit packages along the guide.
        *(
            plan
            for i in (1, 2, 3, 4)
            for plan in _blade_slit(
                f"col_slit_{i}", f"ODIN-ColS{i}:MC", "odin_motion"
            )
        ),
        *_slit("pinhole_selector", "ODIN-PinH:MC", "odin_motion"),
        # Two aperture diaphragms near the detector.
        *(
            plan
            for i in (1, 2)
            for plan in _slit(f"diaphragm_{i}", f"ODIN-Diaph{i}:MC", "odin_motion")
        ),
        DevicePlan(group="filter_changer_1", pv="ODIN-Filt:MC-Whl-01:Mtr", topic="odin_motion", units="deg"),
        DevicePlan(group="filter_changer_2", pv="ODIN-Filt:MC-Whl-02:Mtr", topic="odin_motion", units="deg"),
        *_stage(
            "detector_stage",
            "ODIN-Det:MC",
            "odin_motion",
            (("x", "LinX", "mm"), ("z", "LinZ", "mm"), ("rotation", "Rot", "deg")),
        ),
        DevicePlan(group="beam_stop/x", pv="ODIN-BStp:MC-LinX-01:Mtr", topic="odin_motion"),
        DevicePlan(group="beam_stop/y", pv="ODIN-BStp:MC-LinY-01:Mtr", topic="odin_motion"),
        DevicePlan(group="attenuator_wheel_1", pv="ODIN-Att:MC-Whl-01:Mtr", topic="odin_motion", units="deg"),
        DevicePlan(group="attenuator_wheel_2", pv="ODIN-Att:MC-Whl-02:Mtr", topic="odin_motion", units="deg"),
        DevicePlan(group="polarizer/rotation", pv="ODIN-Pol:MC-Rot-01:Mtr", topic="odin_motion", units="deg"),
        DevicePlan(group="polarizer/translation", pv="ODIN-Pol:MC-Lin-01:Mtr", topic="odin_motion"),
        DevicePlan(group="grating_stage/x", pv="ODIN-Grt:MC-LinX-01:Mtr", topic="odin_motion"),
        DevicePlan(group="grating_stage/z", pv="ODIN-Grt:MC-LinZ-01:Mtr", topic="odin_motion"),
    ),
    logs=(
        *_sample_env("odin", n_temp=4),
        *_vacuum("odin", n=8),
        # Beam diagnostics on the general-data topic (authorized).
        *(
            LogPlan(
                group=f"beam_monitoring/{name}",
                source=f"ODIN-Beam:{pv}",
                topic="tn_data_general",
                units=units,
            )
            for name, pv, units in (
                ("proton_current", "PBI-ICT-001", "uA"),
                ("proton_charge", "PBI-ICT-002", "uC"),
                ("target_temperature", "Tgt-TT-001", "K"),
                ("moderator_temperature", "Mod-TT-001", "K"),
            )
        ),
        # Helium-3 polarization cell telemetry.
        *(
            LogPlan(
                group=f"polarizer/{name}",
                source=f"ODIN-Pol:SE-{pv}",
                topic="odin_sample_env",
                units=units,
            )
            for name, pv, units in (
                ("cell_polarization", "Pol-001", "dimensionless"),
                ("cell_temperature", "TT-001", "K"),
            )
        ),
    ),
)


_TBL = InstrumentNexusPlan(
    name="tbl",
    title="TBL test beamline",
    banks=(
        BankPlan(
            name="panel",
            source="tbl_panel",
            topic="tbl_detector",
            shape=(64, 64),
            logical=True,
        ),
    ),
    monitors=(
        MonitorPlan(name="monitor", source="tbl_mon_1", topic="tbl_monitor", z=-1.0),
    ),
    choppers=(
        ChopperPlan(name="chopper", pv="chopper", topic="tbl_choppers"),
    ),
    devices=(
        *_stage(
            "sample_stage",
            "TBL-Smpl:MC",
            "tbl_motion",
            (("x", "LinX", "mm"), ("z", "LinZ", "mm")),
        ),
    ),
    logs=_sample_env("tbl", n_temp=1),
)


_DUMMY = InstrumentNexusPlan(
    name="dummy",
    title="Dummy development instrument",
    banks=(
        BankPlan(
            name="panel_0",
            source="panel_a",
            topic="dummy_detector",
            shape=(64, 64),
            logical=True,
        ),
    ),
    monitors=(
        MonitorPlan(name="monitor_1", source="mon_src", topic="dummy_monitor", z=-1.0),
    ),
    devices=(
        # NB: not named motor_x — that name is the hand-declared log stream
        # in dummy/specs.py and the two must stay distinct in the LUT.
        DevicePlan(
            group="sample_changer/position",
            pv="DMY-MC:SmplPos",
            topic="dummy_motion",
        ),
    ),
    logs=_sample_env("dummy", n_temp=1),
)


NEXUS_PLANS: dict[str, InstrumentNexusPlan] = {
    p.name: p
    for p in (
        _LOKI,
        _DREAM,
        _BIFROST,
        _ESTIA,
        _NMX,
        _ODIN,
        _TBL,
        _DUMMY,
    )
}


def plan_for(instrument: str) -> InstrumentNexusPlan:
    try:
        return NEXUS_PLANS[instrument]
    except KeyError:
        raise KeyError(
            f"No NeXus plan for instrument {instrument!r}; "
            f"known: {sorted(NEXUS_PLANS)}"
        ) from None

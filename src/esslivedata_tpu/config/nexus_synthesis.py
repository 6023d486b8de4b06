"""Synthesize instrument NeXus geometry artifacts.

The reference framework loads instrument geometry and its f144 stream
catalog from NeXus files fetched by a pooch registry
(reference: preprocessors/detector_data.py:66-127,
scripts/download_geometry.py, nexus_helpers.py). This environment has no
egress, so the artifacts are *synthesized* from declarative per-instrument
plans (``nexus_plans.py``) into files with the same structure real ESS
files have:

- NXdetector banks with ``detector_number``, pixel offsets and a
  ``NXevent_data`` group carrying ``topic``/``source``/``writer_module``
  attributes (the file-writer stream declaration convention);
- NXmonitor groups with ev44 event streams and motorised positioners;
- NXdisk_chopper groups with f144 rotation_speed/delay/phase logs;
- NXpositioner device groups whose ``value``/``target_value``/``idle_flag``
  NXlog children carry EPICS motor-record source suffixes
  (``.RBV``/``.VAL``/``.DMOV``) — the pattern ``stream.name_streams``
  detects and merges into synthesised Device streams;
- plain NXlog sample-environment / vacuum streams.

Everything downstream is identical to a real deployment: the stream
registry is *generated from the file* (``nexus_streams.py``), geometry is
*loaded from the file* (``geometry_store.py``), and swapping in a real ESS
artifact requires no code change.
"""

from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

import numpy as np

__all__ = [
    "BankPlan",
    "StrawPanel",
    "straw_positions",
    "ChopperPlan",
    "DevicePlan",
    "InstrumentNexusPlan",
    "LogPlan",
    "MonitorPlan",
    "write_nexus",
]


@dataclass(frozen=True)
class StrawPanel:
    """Where a flat panel of straw tubes stands, for a ``BankPlan`` of
    shape (layers, tubes, straws, pixels): see ``straw_positions``.

    ``along`` runs along a straw (the pixel axis), ``across`` from tube
    to tube within a layer, ``normal`` from layer to layer; unit vectors.
    """

    centre: tuple[float, float, float]  # m, sample at the origin, beam +z
    along: tuple[float, float, float]
    across: tuple[float, float, float]
    normal: tuple[float, float, float]
    pixel_pitch: float  # m between pixel centres along a straw
    tube_pitch: float  # m between tube axes within a layer
    layer_pitch: float  # m between layers
    straw_radius: float  # m from a tube's axis to its six outer straws


def straw_positions(shape: tuple[int, ...], panel: StrawPanel) -> np.ndarray:
    """[n, 3] pixel centres in m, C order over (layer, tube, straw, pixel):

        u = (pixel - (pixels - 1) / 2) * pixel_pitch
        v = (tube - (tubes - 1) / 2) * tube_pitch + (layer % 2) * tube_pitch / 2
            + straw_radius * cos(phi)
        w = (layer - (layers - 1) / 2) * layer_pitch + straw_radius * sin(phi)
        position = centre + u * along + v * across + w * normal

    Straw 0 lies on the tube's axis (its radius term is 0); straws 1 to
    ``straws - 1`` ring it at ``phi = 2 pi (straw - 1) / (straws - 1)``.
    Odd layers sit half a tube pitch over, as close-packed tubes do.
    """
    layers, tubes, straws, pixels = shape
    layer, tube, straw, pixel = np.meshgrid(
        *(np.arange(n, dtype=np.float64) for n in shape), indexing="ij"
    )
    phi = 2.0 * np.pi * (straw - 1.0) / (straws - 1.0)
    ring = np.where(straw > 0, panel.straw_radius, 0.0)
    u = (pixel - (pixels - 1) / 2.0) * panel.pixel_pitch
    v = (
        (tube - (tubes - 1) / 2.0) * panel.tube_pitch
        + (layer % 2) * (panel.tube_pitch / 2.0)
        + ring * np.cos(phi)
    )
    w = (layer - (layers - 1) / 2.0) * panel.layer_pitch + ring * np.sin(phi)
    return np.stack(
        [
            (
                panel.centre[axis]
                + u * panel.along[axis]
                + v * panel.across[axis]
                + w * panel.normal[axis]
            ).ravel()
            for axis in range(3)
        ],
        axis=1,
    )


@dataclass(frozen=True)
class BankPlan:
    """One detector bank.

    ``logical=False``: a rectangular (or cylinder-mantle) geometric bank —
    ``shape`` is (ny, nx) and pixel offsets are written; with ``panel``
    a straw-tube panel of shape (layers, tubes, straws, pixels).
    ``logical=True``: an N-d logical bank (DREAM/BIFROST style) — ``shape``
    may have any rank, only ``detector_number`` is written (named axes live
    in the instrument's view specs, not the file).
    """

    name: str  # NeXus group name, e.g. 'larmor_detector'
    source: str  # ev44 source name on the wire
    topic: str
    shape: tuple[int, ...]  # (ny, nx) pixels, or N-d for logical banks
    extent: tuple[float, float] = (1.0, 1.0)  # (height, width) metres
    z: float = 5.0  # sample->bank distance along beam, metres
    first_id: int = 1
    curvature_radius: float | None = None  # cylinder mantle around z axis
    logical: bool = False
    panel: StrawPanel | None = None  # a straw-tube panel: shape is 4-d


@dataclass(frozen=True)
class MonitorPlan:
    name: str
    source: str
    topic: str
    z: float = -2.0
    positioner_pv: str | None = None  # adds a motorised monitor_positioner
    positioner_topic: str | None = None


@dataclass(frozen=True)
class ChopperPlan:
    name: str
    pv: str  # PV base, e.g. 'LOKI:Chop:BWC1'
    topic: str
    speed_hz: float = 14.0


@dataclass(frozen=True)
class DevicePlan:
    """A motorised axis: one NXpositioner with RBV/VAL/DMOV NXlog children."""

    group: str  # slash path under /entry/instrument, e.g. 'sample_stage/x'
    pv: str  # EPICS motor record base; .RBV/.VAL/.DMOV appended
    topic: str
    units: str = "mm"
    with_idle: bool = True
    with_target: bool = True


@dataclass(frozen=True)
class LogPlan:
    """A plain f144 log stream (sample environment, vacuum, ...)."""

    group: str  # slash path under /entry, e.g. 'sample/temperature'
    source: str
    topic: str
    units: str = ""


@dataclass(frozen=True)
class InstrumentNexusPlan:
    name: str
    title: str = ""
    banks: tuple[BankPlan, ...] = ()
    monitors: tuple[MonitorPlan, ...] = ()
    choppers: tuple[ChopperPlan, ...] = ()
    devices: tuple[DevicePlan, ...] = ()
    logs: tuple[LogPlan, ...] = ()

    def f144_stream_count(self) -> int:
        """Number of f144 declarations the built file will contain."""
        n = len(self.logs) + 4 * len(self.choppers)
        for d in self.devices:
            n += 1 + int(d.with_target) + int(d.with_idle)
        for m in self.monitors:
            if m.positioner_pv is not None:
                n += 3
        return n


# -- HDF5 writing -----------------------------------------------------------


def _group(parent, name: str, nx_class: str):
    g = parent.require_group(name)
    g.attrs["NX_class"] = nx_class
    return g


def _stream_group(
    parent,
    name: str,
    *,
    nx_class: str,
    writer_module: str,
    topic: str,
    source: str,
    units: str | None = None,
):
    """A NeXus group declaring a Kafka stream (file-writer convention:
    ``topic``/``source``/``writer_module`` attributes on the group)."""
    g = _group(parent, name, nx_class)
    g.attrs["topic"] = topic
    g.attrs["source"] = source
    g.attrs["writer_module"] = writer_module
    if units:
        g.attrs["units"] = units
    if writer_module == "f144":
        # Empty value/time shells: real files have the streamed history;
        # geometry artifacts are truncated to length 0 (same convention as
        # scripts/make_geometry_nexus.py).
        g.create_dataset("time", shape=(0,), dtype="i8")
        v = g.create_dataset("value", shape=(0,), dtype="f8")
        if units:
            v.attrs["units"] = units
    return g


def _write_bank(instr, plan: BankPlan) -> None:
    det = _group(instr, plan.name, "NXdetector")
    n = int(np.prod(plan.shape))
    # Large layouts (NMX panels are 1280x1280) compress ~100x as aranges;
    # shuffle+gzip keeps multi-megapixel artifacts small on disk.
    opts = (
        {"compression": "gzip", "shuffle": True} if n > (1 << 18) else {}
    )
    det.create_dataset(
        "detector_number",
        data=np.arange(plan.first_id, plan.first_id + n, dtype=np.int32).reshape(
            plan.shape
        ),
        **opts,
    )
    if plan.logical:
        _stream_group(
            det,
            f"{plan.name}_events",
            nx_class="NXevent_data",
            writer_module="ev44",
            topic=plan.topic,
            source=plan.source,
        )
        return
    if plan.panel is not None:
        xyz = straw_positions(plan.shape, plan.panel)
        gx, gy, gz = (xyz[:, axis].reshape(plan.shape) for axis in range(3))
        _write_offsets(det, plan, (gx, gy, gz), opts)
        return
    ny, nx = plan.shape
    h, w = plan.extent
    ys = np.linspace(-h / 2, h / 2, ny)
    if plan.curvature_radius is None:
        xs = np.linspace(-w / 2, w / 2, nx)
        gx, gy = np.meshgrid(xs, ys)
        gz = np.full_like(gx, plan.z)
    else:
        # Mantle: pixels on a cylinder of given radius around the z axis.
        r = plan.curvature_radius
        phi = np.linspace(-w / (2 * r), w / (2 * r), nx)
        gphi, gy = np.meshgrid(phi, ys)
        gx = r * np.sin(gphi)
        gz = plan.z + r * (np.cos(gphi) - 1.0)
    _write_offsets(det, plan, (gx, gy, gz), opts)


def _write_offsets(det, plan: BankPlan, grids, opts: dict) -> None:
    for dsname, grid in zip(
        ("x_pixel_offset", "y_pixel_offset", "z_pixel_offset"), grids, strict=True
    ):
        d = det.create_dataset(dsname, data=grid.astype(np.float64), **opts)
        d.attrs["units"] = "m"
    _stream_group(
        det,
        f"{plan.name}_events",
        nx_class="NXevent_data",
        writer_module="ev44",
        topic=plan.topic,
        source=plan.source,
    )


def _write_positioner(
    parent, group_name: str, plan_pv: str, topic: str, units: str,
    with_target: bool = True, with_idle: bool = True,
) -> None:
    pos = _group(parent, group_name, "NXpositioner")
    _stream_group(
        pos,
        "value",
        nx_class="NXlog",
        writer_module="f144",
        topic=topic,
        source=f"{plan_pv}.RBV",
        units=units,
    )
    if with_target:
        _stream_group(
            pos,
            "target_value",
            nx_class="NXlog",
            writer_module="f144",
            topic=topic,
            source=f"{plan_pv}.VAL",
            units=units,
        )
    if with_idle:
        _stream_group(
            pos,
            "idle_flag",
            nx_class="NXlog",
            writer_module="f144",
            topic=topic,
            source=f"{plan_pv}.DMOV",
            units="dimensionless",
        )


def write_nexus(plan: InstrumentNexusPlan, path: str | Path) -> Path:
    """Build the instrument's NeXus geometry artifact at ``path``."""
    import h5py

    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    with h5py.File(path, "w") as f:
        entry = _group(f, "entry", "NXentry")
        entry.create_dataset("title", data=plan.title or plan.name)
        instr = _group(entry, "instrument", "NXinstrument")
        instr.create_dataset("name", data=plan.name.upper())

        for bank in plan.banks:
            _write_bank(instr, bank)

        for mon in plan.monitors:
            g = _group(instr, mon.name, "NXmonitor")
            _stream_group(
                g,
                f"{mon.name}_events",
                nx_class="NXevent_data",
                writer_module="ev44",
                topic=mon.topic,
                source=mon.source,
            )
            d = g.create_dataset("distance", data=np.float64(mon.z))
            d.attrs["units"] = "m"
            if mon.positioner_pv is not None:
                _write_positioner(
                    g,
                    "monitor_positioner",
                    mon.positioner_pv,
                    mon.positioner_topic or mon.topic,
                    "mm",
                )

        for ch in plan.choppers:
            g = _group(instr, ch.name, "NXdisk_chopper")
            d = g.create_dataset(
                "nominal_speed", data=np.float64(ch.speed_hz)
            )
            d.attrs["units"] = "Hz"
            # Source suffixes follow config/chopper.py's PV convention
            # (':SpdSet' setpoint, ':Delay' readback): instruments that
            # hand-declare chopper streams via chopper_pv_streams get
            # *identical* parsed entries, which the catalog merge refines
            # (adds nexus_path) instead of rejecting.
            for group_name, suffix, units in (
                ("rotation_speed_setpoint", "SpdSet", "Hz"),
                ("rotation_speed", "Spd", "Hz"),
                ("delay", "Delay", "ns"),
                ("phase", "Phs", "deg"),
            ):
                _stream_group(
                    g,
                    group_name,
                    nx_class="NXlog",
                    writer_module="f144",
                    topic=ch.topic,
                    source=f"{ch.pv}:{suffix}",
                    units=units,
                )

        for dev in plan.devices:
            *parents, leaf = dev.group.split("/")
            node = instr
            for p in parents:
                node = _group(node, p, "NXcollection")
            _write_positioner(
                node,
                leaf,
                dev.pv,
                dev.topic,
                dev.units,
                with_target=dev.with_target,
                with_idle=dev.with_idle,
            )

        for log in plan.logs:
            *parents, leaf = log.group.split("/")
            node = entry
            for p in parents:
                node = _group(node, p, "NXcollection")
            _stream_group(
                node,
                leaf,
                nx_class="NXlog",
                writer_module="f144",
                topic=log.topic,
                source=log.source,
                units=log.units,
            )
    return path

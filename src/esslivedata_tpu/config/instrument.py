"""Instrument composition root + registry.

Parity with reference ``config/instrument.py`` (Instrument:108,
InstrumentRegistry:86): the per-instrument declaration of detectors (with
detector_number layouts or 3-D positions), monitors, log/device streams and
workflow specs, plus lazy ``load_factories`` so light spec metadata is
importable everywhere while heavy factory construction (projection tables,
kernel instantiation) happens only inside services that run them.
"""

from __future__ import annotations

import importlib
import logging
import threading
from collections.abc import Callable
from dataclasses import dataclass, field
from typing import TYPE_CHECKING

import numpy as np

if TYPE_CHECKING:
    from .stream import ContextBinding, Device, Stream

__all__ = [
    "CameraConfig",
    "DetectorConfig",
    "Instrument",
    "InstrumentRegistry",
    "MonitorConfig",
    "instrument_registry",
]

logger = logging.getLogger(__name__)


class _DeferredGeometry:
    """``DetectorConfig.positions`` / ``.pixel_ids``: the value given,
    or what ``geometry_loader`` returns, read on the first access."""

    def __init__(self, name: str) -> None:
        self._slot = f"_{name}"

    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        if getattr(obj, self._slot) is None and obj.geometry_loader is not None:
            obj._positions, obj._pixel_ids = obj.geometry_loader()
        return getattr(obj, self._slot)

    def __set__(self, obj, value) -> None:
        setattr(obj, self._slot, value)


@dataclass
class DetectorConfig:
    """One detector bank and how to view it.

    ``geometry_loader`` defers a geometric bank's ``positions`` and
    ``pixel_ids`` to their first read (a job on the bank starting), so
    that declaring a large bank costs nothing at import."""

    name: str  # canonical stream name, e.g. 'bank0'
    source_name: str  # ECDC source name on the wire
    detector_number: np.ndarray | None = None  # logical [ny, nx] grid
    # geometric [n, 3] and the ids of its rows: out of repr and ==, which
    # would read a deferred geometry
    positions: np.ndarray | None = field(default=None, repr=False, compare=False)
    pixel_ids: np.ndarray | None = field(default=None, repr=False, compare=False)
    projection: str = "logical"  # 'logical' | 'xy_plane' | 'cylinder_mantle_z'
    resolution: tuple[int, int] = (128, 128)
    noise_sigma: float = 0.0
    n_replica: int = 1
    #: () -> (positions, pixel_ids), called once, on the first read of either.
    geometry_loader: Callable[[], tuple[np.ndarray, np.ndarray]] | None = field(
        default=None, repr=False, compare=False
    )

    def __post_init__(self) -> None:
        if (
            self.detector_number is None
            and self.geometry_loader is None
            and self.positions is None
        ):
            raise ValueError(f"Detector {self.name}: need a layout or positions")

    @property
    def geometry_loaded(self) -> bool:
        """Whether positions are in memory (given, or deferred and read)."""
        return self._positions is not None


DetectorConfig.positions = _DeferredGeometry("positions")
DetectorConfig.pixel_ids = _DeferredGeometry("pixel_ids")


@dataclass
class MonitorConfig:
    name: str
    source_name: str
    #: Per-pixel event-id grid for PIXELLATED monitors (reference
    #: instrument.py:401 configure_pixellated_monitor): monitors whose
    #: ev44 stream carries meaningful pixel ids keep them through the
    #: adapter (DetectorEvents payload) and can feed a 2-D monitor view.
    detector_number: np.ndarray | None = None

    @property
    def pixellated(self) -> bool:
        return self.detector_number is not None


@dataclass
class CameraConfig:
    """One area detector (ad00 camera) stream."""

    name: str
    source_name: str


@dataclass
class Instrument:
    name: str
    detectors: dict[str, DetectorConfig] = field(default_factory=dict)
    monitors: dict[str, MonitorConfig] = field(default_factory=dict)
    cameras: dict[str, CameraConfig] = field(default_factory=dict)
    log_sources: dict[str, str] = field(default_factory=dict)  # stream -> source
    streams: dict[str, "Stream"] = field(default_factory=dict)
    """Name-keyed stream catalog (f144 PVs, synthesised Device streams);
    reference instrument.py streams + ADR 0009 generated registries."""
    choppers: list[str] = field(default_factory=list)
    """Chopper names; declaring any auto-declares the synthetic
    delay_setpoint streams (config/chopper.py)."""
    chopper_delay_atol_ns: float = 1000.0
    context_bindings: list["ContextBinding"] = field(default_factory=list)
    merge_detectors: bool = False
    """Adapt every detector bank onto one logical 'detector' stream
    (BIFROST pattern, reference message_adapter.py:416)."""
    _factories_module: str | None = None
    _specs_module: str | None = None
    _loaded: bool = field(default=False, repr=False)

    def __post_init__(self) -> None:
        if self.choppers:
            self.declare_choppers(self.choppers)

    def declare_choppers(self, names: list[str]) -> None:
        """Post-construction chopper declaration (builder-style specs.py
        mutate the instrument after init, so ``__post_init__`` alone would
        silently skip the synthetic delay_setpoint streams)."""
        from .chopper import declare_chopper_setpoint_streams

        self.choppers = list(names)
        declare_chopper_setpoint_streams(self.streams, self.choppers)

    @property
    def devices(self) -> dict[str, "Device"]:
        """Synthesised Device entries of the stream catalog."""
        from .stream import Device

        return {
            name: s for name, s in self.streams.items() if isinstance(s, Device)
        }

    def add_context_binding(self, binding: "ContextBinding") -> None:
        """Instrument-scope context declaration (reference :244): the value
        of a stream routed as workflow context for dependent sources."""
        self.context_bindings.append(binding)

    def resolve_context_keys(self, source_name: str) -> dict[str, str]:
        """context_key -> stream_name for bindings that apply to a source.

        Two bindings resolving the same key to different streams for one
        source is a misconfiguration and raises rather than silently
        letting the later registration win."""
        out: dict[str, str] = {}
        for b in self.context_bindings:
            if b.dependent_sources and source_name not in b.dependent_sources:
                continue
            if b.workflow_key in out and out[b.workflow_key] != b.stream_name:
                raise ValueError(
                    f"Context key {b.workflow_key!r} for source "
                    f"{source_name!r} bound to both {out[b.workflow_key]!r} "
                    f"and {b.stream_name!r}"
                )
            out[b.workflow_key] = b.stream_name
        return out

    def add_detector(self, config: DetectorConfig) -> None:
        self.detectors[config.name] = config

    def add_monitor(self, config: MonitorConfig) -> None:
        self.monitors[config.name] = config

    def configure_pixellated_monitor(
        self, name: str, detector_number: np.ndarray
    ) -> None:
        """Mark a declared monitor as pixellated (reference
        instrument.py:401): its ev44 pixel ids are preserved through the
        adapter so a 2-D monitor view can consume them."""
        if name not in self.monitors:
            raise ValueError(
                f"Source {name!r} not in declared monitors "
                f"{sorted(self.monitors)}"
            )
        self.monitors[name].detector_number = np.asarray(detector_number)

    @property
    def pixellated_monitor_names(self) -> list[str]:
        return sorted(
            n for n, m in self.monitors.items() if m.pixellated
        )

    def add_camera(self, config: CameraConfig) -> None:
        self.cameras[config.name] = config

    def add_log(self, stream_name: str, source_name: str | None = None) -> None:
        self.log_sources[stream_name] = source_name or stream_name

    @property
    def detector_names(self) -> list[str]:
        return sorted(self.detectors)

    @property
    def monitor_names(self) -> list[str]:
        return sorted(self.monitors)

    def load_factories(self) -> None:
        """Import the heavy factory module, attaching workflow factories to
        the registry (reference instrument.py:654 lazy loading), then check
        registration-time invariants (reference instrument.py:759 validate)."""
        if self._loaded:
            return
        self._loaded = True
        if self._factories_module:
            importlib.import_module(self._factories_module)
            self.validate()

    # -- registration-time invariants (reference instrument.py:759-857) ----
    def _known_stream_names(self) -> set[str]:
        """Every stream name a service could subscribe to for this
        instrument: catalog streams (f144 PVs, synthesized devices +
        their substreams), log sources, chopper synthesis streams."""
        names: set[str] = set(self.streams) | set(self.log_sources)
        for device in self.devices.values():
            names.update(device.substream_names)
        if self.choppers:
            from .chopper import delay_readback_stream, speed_setpoint_stream

            for chopper in self.choppers:
                names.add(speed_setpoint_stream(chopper))
                names.add(delay_readback_stream(chopper))
        return names

    def validate(self) -> None:
        """Raise ValueError on misconfigurations that would otherwise fail
        silently at runtime (a gated job waiting forever on a typo'd
        stream, a binding scoped to sources nothing advertises, colliding
        NICOS device names). Runs at the end of ``load_factories``;
        exposed separately so synthetic instruments in tests can check
        without the package machinery."""
        from ..workflows.workflow_factory import workflow_registry

        specs = workflow_registry.specs_for_instrument(self.name)
        known_sources: set[str] = set()
        for spec in specs:
            known_sources.update(spec.source_names)
        known_streams = self._known_stream_names()

        for binding in self.context_bindings:
            unknown = set(binding.dependent_sources) - known_sources
            if unknown:
                raise ValueError(
                    f"{self.name}: ContextBinding for "
                    f"{binding.stream_name!r} lists dependent_sources "
                    f"{sorted(unknown)} that no registered spec advertises"
                )
            if binding.stream_name not in known_streams:
                raise ValueError(
                    f"{self.name}: ContextBinding targets undeclared "
                    f"stream {binding.stream_name!r} — a job gated on it "
                    f"would wait forever"
                )
        # Same context key bound to different streams for one source.
        for source in sorted(known_sources):
            self.resolve_context_keys(source)
        # Colliding NICOS device names across specs raise here instead of
        # at service assembly.
        if specs:
            from .device_contract import DeviceContract

            DeviceContract.from_specs(specs)


class InstrumentRegistry:
    def __init__(self) -> None:
        self._instruments: dict[str, Instrument] = {}
        self._lock = threading.Lock()

    def register(self, instrument: Instrument) -> Instrument:
        with self._lock:
            if instrument.name in self._instruments:
                raise ValueError(f"Instrument {instrument.name} already registered")
            self._instruments[instrument.name] = instrument
        return instrument

    def __getitem__(self, name: str) -> Instrument:
        self._ensure_builtin(name)
        return self._instruments[name]

    def __contains__(self, name: str) -> bool:
        self._ensure_builtin(name)
        return name in self._instruments

    def names(self) -> list[str]:
        """All registered + built-in instrument names (built-ins are
        discovered from the instruments package without importing them)."""
        import pkgutil

        from . import instruments as _pkg

        builtin = {
            m.name for m in pkgutil.iter_modules(_pkg.__path__) if m.ispkg
        }
        return sorted(set(self._instruments) | builtin)

    def _ensure_builtin(self, name: str) -> None:
        """Import built-in instrument packages on first access."""
        if name in self._instruments:
            return
        try:
            importlib.import_module(f"esslivedata_tpu.config.instruments.{name}")
        except ModuleNotFoundError:
            pass


instrument_registry = InstrumentRegistry()
"""Process-wide registry (reference: instrument.py:86)."""

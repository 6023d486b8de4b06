"""Shared pydantic parameter models (reference: parameter_models.py).

The UI schema vocabulary workflow params are built from: unit-tagged
ranges, bin-edge specs with linear/log scales, unit enums, and the
free-text numeric-list parser backing list inputs. ``get_*`` accessors
return plain floats in the declared unit (the reference returns scipp
scalars; our labeled-array layer keeps units on outputs, params stay
plain numbers converted by the consuming workflow).
"""

from __future__ import annotations

import json
from enum import StrEnum

import numpy as np
from pydantic import BaseModel, Field, field_validator, model_validator

__all__ = [
    "Angle",
    "AngleUnit",
    "DspacingUnit",
    "EdgesModel",
    "LengthUnit",
    "QUnit",
    "RangeModel",
    "Scale",
    "TimeUnit",
    "WavelengthUnit",
    "parse_number_list",
]


def parse_number_list(value: str) -> list[float]:
    """Parse a comma-separated numeric string; blank -> []; raises on
    non-numbers so it can back a pydantic field_validator for free-text
    list inputs (widgets have no native list input)."""
    value = value.strip()
    if not value:
        return []
    try:
        parsed = json.loads(f"[{value}]")
    except json.JSONDecodeError as err:
        raise ValueError(f"Invalid number list: {err}") from err
    if any(
        isinstance(x, bool) or not isinstance(x, (int, float)) for x in parsed
    ):
        raise ValueError("All entries must be numbers")
    return [float(x) for x in parsed]


class Scale(StrEnum):
    LINEAR = "linear"
    LOG = "log"


class TimeUnit(StrEnum):
    NS = "ns"
    US = "us"
    MS = "ms"
    S = "s"


class WavelengthUnit(StrEnum):
    ANGSTROM = "angstrom"
    NANOMETER = "nm"


class DspacingUnit(StrEnum):
    ANGSTROM = "angstrom"
    NANOMETER = "nm"


class LengthUnit(StrEnum):
    METER = "m"
    CENTIMETER = "cm"
    MILLIMETER = "mm"


class AngleUnit(StrEnum):
    DEGREE = "deg"
    RADIAN = "rad"


class QUnit(StrEnum):
    INVERSE_ANGSTROM = "1/angstrom"
    INVERSE_NANOMETER = "1/nm"


class RangeModel(BaseModel):
    """A (start, stop) range; subclasses add a ``unit`` field."""

    start: float = Field(default=0.0, description="Start of the range.")
    stop: float = Field(default=10.0, description="Stop of the range.")

    @field_validator("stop")
    @classmethod
    def _stop_after_start(cls, v, info):
        start = info.data.get("start")
        if start is not None and v <= start:
            raise ValueError("stop must be greater than start")
        return v


class EdgesModel(BaseModel):
    """Bin edges: range + count + scale; ``get_edges`` materializes them."""

    start: float = Field(default=1.0, description="Start of the edges.")
    stop: float = Field(default=10.0, description="Stop of the edges.")
    num_bins: int = Field(default=100, ge=1, le=10000)
    scale: Scale = Field(default=Scale.LINEAR)

    @field_validator("stop")
    @classmethod
    def _stop_after_start(cls, v, info):
        start = info.data.get("start")
        if start is not None and v <= start:
            raise ValueError("stop must be greater than start")
        return v

    @model_validator(mode="after")
    def _log_needs_positive_start(self):
        if self.scale == Scale.LOG and self.start <= 0:
            raise ValueError("start must be positive when scale is 'log'")
        return self

    def get_edges(self) -> np.ndarray:
        if self.scale == Scale.LOG:
            return np.geomspace(self.start, self.stop, self.num_bins + 1)
        return np.linspace(self.start, self.stop, self.num_bins + 1)


class Angle(BaseModel):
    value: float = Field(default=0.0)
    unit: AngleUnit = Field(default=AngleUnit.DEGREE)

    def get_degrees(self) -> float:
        if self.unit == AngleUnit.RADIAN:
            return float(np.rad2deg(self.value))
        return float(self.value)

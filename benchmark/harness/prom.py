"""Prometheus text exposition, as much of it as the benchmark reads."""

from __future__ import annotations

import re

_SAMPLE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> list[tuple[str, dict[str, str], float]]:
    """[(sample name, labels, value)] of every sample line."""
    samples = []
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        match = _SAMPLE.match(line)
        if match is None:
            raise ValueError(f"bad exposition line: {line!r}")
        name, labels, value = match.groups()
        samples.append((name, dict(_LABEL.findall(labels or "")), float(value)))
    return samples


def value(samples, family: str, part: str = "value", **labels) -> float | None:
    """Sum of the family's samples that carry ``labels``. ``part`` is
    ``value`` (the family's own name, with or without ``_total``),
    ``sum`` or ``count`` (a histogram's). None where nothing matches."""
    names = {
        "value": (family, family + "_total"),
        "sum": (family + "_sum",),
        "count": (family + "_count",),
    }[part]
    hits = [
        v
        for name, sample_labels, v in samples
        if name in names and all(sample_labels.get(k) == w for k, w in labels.items())
    ]
    return sum(hits) if hits else None

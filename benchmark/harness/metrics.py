"""Per-layer metric readers: one data file each under
``benchmark/metrics/``, reduced by one of three small vocabularies.

``prometheus``: ``scale * sum(delta of terms) / per`` between the scrape
at the window's start and the one at its end. A term is ``{family, part,
labels}`` with part ``value``, ``sum`` or ``count``; ``per`` is a term,
or ``"window_s"``, or absent (1). ``"absent_is_zero"`` reads a family
that has no sample yet as 0 (a counter that was never bumped).
``generator``: a statistic of the generator's own pulse log.
``trace``: a named reduction of ``trace_reduce`` over the device trace.

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""

from __future__ import annotations

import numpy as np

from . import prom


def _delta(term: dict, before, after) -> float | None:
    labels = term.get("labels", {})
    part = term.get("part", "value")
    end = prom.value(after, term["family"], part, **labels)
    if end is None:
        return None
    start = prom.value(before, term["family"], part, **labels)
    return end - (start or 0.0)


def read_prometheus(reader: dict, ctx: dict) -> float | None:
    before, after = ctx["scrape_start"], ctx["scrape_end"]
    deltas = [_delta(term, before, after) for term in reader["terms"]]
    if any(d is None for d in deltas):
        if reader.get("absent_is_zero"):
            deltas = [d or 0.0 for d in deltas]
        else:
            return None
    per = reader.get("per")
    if per is None:
        divisor = 1.0
    elif per == "window_s":
        divisor = ctx["window_s"]
    else:
        divisor = _delta(per, before, after)
    if not divisor:
        return None
    return reader.get("scale", 1.0) * sum(deltas) / divisor


def read_generator(reader: dict, ctx: dict) -> float | None:
    log = ctx["pulse_log"]  # rows (pulse, due_ns, sent_ns) of the window
    if not len(log):
        return None
    late_ms = (log[:, 2] - log[:, 1]) / 1e6
    stat = reader["stat"]
    if stat == "late_p95_ms":
        return float(np.percentile(late_ms, 95))
    raise ValueError(f"generator stat {stat!r}")


def read_trace(reader: dict, ctx: dict) -> float | None:
    reduced = ctx.get("trace")
    if not reduced:
        return None
    return reduced.get(reader["reduce"])


READERS = {
    "prometheus": read_prometheus,
    "generator": read_generator,
    "trace": read_trace,
}


def evaluate(specs: list[dict], ctx: dict) -> dict:
    """name -> {"value", "unit"} for every metric whose reader found something."""
    out = {}
    for spec in specs:
        kind = spec["reader"]["kind"]
        if kind not in READERS:
            raise ValueError(f"metric {spec['name']}: reader kind {kind!r}")
        got = READERS[kind](spec["reader"], ctx)
        if got is not None:
            out[spec["name"]] = {"value": float(got), "unit": spec["unit"]}
    return out

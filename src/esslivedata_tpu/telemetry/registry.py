"""Process-wide metrics registry: counters, gauges, histograms, collectors.

The serving stack grew one ad-hoc counter surface per layer —
``ops/publish.METRICS``, the ingest pipeline's
``StageTimer``, kafka stream/sink/breaker counts — each with its own
snapshot method and no export surface beyond a 30 s log line. This
module is the one registry they all meet in (ADR 0116): a scrape of
``/metrics`` (``telemetry/http.py``) renders every instrument in
Prometheus text exposition format, and ``bench.py`` embeds the same
snapshot in its JSON metric lines so BENCH trajectories carry the
dispatch/compile decomposition alongside throughput.

Two registration styles, chosen by hot-path cost:

- **Direct instruments** (:class:`Counter`, :class:`Gauge`,
  :class:`Histogram`): for NEW first-class signals recorded at the
  event (jit compile events, tick span
  durations). Increments take one uncontended lock (tens of ns against
  a >=71 ms window) and never allocate on the steady-state path — the
  per-labelset child is resolved once and cached by the caller
  (:meth:`Counter.labels`).

- **Collectors**: for EXISTING thread-safe snapshot surfaces
  (``PublishMetrics.snapshot``,
  ``IngestPipeline`` depths, kafka counters, HBM stats). A collector
  is a zero-hot-path-cost pull: the producer keeps its own lock and
  counters, and the registry polls it only at scrape time. Collectors
  are registered under a caller-chosen key so a restarted service (or
  the next test) REPLACES its predecessor instead of accumulating dead
  callbacks, and a collector that raises is dropped from that scrape
  (logged once at debug), never failing the whole exposition.

Instrument names follow the Prometheus conventions used throughout
``docs/observability.md``: ``livedata_`` prefix, ``_total`` suffix on
counters, base units (seconds, bytes) in the name.
"""

from __future__ import annotations

import bisect
import logging
import math
import threading
from collections.abc import Callable, Iterable, Sequence
from dataclasses import dataclass, field

__all__ = [
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricFamily",
    "MetricsRegistry",
    "Sample",
]

logger = logging.getLogger(__name__)

#: Default latency buckets (seconds): spans the 10 us instrument-op
#: floor through the multi-second compile stalls the compile-event
#: instrument exists to expose. FIXED at construction — a histogram's
#: bucket layout is part of its wire contract (scrapers subtract
#: successive scrapes per bucket), so it must never depend on the data.
DEFAULT_BUCKETS = (
    0.0001,
    0.0005,
    0.001,
    0.0025,
    0.005,
    0.01,
    0.025,
    0.05,
    0.1,
    0.25,
    0.5,
    1.0,
    2.5,
    5.0,
    10.0,
)


@dataclass(frozen=True, slots=True)
class Sample:
    """One exposition line: suffix ('' for the base name), labels, value."""

    suffix: str
    labels: tuple[tuple[str, str], ...]
    value: float


@dataclass(slots=True)
class MetricFamily:
    """One named metric with its samples — the unit of exposition."""

    name: str
    kind: str  # counter | gauge | histogram
    help: str
    samples: list[Sample] = field(default_factory=list)


def _label_key(
    labelnames: tuple[str, ...], labels: dict[str, str]
) -> tuple[str, ...]:
    if set(labels) != set(labelnames):
        raise ValueError(
            f"labels {sorted(labels)} != declared {sorted(labelnames)}"
        )
    return tuple(str(labels[name]) for name in labelnames)


class _Instrument:
    """Shared labelset bookkeeping; subclasses add the value semantics."""

    kind = "untyped"

    def __init__(
        self, name: str, help: str, labelnames: Sequence[str] = ()
    ) -> None:
        self.name = name
        self.help = help
        self._labelnames = tuple(labelnames)
        self._lock = threading.Lock()

    def _label_items(
        self, key: tuple[str, ...]
    ) -> tuple[tuple[str, str], ...]:
        return tuple(zip(self._labelnames, key, strict=True))


class Counter(_Instrument):
    """Monotonic labeled counter.

    ``labels(**kv)`` returns a bound child whose :meth:`_Child.inc` is
    the hot-path entry — resolve it once per steady-state site, not per
    event. ``inc`` on the parent is the convenience form for low-rate
    sites.
    """

    kind = "counter"

    class _Child:
        __slots__ = ("_counter", "_key")

        def __init__(self, counter: Counter, key: tuple[str, ...]) -> None:
            self._counter = counter
            self._key = key

        def inc(self, amount: float = 1.0) -> None:
            if amount < 0:
                raise ValueError("counters only go up")
            counter = self._counter
            with counter._lock:
                counter._values[self._key] = (
                    counter._values.get(self._key, 0.0) + amount
                )

    def __init__(
        self, name: str, help: str, labelnames: Sequence[str] = ()
    ) -> None:
        super().__init__(name, help, labelnames)
        self._values: dict[tuple[str, ...], float] = {}
        self._children: dict[tuple[str, ...], Counter._Child] = {}

    def labels(self, **labels: str) -> Counter._Child:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = Counter._Child(self, key)
            return child

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        if amount < 0:
            raise ValueError("counters only go up")
        key = _label_key(self._labelnames, labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels: str) -> float:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            return self._values.get(key, 0.0)

    def total(self) -> float:
        """Sum over every labelset (bench/test convenience)."""
        with self._lock:
            return sum(self._values.values())

    def items(self) -> list[tuple[dict[str, str], float]]:
        """Snapshot of every (labels, value) pair — the public
        per-labelset read (CompileEventRecorder.total's site filter)."""
        with self._lock:
            return [
                (dict(self._label_items(key)), value)
                for key, value in sorted(self._values.items())
            ]

    def collect(self) -> MetricFamily:
        with self._lock:
            items = sorted(self._values.items())
        family = MetricFamily(self.name, self.kind, self.help)
        # Counters expose a `_total`-suffixed sample; a name that
        # already carries the suffix keeps it verbatim (a naive append
        # would publish `..._total_total`, a series no documented query
        # would ever match).
        suffix = "" if self.name.endswith("_total") else "_total"
        family.samples = [
            Sample(suffix, self._label_items(key), value)
            for key, value in items
        ]
        return family


class Gauge(_Instrument):
    """Labeled gauge (set / inc / dec)."""

    kind = "gauge"

    def __init__(
        self, name: str, help: str, labelnames: Sequence[str] = ()
    ) -> None:
        super().__init__(name, help, labelnames)
        self._values: dict[tuple[str, ...], float] = {}

    def set(self, value: float, **labels: str) -> None:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            self._values[key] = float(value)

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels: str) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels: str) -> float:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            return self._values.get(key, 0.0)

    def collect(self) -> MetricFamily:
        with self._lock:
            items = sorted(self._values.items())
        family = MetricFamily(self.name, self.kind, self.help)
        family.samples = [
            Sample("", self._label_items(key), value)
            for key, value in items
        ]
        return family


class Histogram(_Instrument):
    """Fixed-bucket cumulative histogram (Prometheus semantics).

    Buckets are latched at construction (see :data:`DEFAULT_BUCKETS`);
    ``observe`` costs one lock + one bisect — no allocation once a
    labelset's row exists. ``labels(**kv)`` returns a bound child for
    steady-state sites, mirroring :class:`Counter`.
    """

    kind = "histogram"

    class _Child:
        __slots__ = ("_hist", "_key")

        def __init__(self, hist: Histogram, key: tuple[str, ...]) -> None:
            self._hist = hist
            self._key = key

        def observe(self, value: float) -> None:
            self._hist._observe(self._key, value)

    def __init__(
        self,
        name: str,
        help: str,
        labelnames: Sequence[str] = (),
        buckets: Sequence[float] = DEFAULT_BUCKETS,
    ) -> None:
        super().__init__(name, help, labelnames)
        bounds = tuple(float(b) for b in buckets)
        if list(bounds) != sorted(set(bounds)):
            raise ValueError("buckets must be sorted and distinct")
        if any(not math.isfinite(b) for b in bounds):
            raise ValueError("buckets must be finite (+Inf is implicit)")
        self._bounds = bounds
        # key -> (per-bucket counts [len(bounds)+1, last = +Inf], sum)
        self._rows: dict[tuple[str, ...], tuple[list[int], float]] = {}
        self._children: dict[tuple[str, ...], Histogram._Child] = {}

    @property
    def buckets(self) -> tuple[float, ...]:
        return self._bounds

    def labels(self, **labels: str) -> Histogram._Child:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = Histogram._Child(self, key)
            return child

    def observe(self, value: float, **labels: str) -> None:
        self._observe(_label_key(self._labelnames, labels), value)

    def _observe(self, key: tuple[str, ...], value: float) -> None:
        idx = bisect.bisect_left(self._bounds, value)
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                row = self._rows[key] = ([0] * (len(self._bounds) + 1), 0.0)
            counts, total = row
            counts[idx] += 1
            self._rows[key] = (counts, total + value)

    def count(self, **labels: str) -> int:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            row = self._rows.get(key)
            return 0 if row is None else sum(row[0])

    def sum(self, **labels: str) -> float:
        key = _label_key(self._labelnames, labels)
        with self._lock:
            row = self._rows.get(key)
            return 0.0 if row is None else row[1]

    def total_count(self) -> int:
        with self._lock:
            return sum(sum(counts) for counts, _ in self._rows.values())

    def collect(self) -> MetricFamily:
        with self._lock:
            rows = [
                (key, list(counts), total)
                for key, (counts, total) in sorted(self._rows.items())
            ]
        family = MetricFamily(self.name, self.kind, self.help)
        for key, counts, total in rows:
            base = self._label_items(key)
            cumulative = 0
            for bound, count in zip(self._bounds, counts[:-1], strict=True):
                cumulative += count
                family.samples.append(
                    Sample(
                        "_bucket",
                        base + (("le", _format_le(bound)),),
                        cumulative,
                    )
                )
            cumulative += counts[-1]
            family.samples.append(
                Sample("_bucket", base + (("le", "+Inf"),), cumulative)
            )
            family.samples.append(Sample("_sum", base, total))
            family.samples.append(Sample("_count", base, cumulative))
        return family


def _format_le(bound: float) -> str:
    """Canonical ``le`` rendering: integral bounds without the trailing
    .0 Python's repr would add ('1' not '1.0'), everything else repr."""
    if bound == int(bound):
        return str(int(bound))
    return repr(bound)


class MetricsRegistry:
    """Names -> instruments + keyed collectors; the scrape entry point.

    ``counter``/``gauge``/``histogram`` are get-or-create and
    type-checked: the process-wide default registry is touched from
    module scope in several layers, so two callers naming the same
    instrument must receive the same object (or a loud TypeError on a
    kind/labels mismatch — silently forking a name would split its
    series across scrapes).
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._instruments: dict[str, _Instrument] = {}
        self._collectors: dict[str, Callable[[], Iterable[MetricFamily]]] = {}

    # -- direct instruments ------------------------------------------------
    def _get_or_create(self, cls, name, help, labelnames, **kwargs):
        with self._lock:
            existing = self._instruments.get(name)
            if existing is not None:
                if not isinstance(existing, cls) or existing._labelnames != tuple(
                    labelnames
                ):
                    raise TypeError(
                        f"metric {name!r} already registered as "
                        f"{type(existing).__name__}{existing._labelnames}"
                    )
                # Bucket layout is part of the wire contract too: a
                # second registration asking for different buckets must
                # fail loudly, not silently observe into the first
                # caller's layout.
                buckets = kwargs.get("buckets")
                if buckets is not None and existing.buckets != tuple(
                    float(b) for b in buckets
                ):
                    raise TypeError(
                        f"histogram {name!r} already registered with "
                        f"buckets {existing.buckets}"
                    )
                return existing
            instrument = cls(name, help, labelnames, **kwargs)
            self._instruments[name] = instrument
            return instrument

    def counter(
        self, name: str, help: str, labelnames: Sequence[str] = ()
    ) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(
        self, name: str, help: str, labelnames: Sequence[str] = ()
    ) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(
        self,
        name: str,
        help: str,
        labelnames: Sequence[str] = (),
        buckets: Sequence[float] = DEFAULT_BUCKETS,
    ) -> Histogram:
        return self._get_or_create(
            Histogram, name, help, labelnames, buckets=buckets
        )

    def get(self, name: str) -> _Instrument | None:
        with self._lock:
            return self._instruments.get(name)

    # -- collectors --------------------------------------------------------
    def register_collector(
        self, key: str, collector: Callable[[], Iterable[MetricFamily]]
    ) -> None:
        """(Re)register a pull-time callback under ``key``. Keyed so a
        restarted producer replaces its predecessor — the registry is
        process-wide and producers (services, tests) come and go."""
        with self._lock:
            self._collectors[key] = collector

    def unregister_collector(
        self,
        key: str,
        collector: Callable[[], Iterable[MetricFamily]] | None = None,
    ) -> None:
        """Remove ``key``'s collector. Pass the callback to make the
        removal owner-guarded: a producer whose registration was
        already REPLACED by a successor (same key, new instance) must
        not delete the successor's live collector on its own late
        shutdown. Equality, not identity — bound methods are fresh
        objects per access but compare equal for the same
        (function, instance) pair."""
        with self._lock:
            if (
                collector is not None
                and self._collectors.get(key) != collector
            ):
                return
            self._collectors.pop(key, None)

    # -- scrape ------------------------------------------------------------
    def collect(self) -> list[MetricFamily]:
        """Every family: direct instruments first (stable name order),
        then collector output in registration order. A failing collector
        loses only its own families for this scrape."""
        with self._lock:
            instruments = [
                self._instruments[name] for name in sorted(self._instruments)
            ]
            collectors = list(self._collectors.items())
        families = [instrument.collect() for instrument in instruments]
        for key, collector in collectors:
            try:
                families.extend(collector())
            except Exception:
                logger.debug("collector %r failed", key, exc_info=True)
        return families

    def snapshot(self, *, compact: bool = False) -> dict[str, dict[str, float]]:
        """Flat {name: {label-rendered-sample: value}} — what bench.py
        embeds in its JSON metric lines (``telemetry`` field).
        ``compact`` drops per-bucket histogram samples (keeping _sum /
        _count) so a metric line carries the decomposition without a
        wall of bucket rows."""
        out: dict[str, dict[str, float]] = {}
        for family in self.collect():
            bucket = out.setdefault(family.name, {})
            for sample in family.samples:
                if compact and sample.suffix == "_bucket":
                    continue
                label = sample.suffix
                if sample.labels:
                    label += (
                        "{"
                        + ",".join(f"{k}={v}" for k, v in sample.labels)
                        + "}"
                    )
                bucket[label] = sample.value
        return out


#: The process-wide registry every service/bench scrape reads.
REGISTRY = MetricsRegistry()

"""Unified telemetry for the tick-program serving stack (ADR 0116).

One process-wide :data:`~.registry.REGISTRY` (counters / gauges /
fixed-bucket histograms + pull-time collectors), a Prometheus
text-exposition HTTP plane (``/metrics`` + ``/healthz``,
``--metrics-port`` on every service runner), a per-tick tracer with
Chrome ``trace_event`` export (``--trace-dump``) and a slow-tick
watchdog, and the compile-event instrument that turns jit-cache misses
into a labeled histogram.

See ``docs/observability.md`` for the metric name catalog, the
trace-id lifecycle and how to wire a new workflow metric.
"""

from .compile import COMPILE_EVENTS, CompileEventRecorder
from .e2e import E2E_LATENCY, E2E_STAGES, observe_stage
from .health import HEALTH, STATE_LOST, HealthState
from .exposition import (
    CONTENT_TYPE,
    ParsedMetric,
    parse_prometheus_text,
    render_text,
)
from .http import MetricsServer, start_metrics_server
from .registry import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricFamily,
    MetricsRegistry,
    Sample,
)
from .trace import TRACER, Span, TickTracer

__all__ = [
    "COMPILE_EVENTS",
    "CONTENT_TYPE",
    "E2E_LATENCY",
    "E2E_STAGES",
    "HEALTH",
    "REGISTRY",
    "STATE_LOST",
    "TRACER",
    "CompileEventRecorder",
    "Counter",
    "Gauge",
    "HealthState",
    "Histogram",
    "MetricFamily",
    "MetricsRegistry",
    "MetricsServer",
    "ParsedMetric",
    "Sample",
    "Span",
    "TickTracer",
    "observe_stage",
    "parse_prometheus_text",
    "render_text",
    "start_metrics_server",
]

"""End-to-end latency propagation: source timestamp -> per-stage histogram.

The flight recorder (ADR 0116) traces ticks *within* the process; this
module measures the quantity the product actually promises — how stale
a subscriber's frame is against the **source timestamp** of the data it
renders (ADR 0120). The source timestamp is the data clock already on
every message (ev44 ``reference_time[-1]``, f144/da00 payload time —
kafka/message_adapter.py), "born at consume": it rides
``MessageBatch.end`` into ``PipelineWindow.source_ts_ns`` and
``JobResult.source_ts_ns``, through the tick program, into the serving
plane's da00 frame (the frame's own ``timestamp`` field — which is why
the correlation test can assert byte-exact survival) and out on the SSE
wire.

Each boundary folds ``wall_now - source_ts`` into ONE histogram family,
``livedata_e2e_latency_seconds{stage}``:

======================  ====================================================
stage                   observed at
======================  ====================================================
``consume``             adapter decode on the consume path (per message,
                        kafka/message_adapter.py — producer+transport lag)
``decode``              window decoded (pipeline decode worker / serial
                        preprocess). Batch-granular (ADR 0125): ONE
                        observation per window, anchored at the OLDEST
                        member's source timestamp — the upper bound on
                        any single message's decode staleness — so the
                        sample count tracks windows, not messages, and
                        per-message fidelity is preserved conservatively
``staged``              window prestaged onto the device (pipelined only —
                        the serial loop stages at step time)
``published``           results finalized + sink publish done
``fanout_encoded``      serving plane encoded the da00 frame + delta blob
``relay_ingress``       a relay received/decoded a frame from its
                        upstream hop (fleet/relay.py, ADR 0121; absent
                        without a relay in the path)
``relay_published``     the relay re-encoded the frame into its own hub
``subscriber_delivered``  a subscriber dequeued the blob
                        (serving/broadcast.py ``Subscription.next_blob``)
======================  ====================================================

Successive stages nest, so the scrape decomposes the p99: the
``subscriber_delivered`` histogram is the headline SLO
(``scripts/slo_gate.py`` gates its p99 against the rule-file budget)
and stage-to-stage differences name the phase that ate the budget.

Cost: one ``time.time_ns`` + one histogram observe per boundary per
window (per blob for delivery) — nanoseconds against the >= 71 ms
window. Always on: unlike span tracing there is no ring to fill, and
the wire is untouched (pinned by the telemetry on-vs-off byte-parity
test), so there is nothing to gain from a kill switch.

Clock caveat: latency is wall clock minus data clock, so it contains
producer lag and clock skew by design — the reference survey's
"freshness" IS that sum (a dashboard user cares how old the rendered
data is, not which hop aged it). Synthetic timestamps (tests, benches
driving ``Timestamp.from_ns(small)``) land in the +Inf bucket; the SLO
harness (harness/load.py) stamps real wall-clock source times and the
gate evaluates scrape DELTAS, so neighbors in the same process cannot
pollute a gated run.
"""

from __future__ import annotations

import time

from .registry import REGISTRY

__all__ = ["E2E_BUCKETS", "E2E_LATENCY", "E2E_STAGES", "observe_stage"]

#: Pipeline stages in boundary order (see module docstring table).
#: The two relay stages (ADR 0121) only record when a relay hop is in
#: the path: ``relay_ingress`` when a relay dequeues/receives a frame
#: from its upstream, ``relay_published`` when it has re-encoded and
#: fanned the frame into its own hub — so the freshness histogram
#: spans the whole relay tree and the hop's cost is the difference
#: between ``fanout_encoded`` and ``relay_published``.
E2E_STAGES = (
    "consume",
    "decode",
    "staged",
    "published",
    "fanout_encoded",
    "relay_ingress",
    "relay_published",
    "subscriber_delivered",
)

#: Freshness buckets: resolve the <100 ms SLO region finely (the
#: ROADMAP headline), keep coverage out to the multi-second stalls a
#: congested publish path or a wedged consumer produces.
E2E_BUCKETS = (
    0.001,
    0.0025,
    0.005,
    0.01,
    0.025,
    0.05,
    0.075,
    0.1,
    0.25,
    0.5,
    1.0,
    2.5,
    5.0,
    10.0,
    30.0,
)

E2E_LATENCY = REGISTRY.histogram(
    "livedata_e2e_latency_seconds",
    "End-to-end freshness (wall clock minus source data timestamp) at "
    "each serving-path boundary: consume -> decode -> staged -> "
    "published -> fanout_encoded -> subscriber_delivered (ADR 0120)",
    labelnames=("stage",),
    buckets=E2E_BUCKETS,
)

#: Bound children resolved once — the hot-path entry per stage.
_CHILDREN = {stage: E2E_LATENCY.labels(stage=stage) for stage in E2E_STAGES}


def observe_stage(
    stage: str, source_ts_ns: int | None, *, now_ns: int | None = None
) -> None:
    """Fold one boundary crossing in. ``source_ts_ns`` None (a window
    with no data time — empty finishing-job flushes) records nothing:
    an invented latency is worse than a missing sample. Negative
    deltas (future-timestamped data, clock skew) clamp to 0 — the
    stream-lag report already surfaces future timestamps as errors;
    this histogram answers 'how stale', and 'not at all' is 0."""
    if source_ts_ns is None:
        return
    if now_ns is None:
        now_ns = time.time_ns()
    delta_s = (now_ns - int(source_ts_ns)) / 1e9
    _CHILDREN[stage].observe(delta_s if delta_s > 0.0 else 0.0)

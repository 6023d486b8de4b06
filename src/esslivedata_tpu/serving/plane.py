"""ServingPlane: the processor-facing entry to the fan-out tier.

The OrchestratingProcessor's publish path calls
:meth:`ServingPlane.publish_results` (duck-typed ``result_fanout``
hook) with the same finalized :class:`~..core.job.JobResult` list it
hands the Kafka sink. Each output is encoded to the EXACT da00 wire
the sink serializer produces — same ``ResultKey`` source name, same
timestamp — so a subscriber's reconstructed frame is byte-identical to
what a Kafka consumer of that publish would read (the acceptance
contract, pinned in tests/serving/fanout_integration_test.py).

Epoch token per (job, output): the output's structural layout (variable
names, shapes, dtypes, axes — a projection/layout swap changes it) plus
the job's ``state_epoch`` (core/job.py — bumped on clear/reset and on a
``state_lost`` donation failure). Either changing forces the delta
codec onto a keyframe with a bumped epoch, so no delta ever splices
across state generations.

Containment: one output failing to encode loses only that output's
frame for that tick (logged), mirroring the sink's per-message
serialization containment — the fan-out hook must never take the step
worker down.

``get_or_create_plane`` keys planes by requested port so a process
that builds services repeatedly (tests driving ``main()``) reuses its
listener instead of failing the second bind — the core/service.py
``_metrics_servers`` rule.
"""

from __future__ import annotations

import logging
import threading

import numpy as np

from ..kafka.da00_compat import dataarray_to_da00
from ..kafka.wire import encode_da00
from ..telemetry.e2e import observe_stage
from .broadcast import BroadcastServer, stream_key
from .result_cache import ResultCache

__all__ = ["ServingPlane", "get_or_create_plane"]

logger = logging.getLogger(__name__)

#: getattr sentinel: "the result type has no source_ts_ns at all"
#: (bespoke/test doubles) — distinct from a real None (no data time).
_NO_SOURCE_TS = object()


class ServingPlane:
    """ResultCache + BroadcastServer behind the processor hook."""

    def __init__(
        self,
        *,
        port: int | None = None,
        host: str = "0.0.0.0",
        ring: int = 8,
        queue_limit: int = 32,
        name: str = "serving",
        heartbeat_s: float = 10.0,
        hop: int = 0,
    ) -> None:
        self.cache = ResultCache(ring=ring)
        self.server = BroadcastServer(
            cache=self.cache,
            port=port,
            host=host,
            queue_limit=queue_limit,
            name=name,
            heartbeat_s=heartbeat_s,
            hop=hop,
        )
        #: True after close(): the reuse table must not hand a plane
        #: with a dead listener to a later service build.
        self.closed = False

    @property
    def port(self) -> int | None:
        return self.server.port

    # -- processor hook ----------------------------------------------------
    def publish_results(self, results, timestamp) -> None:
        """Fan one publish tick's finalized results out. Runs on the
        service/step worker right after the sink publish; everything
        here is bounded host work (one da00 encode + one delta encode
        per output, one bounded enqueue per subscriber)."""
        ts = timestamp.ns
        window_source_ts: int | None = None
        for result in results:
            job = (
                f"{result.job_id.source_name}:{result.job_id.job_number}"
            )
            state_epoch = getattr(result, "state_epoch", 0)
            # The e2e anchor rides the result (ADR 0120). Distinguish
            # "bespoke result object without the attribute" (fall back
            # to the publish data timestamp) from a real JobResult
            # whose window carried NO data time (source_ts_ns is None):
            # the latter must stay None — an invented latency is worse
            # than a missing sample (telemetry/e2e.py), and the
            # freshness gauge must not report a dataless flush as
            # perfectly fresh.
            source_ts = getattr(result, "source_ts_ns", _NO_SOURCE_TS)
            if source_ts is _NO_SOURCE_TS:
                source_ts = ts
            if source_ts is not None and (
                window_source_ts is None or source_ts > window_source_ts
            ):
                window_source_ts = source_ts
            for key, da in zip(
                result.keys(), result.outputs.values(), strict=True
            ):
                try:
                    variables = dataarray_to_da00(da)
                    token = (
                        state_epoch,
                        tuple(
                            (
                                v.name,
                                tuple(np.asarray(v.data).shape),
                                str(np.asarray(v.data).dtype),
                                tuple(v.axes),
                            )
                            for v in variables
                        ),
                    )
                    frame = encode_da00(key.to_string(), ts, variables)
                    self.server.publish_frame(
                        stream_key(job, key.output_name),
                        frame,
                        token,
                        source_ts_ns=source_ts,
                    )
                except Exception:
                    logger.exception(
                        "fan-out encode failed for %s/%s",
                        job,
                        key.output_name,
                    )
        # One boundary observation per publish tick (ADR 0120): every
        # output of this window is now delta-encoded and enqueued.
        observe_stage("fanout_encoded", window_source_ts)

    def drop_job(self, job_id) -> int:
        """Drop a removed job's streams (wired to
        ``JobManager.set_retire_observer`` by the processor). Accepts a
        JobId or the already-formatted ``source:job_number`` string."""
        job = (
            job_id
            if isinstance(job_id, str)
            else f"{job_id.source_name}:{job_id.job_number}"
        )
        return self.server.drop_job(job)

    # -- QoS feedback ------------------------------------------------------
    def qos(self) -> dict[str, float | int]:
        """Subscriber count + worst queue pressure of the broadcast
        hub (``bench.py --fanout`` reports it)."""
        return self.server.qos()

    def close(self) -> None:
        self.closed = True
        self.server.close()


#: Planes by REQUESTED port (including 0): repeated service builds in
#: one process reuse their endpoint instead of leaking listeners.
#: Creation kwargs are remembered so a reuse with DIFFERENT settings
#: warns instead of silently dropping them.
_planes: dict[int, tuple[ServingPlane, dict]] = {}
_planes_lock = threading.Lock()


def get_or_create_plane(port: int, **kwargs) -> ServingPlane:
    with _planes_lock:
        entry = _planes.get(int(port))
        if entry is not None and entry[0].closed:
            # A closed plane's listener is dead: handing it out would
            # silently run the new service without the fan-out endpoint
            # — the exact dark-launch the loud-bind rule forbids.
            entry = None
        if entry is None:
            plane = ServingPlane(port=int(port), **kwargs)
            _planes[int(port)] = (plane, dict(kwargs))
            return plane
        plane, created_kwargs = entry
        if kwargs != created_kwargs:
            # Two services sharing one requested port share ONE plane
            # (their streams merge on one endpoint; job ids keep them
            # distinct) — but the second caller's settings do not
            # apply, which an operator should see, not guess.
            logger.warning(
                "serving plane on port %s reused with different "
                "settings %r (created with %r); the original settings "
                "stay in effect",
                port,
                kwargs,
                created_kwargs,
            )
        return plane

"""Broadcast plane: one publish in, N subscribers out (ADR 0117).

The hub decouples every viewer from the compute loop. The service's
publish hook calls :meth:`BroadcastServer.publish_frame` once per
(job, output) per publish tick; the hub stores the frame in the
:class:`~.result_cache.ResultCache`, delta-encodes it ONCE against the
previous tick (serving/delta.py), and enqueues the resulting blob onto
every attached subscriber's bounded queue. Per-subscriber cost is one
``put_nowait`` — no encoding, no device work, no serialization — so
publish-side work is flat in subscriber count (the bench ``--fanout``
acceptance).

Slow consumers are coalesced, never buffered unboundedly and never
waited on: when a subscriber's queue is full, its backlog is dropped,
a coalesce drop is counted, and a fresh keyframe of the CURRENT tick
takes its place — the consumer loses intermediate deltas (each tick's
frame supersedes the last; dashboards want now, not history) and
recovers exact state from the keyframe. The publish hook therefore
runs in O(subscribers) bounded, lock-cheap steps regardless of how
wedged any consumer is.

HTTP surface (stdlib ThreadingHTTPServer, the telemetry/http.py
pattern — daemon threads, loud bind failure at startup):

- ``GET /results`` — JSON index of every cached stream (job, output,
  epoch, seq, frame bytes, subscriber count);
- ``GET /streams/<job>/<output>`` — SSE: one ``keyframe`` event from
  the cache immediately, then live ``keyframe``/``delta`` events as
  ticks publish. ``data:`` is the base64 blob (serving/delta.py wire),
  ``id:`` the publish seq. ``<job>`` is ``source_name:job_number``.

``port=None`` runs the hub without HTTP — the bench's simulated
subscribers and the unit tests attach through :meth:`subscribe`, the
exact API the SSE handler uses.

Telemetry (ADR 0116): ``livedata_serving_frames``/``_bytes`` counters
(labeled keyframe|delta, counted per subscriber delivery — the fan-out
volume), ``livedata_serving_coalesce_drops``, and a keyed collector
exposing per-stream subscriber gauges and per-subscriber queue depths.
"""

from __future__ import annotations

import base64
import json
import logging
import os
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import unquote

from ..telemetry.e2e import observe_stage
from ..telemetry.registry import REGISTRY, MetricFamily, Sample
from .delta import (
    DeltaEncoder,
    decode_header,
    encode_delta,
    encode_keyframe,
)
from .result_cache import ResultCache

__all__ = ["BroadcastServer", "Subscription", "stream_key"]

logger = logging.getLogger(__name__)

#: Fan-out volume: frames/bytes enqueued per subscriber delivery, split
#: keyframe vs delta — delta bytes ≪ keyframe bytes is the tier's
#: bandwidth claim (bench --fanout records the ratio).
SERVING_FRAMES = REGISTRY.counter(
    "livedata_serving_frames",
    "Frames enqueued to subscribers by the broadcast plane",
    labelnames=("kind",),
)
SERVING_BYTES = REGISTRY.counter(
    "livedata_serving_bytes",
    "Bytes enqueued to subscribers by the broadcast plane",
    labelnames=("kind",),
)
SERVING_COALESCE_DROPS = REGISTRY.counter(
    "livedata_serving_coalesce_drops",
    "Slow-subscriber backlogs dropped and replaced by a keyframe",
)
#: Hub-side encodes per publish tick — ONE per (stream, tick) however
#: many subscribers or relays are attached (the fan-out saving; the
#: relay bench gates encodes/tick at the compute hub directly).
SERVING_ENCODES = REGISTRY.counter(
    "livedata_serving_encodes",
    "Delta/keyframe encodes performed by the broadcast hub (one per "
    "stream per publish tick, independent of subscriber count)",
    labelnames=("kind",),
)
#: Last-Event-ID resume outcomes (relay reconnects, browser refreshes):
#: ``delta`` = the gap was served from the recent-frame ring without a
#: full keyframe, ``current`` = the client was already at the head,
#: ``keyframe`` = epoch mismatch or ring miss forced a full rebase.
SERVING_RESUMES = REGISTRY.counter(
    "livedata_serving_resumes",
    "Subscriber attaches that carried Last-Event-ID resume metadata, "
    "by outcome",
    labelnames=("result",),
)


def stream_key(job: str, output: str) -> str:
    """The hub's stream id — mirrors the SSE path ``/streams/<job>/<output>``."""
    return f"{job}/{output}"


class Subscription:
    """One attached consumer: a bounded blob queue + resync flag.

    The queue is the ONLY hand-off between the publish hook and the
    consumer thread; it is bounded (coalesce-on-overflow, see module
    docstring) and drained with timeouts, so neither side can park
    forever (graftlint JGL010 discipline). Entries are
    ``(blob, source_ts_ns)`` pairs internally: the source timestamp
    rides along so dequeue can fold the ``subscriber_delivered`` e2e
    boundary in (ADR 0120) — the blob wire itself is untouched.
    """

    __slots__ = ("stream", "sub_id", "_queue", "delivered", "chaos", "stage")

    def __init__(
        self,
        stream: str,
        sub_id: int,
        limit: int,
        chaos=None,
        stage: str = "subscriber_delivered",
    ) -> None:
        self.stream = stream
        self.sub_id = sub_id
        self._queue: queue.Queue[tuple[bytes, int | None]] = queue.Queue(
            maxsize=limit
        )
        #: Blobs enqueued to this subscriber (hub-lock-guarded).
        self.delivered = 0
        #: Fault-injection schedule (harness/chaos.py): a fired
        #: ``subscriber_stall`` delays THIS consumer's dequeue — the
        #: slow-reader shape the coalesce path exists for.
        self.chaos = chaos
        #: The e2e boundary this consumer's dequeue observes (ADR
        #: 0120/0121): end viewers record ``subscriber_delivered``; a
        #: relay's upstream subscription records ``relay_ingress`` so
        #: the freshness histogram decomposes per hop instead of
        #: double-counting the headline stage.
        self.stage = stage

    def next_blob(self, timeout: float = 0.5) -> bytes | None:
        """The next blob, or None after ``timeout`` — callers loop and
        re-check their stop condition (never an untimeboxed park)."""
        blob, _ts = self.next_blob_meta(timeout=timeout)
        return blob

    def next_blob_meta(
        self, timeout: float = 0.5
    ) -> tuple[bytes | None, int | None]:
        """:meth:`next_blob` plus the blob's source timestamp (ns) —
        the SSE handler emits it as frame metadata. Dequeue is the
        ``subscriber_delivered`` boundary: the consumer owns the frame
        from here, whatever it does with it next."""
        if self.chaos is not None:
            self.chaos.maybe_delay("subscriber_stall")
        try:
            blob, ts = self._queue.get(timeout=timeout)
        except queue.Empty:
            return None, None
        observe_stage(self.stage, ts)
        return blob, ts

    def depth(self) -> int:
        return self._queue.qsize()

    # -- hub side (caller holds the hub lock) ------------------------------
    def _offer(self, blob: bytes, resync_keyframe, ts: int | None) -> bool:
        """Enqueue ``blob``; on overflow drop the backlog and enqueue a
        fresh keyframe instead (``resync_keyframe`` is a thunk so the
        keyframe encodes at most once per publish no matter how many
        subscribers overflowed). Returns False when coalesced."""
        try:
            self._queue.put_nowait((blob, ts))
            return True
        except queue.Full:
            while True:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break
            try:
                self._queue.put_nowait((resync_keyframe(), ts))
            except queue.Full:  # pragma: no cover - limit >= 1 by ctor
                pass
            return False


class BroadcastServer:
    """Subscriber hub + optional SSE/HTTP plane over a ResultCache."""

    def __init__(
        self,
        *,
        cache: ResultCache | None = None,
        port: int | None = None,
        host: str = "0.0.0.0",
        queue_limit: int = 32,
        name: str = "serving",
        heartbeat_s: float = 10.0,
        hop: int = 0,
        registry=REGISTRY,
    ) -> None:
        if queue_limit < 1:
            raise ValueError("queue_limit must be >= 1")
        if heartbeat_s <= 0:
            raise ValueError("heartbeat_s must be positive")
        self.cache = cache if cache is not None else ResultCache()
        self._queue_limit = int(queue_limit)
        self._name = name
        #: Seconds between SSE heartbeat comments on an idle stream —
        #: how fast a downstream relay/browser can tell a dead upstream
        #: from a quiet one (fleet/sse_client.py sizes its idle timeout
        #: from this).
        self.heartbeat_s = float(heartbeat_s)
        #: Distance from the compute tier in relay hops: 0 at the
        #: publishing service, upstream+1 at each relay. Rides every
        #: ``/results`` row so clients (and the metrics smoke) can see
        #: which tier they landed on.
        self.hop = int(hop)
        #: Hub incarnation id, leading every SSE event id
        #: (``<boot>:<epoch>:<seq>``). Epoch/seq numbering restarts
        #: with the process, so a ``Last-Event-ID`` from a PREVIOUS
        #: incarnation is not comparable — a boot mismatch forces the
        #: keyframe attach instead of silently treating the client as
        #: caught up (and lets a relay tell "upstream restarted" from
        #: "my connection blipped", ADR 0121).
        self.boot = os.urandom(4).hex()
        #: Optional callable returning extra ``/results`` rows for
        #: streams served by PEER nodes (fleet/control.py): each row
        #: carries a ``url`` pointing at the right hop. None = local
        #: index only.
        self._index_peers = None
        self._lock = threading.Lock()
        self._subscribers: dict[str, dict[int, Subscription]] = {}
        self._next_sub_id = 0
        #: Per-stream delta encoders — touched ONLY by the publish hook
        #: (single-writer contract, serving/delta.py); subscriber attach
        #: reads keyframes from the cache, never from here.
        self._encoders: dict[str, DeltaEncoder] = {}
        #: Last published source timestamp per stream (hub-lock-guarded):
        #: attach keyframes inherit it, and the scrape-time freshness
        #: collector reads it (ADR 0120).
        self._last_source_ts: dict[str, int] = {}
        #: Fault-injection schedule handed to new subscriptions
        #: (harness/chaos.py); None in production.
        self._chaos = None
        #: THIS hub's publish-tick encodes (hub-lock-guarded): the
        #: global ``livedata_serving_encodes`` counter sums every hub
        #: in the process, but the relay bench must prove the COMPUTE
        #: hub alone encodes once per stream per tick however many
        #: relays fan it out (ADR 0121).
        self.encodes = 0
        self._stopped = threading.Event()
        self._registry = registry
        self._collector_key = f"serving:{name}"
        registry.register_collector(self._collector_key, self._telemetry)
        self._frames_key = SERVING_FRAMES.labels(kind="keyframe")
        self._frames_delta = SERVING_FRAMES.labels(kind="delta")
        self._bytes_key = SERVING_BYTES.labels(kind="keyframe")
        self._bytes_delta = SERVING_BYTES.labels(kind="delta")
        self._server: ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        if port is not None:
            handler = type(
                "_BoundHandler", (_Handler,), {"broadcast": self}
            )
            # A bind failure raises at startup — an operator who asked
            # for a serve port must not silently run dark (the
            # telemetry/http.py rule).
            self._server = ThreadingHTTPServer((host, int(port)), handler)
            self._server.daemon_threads = True
            self._thread = threading.Thread(
                target=self._server.serve_forever,
                name=f"serving-http-{self.port}",
                daemon=True,
            )
            self._thread.start()
            logger.info(
                "result fan-out endpoint on %s:%d (/results, /streams/...)",
                host,
                self.port,
            )

    @property
    def port(self) -> int | None:
        """The bound port (0 requests an ephemeral one); None = hub-only."""
        return None if self._server is None else self._server.server_address[1]

    @property
    def stopped(self) -> bool:
        return self._stopped.is_set()

    def set_chaos(self, chaos) -> None:
        """Install a fault-injection schedule (harness/chaos.py) handed
        to every LATER subscription — existing consumers keep running
        clean, which is exactly how a partial-outage drill looks."""
        self._chaos = chaos

    def set_index_peers(self, peers) -> None:
        """Install a callable returning extra ``/results`` rows for
        streams served by peer nodes (fleet/control.py federation):
        replicas list each other's partitions, a relay lists upstream
        streams it has not (yet) relayed — each row's ``url`` points
        the client at the right hop. None removes the hook."""
        self._index_peers = peers

    # -- hub ---------------------------------------------------------------
    def subscribe(
        self,
        stream: str,
        *,
        resume: tuple[int, int] | None = None,
        stage: str = "subscriber_delivered",
    ) -> Subscription:
        """Attach a consumer; a keyframe of the latest cached tick is
        enqueued immediately (registration and the cache read happen
        under the hub lock, so a concurrent publish either reaches this
        subscriber's queue or is already inside its keyframe — the
        stale-delta rule in DeltaDecoder absorbs the overlap).

        ``resume`` is Last-Event-ID-style metadata ``(epoch, seq)`` — a
        reconnecting client that still holds the frame it decoded at
        that tick. When the epoch still matches and the recent-frame
        ring covers the gap, the missed ticks are served as DELTAS
        against the client's held frame instead of a full keyframe (the
        relay reconnect path, ADR 0121); an epoch mismatch or a gap
        older than the ring falls back to today's keyframe attach, and
        a client already at the head gets nothing queued (live frames
        follow). Outcomes count into ``livedata_serving_resumes``.

        ``stage`` names the e2e boundary this consumer's dequeues
        observe (see :class:`Subscription`).
        """
        with self._lock:
            sub_id = self._next_sub_id
            self._next_sub_id += 1
            sub = Subscription(
                stream,
                sub_id,
                self._queue_limit,
                chaos=self._chaos,
                stage=stage,
            )
            self._subscribers.setdefault(stream, {})[sub_id] = sub
            cached = self.cache.latest(stream)
            if cached is not None:
                ts = self._last_source_ts.get(stream)
                blobs, outcome = self._attach_blobs(stream, cached, resume)
                resync: list[bytes] = []

                def resync_keyframe() -> bytes:
                    # Overflow during a multi-delta resume must coalesce
                    # to a REAL keyframe (enqueuing a later delta would
                    # hand the client an unsignaled seq gap); encoded at
                    # most once, and reused when the attach blob already
                    # is that keyframe.
                    if blobs and decode_header(blobs[-1]).keyframe:
                        return blobs[-1]
                    if not resync:
                        resync.append(
                            encode_keyframe(
                                cached.frame,
                                epoch=cached.epoch,
                                seq=cached.seq,
                            )
                        )
                    return resync[0]

                for blob in blobs:
                    header = decode_header(blob)
                    if sub._offer(blob, resync_keyframe, ts):
                        sub.delivered += 1
                        if header.keyframe:
                            self._frames_key.inc()
                            self._bytes_key.inc(len(blob))
                        else:
                            self._frames_delta.inc()
                            self._bytes_delta.inc(len(blob))
                    else:
                        sub.delivered += 1
                        SERVING_COALESCE_DROPS.inc()
                        self._frames_key.inc()
                        self._bytes_key.inc(len(resync_keyframe()))
                if resume is not None:
                    SERVING_RESUMES.labels(result=outcome).inc()
        return sub

    def _attach_blobs(
        self, stream: str, latest, resume: tuple[int, int] | None
    ) -> tuple[list[bytes], str]:
        """The blobs a fresh subscription starts with (caller holds the
        hub lock): a keyframe normally; under a matching ``resume``,
        the ring-served delta gap or nothing at all. The keyframe is
        only encoded on the branches that return it — a clean resume
        must not pay an O(frame) copy under the hub lock."""

        def keyframe() -> list[bytes]:
            return [
                encode_keyframe(
                    latest.frame, epoch=latest.epoch, seq=latest.seq
                )
            ]

        if resume is None:
            return keyframe(), "keyframe"
        epoch, seq = resume
        if epoch != latest.epoch:
            return keyframe(), "keyframe"
        if seq >= latest.seq:
            # Already at (or somehow past) the head: live deltas apply
            # directly to the client's held frame.
            return [], "current"
        ring = {
            frame.seq: frame.frame for frame in self.cache.recent(stream)
        }
        if any(s not in ring for s in range(seq, latest.seq + 1)):
            # The gap predates the ring (or spans an epoch reset that
            # cleared it): only a full rebase is sound.
            return keyframe(), "keyframe"
        deltas = [
            encode_delta(
                ring[s - 1], ring[s], epoch=latest.epoch, seq=s
            )
            for s in range(seq + 1, latest.seq + 1)
        ]
        return deltas, "delta"

    def unsubscribe(self, sub: Subscription) -> None:
        with self._lock:
            subs = self._subscribers.get(sub.stream)
            if subs is not None:
                subs.pop(sub.sub_id, None)
                if not subs:
                    del self._subscribers[sub.stream]

    def publish_frame(
        self, stream: str, frame: bytes, token, source_ts_ns: int | None = None
    ) -> None:
        """One publish tick for one stream: cache it, delta-encode it
        once, fan the blob out to every attached subscriber's bounded
        queue. Called from the service's publish hook (step worker) —
        everything here is host-side O(frame) + O(subscribers).
        ``source_ts_ns`` (ADR 0120) rides each queue entry so dequeue
        records delivery freshness, and feeds the per-stream freshness
        gauges the scrape collector exposes."""
        cached = self.cache.put(stream, frame, token)
        encoder = self._encoders.get(stream)
        if encoder is None:
            encoder = self._encoders[stream] = DeltaEncoder()
        blob = encoder.encode(frame, epoch=cached.epoch, seq=cached.seq)
        is_keyframe = bool(decode_header(blob).keyframe)
        SERVING_ENCODES.labels(
            kind="keyframe" if is_keyframe else "delta"
        ).inc()
        resync: list[bytes] = []

        def resync_keyframe() -> bytes:
            # At most one keyframe encode per publish, shared by every
            # overflowed subscriber; when the tick's own blob already IS
            # the keyframe, reuse it outright.
            if is_keyframe:
                return blob
            if not resync:
                resync.append(
                    encode_keyframe(
                        frame, epoch=cached.epoch, seq=cached.seq
                    )
                )
                SERVING_ENCODES.labels(kind="resync").inc()
            return resync[0]

        frames_child = self._frames_key if is_keyframe else self._frames_delta
        bytes_child = self._bytes_key if is_keyframe else self._bytes_delta
        with self._lock:
            self.encodes += 1
            if source_ts_ns is not None:
                self._last_source_ts[stream] = int(source_ts_ns)
            subs = self._subscribers.get(stream)
            if not subs:
                return
            for sub in subs.values():
                delivered = sub._offer(blob, resync_keyframe, source_ts_ns)
                sub.delivered += 1
                if delivered:
                    frames_child.inc()
                    bytes_child.inc(len(blob))
                else:
                    SERVING_COALESCE_DROPS.inc()
                    self._frames_key.inc()
                    self._bytes_key.inc(len(resync_keyframe()))

    def drop_stream(self, stream: str) -> None:
        """Forget a retired stream (job removed): cache entry, encoder
        state and freshness entry go; attached subscribers simply stop
        receiving. (Dropping the freshness entry matters: a dead
        stream's gauge would otherwise read ever-staler forever —
        and pin the label set, the JGL025 cardinality leak.)"""
        self.cache.invalidate(stream)
        self._encoders.pop(stream, None)
        with self._lock:
            self._last_source_ts.pop(stream, None)

    def drop_job(self, job: str) -> int:
        """Forget every stream of one retired job (the JobManager's
        remove command, via the retire observer): without this a
        long-running service under job churn would cache a ring of
        full frames per dead stream forever and keep listing it in
        ``/results`` as if live. Returns how many streams dropped."""
        prefix = f"{job}/"
        streams = [
            stream
            for stream in self.cache.streams()
            if stream.startswith(prefix)
        ]
        # Encoder keys are publish-hook-private, but a removed job
        # publishes nothing further — popping here is safe and frees
        # the prev-frame copy the encoder holds.
        for stream in streams:
            self.drop_stream(stream)
        return len(streams)

    # -- QoS ----------------------------------------------------------------
    def qos(self) -> dict[str, float | int]:
        """Subscriber count + worst send-queue pressure in [0, 1] (the
        load harness, harness/load.py, reports both)."""
        with self._lock:
            n = sum(len(subs) for subs in self._subscribers.values())
            pressure = 0.0
            for subs in self._subscribers.values():
                for sub in subs.values():
                    pressure = max(
                        pressure, sub.depth() / self._queue_limit
                    )
            return {"subscribers": n, "queue_pressure": pressure}

    # -- telemetry ----------------------------------------------------------
    def _telemetry(self) -> list[MetricFamily]:
        subs_fam = MetricFamily(
            "livedata_serving_subscribers",
            "gauge",
            "Attached broadcast subscribers per stream",
        )
        depth_fam = MetricFamily(
            "livedata_serving_queue_depth",
            "gauge",
            "Per-subscriber send-queue depth (bounded at queue_limit; "
            "overflow coalesces to a keyframe instead of growing)",
        )
        fresh_fam = MetricFamily(
            "livedata_result_freshness_seconds",
            "gauge",
            "Wall-clock age of the newest published source timestamp "
            "per (job, output) stream (ADR 0120): how stale a viewer "
            "attaching NOW would be",
        )
        now_ns = time.time_ns()
        base = (("server", self._name),)
        with self._lock:
            total = 0
            for stream, subs in sorted(self._subscribers.items()):
                total += len(subs)
                subs_fam.samples.append(
                    Sample("", base + (("stream", stream),), len(subs))
                )
                for sub_id, sub in sorted(subs.items()):
                    depth_fam.samples.append(
                        Sample(
                            "",
                            base
                            + (
                                ("stream", stream),
                                ("subscriber", str(sub_id)),
                            ),
                            sub.depth(),
                        )
                    )
            for stream, ts in sorted(self._last_source_ts.items()):
                job, _, output = stream.partition("/")
                fresh_fam.samples.append(
                    Sample(
                        "",
                        base + (("job", job), ("output", output)),
                        max(0.0, (now_ns - ts) / 1e9),
                    )
                )
        subs_fam.samples.append(
            Sample("", base + (("stream", "all"),), total)
        )
        return [subs_fam, depth_fam, fresh_fam]

    def close(self) -> None:
        self._stopped.set()
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            if self._thread is not None:
                self._thread.join(timeout=5.0)
        # Owner-guarded: a successor server under the same name must
        # not lose its live collector to our late close (ADR 0116).
        self._registry.unregister_collector(
            self._collector_key, self._telemetry
        )


class _Handler(BaseHTTPRequestHandler):
    broadcast: BroadcastServer

    protocol_version = "HTTP/1.1"

    def do_GET(self) -> None:  # noqa: N802 - BaseHTTPRequestHandler API
        path = self.path.split("?", 1)[0]
        if path == "/results":
            self._serve_index()
        elif path.startswith("/streams/"):
            self._serve_stream(path)
        else:
            self._json_error(
                404, "unknown path (try /results or /streams/<job>/<output>)"
            )

    def _json_error(self, code: int, message: str) -> None:
        payload = json.dumps({"error": message}).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def _serve_index(self) -> None:
        hub = self.broadcast
        streams = hub.cache.streams()
        with hub._lock:
            counts = {
                stream: len(subs)
                for stream, subs in hub._subscribers.items()
            }
            peers = hub._index_peers
        rows = []
        for stream, cached in sorted(streams.items()):
            job, _, output = stream.partition("/")
            rows.append(
                {
                    "job": job,
                    "output": output,
                    "stream": stream,
                    "epoch": cached.epoch,
                    "seq": cached.seq,
                    "frame_bytes": len(cached.frame),
                    "subscribers": counts.get(stream, 0),
                    "path": f"/streams/{stream}",
                    "node": hub._name,
                    "hop": hub.hop,
                }
            )
        if peers is not None:
            # Federation (ADR 0121): append peer rows for streams this
            # node does not serve locally — a peer outage degrades the
            # index to local-only instead of 500ing it.
            local = {row["stream"] for row in rows}
            try:
                rows.extend(
                    row
                    for row in peers()
                    if row.get("stream") not in local
                )
            except Exception:
                logger.exception("peer index federation failed")
        payload = json.dumps({"streams": rows}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def _serve_stream(self, path: str) -> None:
        parts = path.split("/", 3)
        if len(parts) < 4 or not parts[2] or not parts[3]:
            self._json_error(404, "expected /streams/<job>/<output>")
            return
        stream = stream_key(unquote(parts[2]), unquote(parts[3]))
        hub = self.broadcast
        if hub.cache.latest(stream) is None:
            self._json_error(
                404,
                f"no published results for stream {stream!r} "
                "(see /results for the index)",
            )
            return
        # Last-Event-ID resume (ADR 0121): the SSE ``id:`` field is
        # ``<boot>:<epoch>:<seq>``; a reconnecting EventSource (or
        # relay) echoes it back and, boot + epoch permitting, resumes
        # on deltas instead of a full keyframe. An id minted by a
        # PREVIOUS hub incarnation (boot mismatch) or a malformed one
        # degrades to the plain keyframe attach.
        resume = None
        raw_id = self.headers.get("Last-Event-ID")
        if raw_id:
            parts = raw_id.strip().split(":")
            if len(parts) == 3 and parts[0] == hub.boot:
                try:
                    resume = (int(parts[1]), int(parts[2]))
                except ValueError:
                    resume = None
        sub = hub.subscribe(stream, resume=resume)
        try:
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            # SSE is an unbounded response: no Content-Length, and the
            # connection closes when either side goes away.
            self.send_header("Connection", "close")
            self.end_headers()
            self.wfile.write(b"retry: 3000\n\n")
            last_write = time.monotonic()
            heartbeat_s = hub.heartbeat_s
            while not hub.stopped:
                blob, source_ts = sub.next_blob_meta(
                    timeout=min(0.5, heartbeat_s / 2)
                )
                if blob is None:
                    if time.monotonic() - last_write >= heartbeat_s:
                        # Idle-stream heartbeat: lets a client (relay,
                        # EventSource wrapper) distinguish "no new
                        # ticks" from "dead upstream" without waiting
                        # out a TCP timeout (ADR 0121).
                        self.wfile.write(b": keepalive\n\n")
                        self.wfile.flush()
                        last_write = time.monotonic()
                    continue
                header = decode_header(blob)
                kind = b"keyframe" if header.keyframe else b"delta"
                # Frame metadata (ADR 0120): the source timestamp as an
                # SSE comment — EventSource clients ignore comments, so
                # the data wire is unchanged, but a latency-aware
                # client (the SLO harness, dashboards) reads its
                # freshness without decoding da00.
                meta = (
                    b""
                    if source_ts is None
                    else b": source_ts_ns=%d\n" % source_ts
                )
                self.wfile.write(
                    b"%sid: %s:%d:%d\nevent: %s\ndata: %s\n\n"
                    % (
                        meta,
                        hub.boot.encode(),
                        header.epoch,
                        header.seq,
                        kind,
                        base64.b64encode(blob),
                    )
                )
                self.wfile.flush()
                last_write = time.monotonic()
        except (BrokenPipeError, ConnectionResetError, OSError):
            # Consumer went away mid-stream: routine, not an error.
            logger.debug("SSE subscriber %d disconnected", sub.sub_id)
        finally:
            hub.unsubscribe(sub)

    def log_message(self, format: str, *args) -> None:  # noqa: A002
        logger.debug("serving http: " + format, *args)

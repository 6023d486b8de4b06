"""The /metrics plane: a stdlib HTTP endpoint for scrapes + liveness.

Every service runner grows ``--metrics-port`` / ``LIVEDATA_METRICS_PORT``
(core/service.py ``setup_arg_parser``); when set, a
:class:`MetricsServer` serves

- ``GET /metrics`` — the process registry rendered in Prometheus text
  exposition format (telemetry/exposition.py);
- ``GET /healthz`` — liveness plus a degraded latch (ADR 0120):
  ``200 {"status": "ok"}`` normally, ``200 {"status": "degraded",
  "reason": ...}`` while the slow-tick watchdog is latched or a
  ``state_lost`` containment fired in the last interval
  (telemetry/health.py). Always HTTP 200 — a supervisor's restart
  probe must not restart-loop a degraded-but-alive service; readiness
  semantics stay with the x5f2 status heartbeats, which carry the real
  job/source health;
- ``POST /profile?seconds=N`` — profiler on command: a
  ``jax.profiler`` session of N seconds (default 5, at most 60) into a
  fresh directory under ``$TMPDIR`` that the ``202`` response names.
  The one path that changes state on an unauthenticated port, so it is
  held short: no path is taken from the client; ``409`` while a
  session runs (one started by ``--profile`` included); ``429`` with
  ``Retry-After`` for two minutes after a session it started (the
  profiler costs the tail of every tick it covers); the four newest
  ``livedata-profile-*`` directories under ``$TMPDIR`` are kept and
  older ones deleted; ``503`` in a process that never loaded jax (the
  relay, the fakes: this endpoint must not be what imports it);
  ``400`` on a bad ``seconds``. While the session runs the tick spans
  are in its trace too (telemetry/trace.py).

stdlib only (``http.server`` ThreadingHTTPServer on a daemon thread):
the container bakes no prometheus_client, and a scrape every 15 s is
far below any load that would justify one. The server binds once per
process — a second start on the same port raises loudly at startup
(a deployment error), never mid-serve.
"""

from __future__ import annotations

import json
import logging
import math
import shutil
import sys
import tempfile
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from urllib.parse import parse_qs, urlsplit

from .exposition import CONTENT_TYPE, render_text
from .health import HEALTH
from .registry import REGISTRY, MetricsRegistry

__all__ = ["MetricsServer", "start_metrics_server"]

logger = logging.getLogger(__name__)

#: POST /profile: the session length when none is asked for, and the
#: longest one an unauthenticated caller may start (a trace grows by
#: tens of MB a second on a busy chip).
PROFILE_DEFAULT_S = 5.0
PROFILE_MAX_S = 60.0
#: ... how long after a session of its own the endpoint refuses the next
#: (a profiled tick's tail is 15-35 ms longer: PERF.md section 6), and
#: how many trace directories it leaves under $TMPDIR.
PROFILE_COOLDOWN_S = 120.0
PROFILE_KEEP_DIRS = 4
_PROFILE_PREFIX = "livedata-profile-"


class _ProfileOnCommand:
    """``POST /profile``: decides and starts one session at a time."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        #: ``monotonic`` time before which the next session is refused.
        self._not_before = 0.0

    @staticmethod
    def _fresh_dir() -> str:
        """A new trace directory under $TMPDIR, after deleting all but
        the newest ``PROFILE_KEEP_DIRS - 1`` that earlier sessions (of
        this process or an earlier one) left there."""
        root = Path(tempfile.gettempdir())
        old = sorted(
            (p for p in root.glob(_PROFILE_PREFIX + "*") if p.is_dir()),
            key=lambda p: p.stat().st_mtime,
        )
        for path in old[: max(0, len(old) - (PROFILE_KEEP_DIRS - 1))]:
            shutil.rmtree(path, ignore_errors=True)
        return tempfile.mkdtemp(prefix=_PROFILE_PREFIX)

    def start(self, seconds: float) -> tuple[int, dict, dict[str, str]]:
        """(status, body, headers) of the answer."""
        if "jax" not in sys.modules:
            return 503, {"error": "no device runtime in this process"}, {}
        from ..utils.profiling import bounded_device_trace, session_active

        running = 409, {"error": "a profiler session is running"}, {}
        with self._lock:
            if session_active():
                return running
            wait = self._not_before - time.monotonic()
            if wait > 0:
                return (
                    429,
                    {"error": "cooling down after the last session"},
                    {"Retry-After": str(math.ceil(wait))},
                )
            log_dir = self._fresh_dir()
            if not bounded_device_trace(log_dir, seconds):
                # a --profile session won the race
                shutil.rmtree(log_dir, ignore_errors=True)
                return running
            self._not_before = time.monotonic() + seconds + PROFILE_COOLDOWN_S
        logger.info("profiler session: %.1f s into %s", seconds, log_dir)
        return 202, {"dir": log_dir, "seconds": seconds}, {}


_PROFILE = _ProfileOnCommand()


class _Handler(BaseHTTPRequestHandler):
    registry: MetricsRegistry = REGISTRY

    def do_GET(self) -> None:  # noqa: N802 - BaseHTTPRequestHandler API
        path = self.path.split("?", 1)[0]
        if path == "/metrics":
            try:
                payload = render_text(self.registry.collect()).encode()
            except Exception:
                logger.exception("metrics render failed")
                self.send_error(500, "metrics render failed")
                return
            self.send_response(200)
            self.send_header("Content-Type", CONTENT_TYPE)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)
        elif path == "/healthz":
            payload = json.dumps(HEALTH.healthz()).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)
        else:
            self.send_error(404, "unknown path (try /metrics or /healthz)")

    def _send_json(
        self, status: int, body: dict, headers: dict[str, str] | None = None
    ) -> None:
        payload = json.dumps(body).encode()
        self.send_response(status)
        for name, value in (headers or {}).items():
            self.send_header(name, value)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(payload)))
        self.end_headers()
        self.wfile.write(payload)

    def do_POST(self) -> None:  # noqa: N802 - BaseHTTPRequestHandler API
        url = urlsplit(self.path)
        if url.path != "/profile":
            self.send_error(404, "unknown path (try POST /profile)")
            return
        asked = parse_qs(url.query).get("seconds", [str(PROFILE_DEFAULT_S)])
        try:
            seconds = float(asked[-1])
        except ValueError:
            seconds = math.nan
        if not 0 < seconds <= PROFILE_MAX_S:
            self._send_json(
                400,
                {"error": f"seconds must be in (0, {PROFILE_MAX_S:g}]"},
            )
            return
        try:
            status, body, headers = _PROFILE.start(seconds)
        except Exception:
            logger.exception("profiler start failed")
            self.send_error(500, "profiler start failed")
            return
        self._send_json(status, body, headers)

    def log_message(self, format: str, *args) -> None:  # noqa: A002
        # Scrapes every few seconds must not spam the service log.
        logger.debug("metrics http: " + format, *args)


class MetricsServer:
    """ThreadingHTTPServer on a daemon thread; ``close()`` joins it."""

    def __init__(
        self,
        port: int,
        *,
        host: str = "0.0.0.0",
        registry: MetricsRegistry = REGISTRY,
    ) -> None:
        handler = type("_BoundHandler", (_Handler,), {"registry": registry})
        self._server = ThreadingHTTPServer((host, port), handler)
        self._server.daemon_threads = True
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"metrics-http-{port}",
            daemon=True,
        )
        self._thread.start()
        logger.info(
            "metrics endpoint on %s:%d (/metrics, /healthz, POST /profile)",
            host,
            self.port,
        )

    @property
    def port(self) -> int:
        """The bound port (port 0 requests an ephemeral one — tests)."""
        return self._server.server_address[1]

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5.0)


def start_metrics_server(
    port: int | None, *, registry: MetricsRegistry = REGISTRY
) -> MetricsServer | None:
    """Start the plane when a port is configured; None otherwise.

    A bind failure raises: an operator who asked for a metrics port
    must not silently run blind (the same loud-failure rule as a bad
    --mesh spec)."""
    if port is None:
        return None
    return MetricsServer(int(port), registry=registry)

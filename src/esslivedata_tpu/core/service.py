"""Process lifecycle for backend services.

Parity with reference ``core/service.py`` (ServiceBase:22, Service:100,
_run_loop:156, setup_arg_parser:194, get_env_defaults:236): a worker thread
polls ``processor.process()`` every ``poll_interval``; SIGTERM/SIGINT stop
cleanly; an uncaught worker error stops the service with a nonzero exit code
so a ``restart: on-failure`` supervisor restarts the process. ``step()``
single-steps the loop deterministically for tests.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import threading
import time
from typing import Any

from .processor import Processor

__all__ = ["Service", "ServiceBase", "get_env_defaults", "setup_arg_parser"]

logger = logging.getLogger(__name__)

# GC pinning is interpreter-global state: with several Service loops in
# one process (tests, combined deployments) the collector must stay
# disabled until the LAST pinned loop exits, and be restored only if it
# was enabled when the FIRST loop pinned it.
_gc_pin_lock = threading.Lock()
_gc_pin_count = 0
_gc_was_enabled = False


def _gc_pin() -> bool:
    """Pin the cycle collector off (process-wide refcount). Returns True
    iff the caller must balance with ``_gc_unpin``."""
    import gc

    global _gc_pin_count, _gc_was_enabled
    with _gc_pin_lock:
        _gc_pin_count += 1
        if _gc_pin_count == 1:
            _gc_was_enabled = gc.isenabled()
            gc.freeze()  # startup objects: off the collector's plate
            gc.disable()
    return True


def _gc_unpin() -> None:
    import gc

    global _gc_pin_count
    with _gc_pin_lock:
        _gc_pin_count -= 1
        if _gc_pin_count == 0:
            gc.unfreeze()
            if _gc_was_enabled:
                gc.enable()

ENV_PREFIX = "LIVEDATA_"


def get_env_defaults(parser: argparse.ArgumentParser, prefix: str = ENV_PREFIX) -> dict[str, Any]:
    """Defaults for parser args from LIVEDATA_* env vars (reference
    service.py:236): ``--instrument`` <- ``LIVEDATA_INSTRUMENT`` etc."""
    defaults: dict[str, Any] = {}
    for action in parser._actions:  # noqa: SLF001 - argparse has no public iteration
        if not action.option_strings:
            continue
        env_name = prefix + action.dest.upper()
        if env_name not in os.environ:
            continue
        raw = os.environ[env_name]
        if action.const is not None and isinstance(action.const, bool):
            defaults[action.dest] = raw.lower() in ("1", "true", "yes")
        elif action.type is not None:
            defaults[action.dest] = action.type(raw)
        else:
            defaults[action.dest] = raw
    return defaults


#: Metrics servers started by parse_args, keyed by the REQUESTED port
#: (including 0, the ephemeral ask): a process that parses twice (tests
#: driving main() repeatedly) must reuse its endpoint — keying by the
#: resolved port would make every `--metrics-port 0` parse leak another
#: listener, the exact accumulation this table exists to prevent. The
#: bound port is `server.port` on the stored value.
_metrics_servers: dict[int, Any] = {}
_trace_dump_paths: set[str] = set()


def _start_telemetry(parsed: argparse.Namespace) -> None:
    """Telemetry plane wiring shared by every runner (ADR 0116):
    ``--metrics-port``/``LIVEDATA_METRICS_PORT`` starts the /metrics +
    /healthz endpoint; ``--trace-dump PATH`` registers an exit-time
    Chrome trace_event dump of the tick tracer's ring."""
    port = getattr(parsed, "metrics_port", None)
    if port is None and os.environ.get("LIVEDATA_METRICS_PORT"):
        # Belt-and-braces: the env default normally lands via
        # get_env_defaults, but a runner that skips set_defaults still
        # honors the operator's env.
        port = int(os.environ["LIVEDATA_METRICS_PORT"])
    if port is not None and int(port) not in _metrics_servers:
        from ..telemetry.http import start_metrics_server

        server = start_metrics_server(int(port))
        if server is not None:
            _metrics_servers[int(port)] = server
    dump_path = getattr(parsed, "trace_dump", None)
    if dump_path and dump_path not in _trace_dump_paths:
        _trace_dump_paths.add(dump_path)
        import atexit

        from ..telemetry.trace import TRACER

        def _dump() -> None:
            try:
                TRACER.dump(dump_path)
            except Exception:  # pragma: no cover - exit-path best effort
                logger.exception("trace dump to %s failed", dump_path)

        atexit.register(_dump)


class _ServiceArgumentParser(argparse.ArgumentParser):
    """parse_args applies the CPU pin (and starts the telemetry plane)
    BEFORE returning: every service main parses first and builds
    (touching JAX) after, so handling it here covers --cpu /
    LIVEDATA_FORCE_CPU, --metrics-port / LIVEDATA_METRICS_PORT and
    programmatic argv lists alike, for all eight runners.
    """

    def parse_args(self, *args, **kwargs):  # type: ignore[override]
        parsed = super().parse_args(*args, **kwargs)
        force_env = os.environ.get("LIVEDATA_FORCE_CPU", "").lower() in (
            "1",
            "true",
            "yes",
        )
        if getattr(parsed, "cpu", False) or force_env:
            from ..utils.platform_pin import pin_cpu

            pin_cpu()
        _start_telemetry(parsed)
        return parsed


def setup_arg_parser(description: str = "") -> argparse.ArgumentParser:
    """Common CLI surface shared by all services (reference service.py:194).

    ``LIVEDATA_FORCE_CPU`` (1/true/yes) or ``--cpu`` pins JAX to the CPU
    backend before anything initializes one — how a dev/demo run CHOOSES
    the CPU. Without it the service runs on whatever jax finds and names
    it at start (utils/runtime.log_device_identity).
    """
    parser = _ServiceArgumentParser(description=description)
    parser.add_argument("--instrument", required=False, default="dummy")
    parser.add_argument("--dev", action="store_true", default=False)
    parser.add_argument(
        "--cpu",
        action="store_true",
        default=False,
        help="pin JAX to the CPU backend (see LIVEDATA_FORCE_CPU)",
    )
    parser.add_argument("--log-level", default="INFO")
    parser.add_argument("--log-json-file", default=None)
    parser.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve the process telemetry registry on this port "
        "(GET /metrics: Prometheus text exposition; GET /healthz: "
        "liveness). LIVEDATA_METRICS_PORT equivalently; 0 picks an "
        "ephemeral port (ADR 0116)",
    )
    parser.add_argument(
        "--serve-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve the result fan-out tier on this port (GET "
        "/results: JSON stream index; GET /streams/<job>/<output>: "
        "SSE keyframe-then-deltas broadcast of the job's da00 "
        "outputs). LIVEDATA_SERVE_PORT equivalently; 0 picks an "
        "ephemeral port (ADR 0117)",
    )
    parser.add_argument(
        "--checkpoint-dir",
        default=None,
        metavar="DIR",
        help="durability plane (ADR 0118): periodically checkpoint "
        "every job's device state + Kafka offset bookmarks into DIR "
        "(atomic manifests); on restart the newest consistent "
        "generation restores and consumers seek to the bookmarks, so "
        "the gap replays instead of the accumulation resetting. "
        "LIVEDATA_CHECKPOINT_DIR equivalently",
    )
    parser.add_argument(
        "--checkpoint-interval",
        type=float,
        default=None,
        metavar="SECONDS",
        help="checkpoint cadence (default 30 s; "
        "LIVEDATA_CHECKPOINT_INTERVAL equivalently)",
    )
    parser.add_argument(
        "--warmup",
        action="store_true",
        default=False,
        help="AOT warm-up (ADR 0118): compile tick programs on a "
        "background thread at job-commit/regroup time so the hot "
        "path never pays a jit compile at commit "
        "(LIVEDATA_WARMUP equivalently)",
    )
    parser.add_argument(
        "--batch-decode",
        action="store_true",
        default=False,
        help="batch decode plane (ADR 0125): adapt a whole consume "
        "poll per dispatch — ev44 headers walked once, payloads landed "
        "zero-copy into reusable decode arenas, pixel-id sanitize "
        "fused into device staging. Byte-identical da00 output vs the "
        "per-message reference path (LIVEDATA_BATCH_DECODE=1 "
        "equivalently)",
    )
    parser.add_argument(
        "--trace-dump",
        default=None,
        metavar="PATH",
        help="write the per-tick tracer's span ring as Chrome "
        "trace_event JSON (chrome://tracing / Perfetto loadable) to "
        "PATH at exit; span recording itself is on unless "
        "LIVEDATA_TRACE=0 (ADR 0116)",
    )
    return parser


class ServiceBase:
    """Shared start/stop/signal scaffolding."""

    def __init__(self, *, name: str | None = None) -> None:
        self._name = name or self.__class__.__name__
        self._running = threading.Event()
        self._stopped = False
        self.exit_code = 0

    @property
    def name(self) -> str:
        return self._name

    @property
    def is_running(self) -> bool:
        return self._running.is_set()

    def start(self, blocking: bool = True) -> None:
        logger.info("Starting service %s", self._name)
        self._stopped = False
        self._running.set()
        self._start_impl()
        if blocking:
            self.run_forever()

    def _start_impl(self) -> None:  # pragma: no cover - overridden
        pass

    def stop(self) -> None:
        # _running may already be cleared (signal handler, worker failure);
        # _stop_impl must still run exactly once so the worker is joined and
        # finalize() can flush before the interpreter exits.
        if self._stopped:
            return
        self._stopped = True
        logger.info("Stopping service %s", self._name)
        self._running.clear()
        self._stop_impl()

    def _stop_impl(self) -> None:  # pragma: no cover - overridden
        pass

    def _signal_handler(self, signum: int, frame: Any) -> None:  # noqa: ARG002
        logger.info("Service %s received signal %s", self._name, signum)
        self._running.clear()

    def install_signal_handlers(self) -> None:
        signal.signal(signal.SIGTERM, self._signal_handler)
        signal.signal(signal.SIGINT, self._signal_handler)

    def run_forever(self) -> None:
        """Park the main thread until a signal or worker failure stops us."""
        self.install_signal_handlers()
        try:
            while self._running.is_set():
                time.sleep(0.1)
        finally:
            self.stop()


class Service(ServiceBase):
    """Runs a processor in a worker thread at a fixed poll interval."""

    def __init__(
        self,
        *,
        processor: Processor,
        name: str | None = None,
        poll_interval_s: float = 0.01,
    ) -> None:
        super().__init__(name=name)
        self._processor = processor
        self._poll_interval_s = poll_interval_s
        self._thread: threading.Thread | None = None

    @property
    def processor(self) -> Processor:
        return self._processor

    def step(self) -> None:
        """Single-step the loop — the deterministic test entry point
        (reference service.py:150)."""
        self._processor.process()

    #: Worker iterations between explicit cycle collections while the
    #: collector is pinned off (~14 s at the 14 Hz pulse cadence).
    GC_COLLECT_EVERY = 200

    def _run_loop(self) -> None:
        # GC pinning (LIVEDATA_GC_PINNING=0 disables): a gen-2 cycle
        # collection landing inside the ingest->publish window is a
        # multi-ms p99 outlier at LOKI batch sizes. Reference-counting
        # frees the numpy temporaries either way; the cycle collector is
        # only needed for cycles, so run it explicitly BETWEEN process()
        # calls where the 71 ms pulse budget absorbs it.
        pin_gc = os.environ.get("LIVEDATA_GC_PINNING", "1") != "0"
        did_disable = False
        if pin_gc:
            did_disable = _gc_pin()
        iterations = 0
        try:
            while self._running.is_set():
                start = time.monotonic()
                self._processor.process()
                iterations += 1
                if pin_gc and iterations % self.GC_COLLECT_EVERY == 0:
                    import gc

                    gc.collect()
                elapsed = time.monotonic() - start
                sleep = self._poll_interval_s - elapsed
                if sleep > 0:
                    time.sleep(sleep)
        except Exception:
            logger.exception("Service %s worker failed", self._name)
            self.exit_code = 1
            self._running.clear()
            # Wake the parked main thread so the process exits and the
            # supervisor restarts it (reference service.py:166-180).
            try:
                signal.raise_signal(signal.SIGINT)
            # Intentional swallow: the wakeup is best-effort during crash
            # teardown, and any error here (exotic platform, interpreter
            # shutdown) must not mask the worker failure logged above.
            except Exception:  # pragma: no cover  # graftlint: disable=JGL007
                pass
        finally:
            if did_disable:
                _gc_unpin()
            try:
                self._processor.finalize()
            except Exception:
                logger.exception("Service %s finalize failed", self._name)

    def _start_impl(self) -> None:
        self._thread = threading.Thread(
            target=self._run_loop, name=f"{self._name}-worker", daemon=True
        )
        self._thread.start()

    def _stop_impl(self) -> None:
        if self._thread is not None:
            # Processors advertise how long their finalize may take
            # (a pipelined processor drains in-flight windows, ADR 0111:
            # no dropped batches on stop); default to the historical 5 s.
            timeout = float(
                getattr(self._processor, "stop_grace_s", 5.0)
            )
            self._thread.join(timeout=timeout)
            self._thread = None

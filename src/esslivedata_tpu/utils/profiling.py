"""Device + host profiling subsystem.

SURVEY.md §5 build note: the reference has no dedicated tracer (timings
come from per-batch processing_time_s + 30 s metrics); here device-level
profiling is first-class. Two tools:

- :func:`bounded_device_trace`: a wall-clock-bounded ``jax.profiler``
  session (``--profile`` at launch, ``POST /profile`` on command)
  writing a TensorBoard/Perfetto-loadable trace of XLA execution with
  the tick spans beside it (telemetry/trace.py mirrors them in).
- :class:`StageTimer`: cheap wall-clock stage accounting for the service
  hot loop (decode / stage / device step / publish), drained into the 30 s
  metrics report the same way consumer metrics are.
"""

from __future__ import annotations

import threading
import time
import logging
from collections import defaultdict
from contextlib import contextmanager

__all__ = [
    "StageTimer",
    "bounded_device_trace",
    "device_memory_stats",
    "session_active",
]


class StageTimer:
    """Accumulates wall time per named stage; thread-safe; drain-and-reset.

    ``with timer.stage("device_step"): ...`` around hot-loop phases; the
    metrics reporter drains a summary every interval.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._total_s: dict[str, float] = defaultdict(float)
        self._count: dict[str, int] = defaultdict(int)
        self._max_s: dict[str, float] = defaultdict(float)
        # Cumulative twins that drain() does NOT reset: the telemetry
        # collectors (ADR 0116) need monotone busy-seconds counters —
        # Prometheus rate() is a subtraction of successive scrapes, and
        # a 30 s-drained total would alias with any scrape interval
        # that is not a divisor of the metrics cadence.
        self._cum_total_s: dict[str, float] = defaultdict(float)
        self._cum_count: dict[str, int] = defaultdict(int)

    @contextmanager
    def stage(self, name: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            self.record(name, time.perf_counter() - start)

    def record(self, name: str, seconds: float) -> None:
        """Fold an externally measured duration in — used where the
        timing happened on another thread (pipeline stage workers) and
        only the number crosses over."""
        with self._lock:
            self._total_s[name] += seconds
            self._count[name] += 1
            if seconds > self._max_s[name]:
                self._max_s[name] = seconds
            self._cum_total_s[name] += seconds
            self._cum_count[name] += 1

    def cumulative(self) -> dict[str, dict[str, float]]:
        """Per-stage {total_s, count} since construction — never reset
        by :meth:`drain` (the telemetry collector's read)."""
        with self._lock:
            return {
                name: {
                    "total_s": self._cum_total_s[name],
                    "count": float(self._cum_count[name]),
                }
                for name in self._cum_total_s
            }

    def drain(self) -> dict[str, dict[str, float]]:
        """Per-stage {total_s, count, mean_ms, max_ms}; resets counters."""
        with self._lock:
            out = {
                name: {
                    "total_s": self._total_s[name],
                    "count": self._count[name],
                    "mean_ms": 1e3 * self._total_s[name] / self._count[name],
                    "max_ms": 1e3 * self._max_s[name],
                }
                for name in self._total_s
                if self._count[name]
            }
            self._total_s.clear()
            self._count.clear()
            self._max_s.clear()
            return out


#: Held while a profiler session runs: ``jax.profiler`` allows one per
#: process, whoever started it (--profile at launch, POST /profile).
_SESSION = threading.Lock()


def session_active() -> bool:
    """Whether a profiler session started here is still running."""
    return _SESSION.locked()


def bounded_device_trace(log_dir: str, seconds: float) -> bool:
    """Capture a wall-clock-bounded device trace without blocking the
    caller: starts the JAX profiler now and schedules the stop on a timer
    thread. For long-running services (``--profile``, ``POST /profile``):
    an unbounded trace would grow without limit, so the capture window is
    explicit. The stop also runs at interpreter exit — a service stopped
    before the window elapses must still flush the trace, not lose it.

    Returns False, and starts nothing, while another session runs."""
    import atexit

    import jax

    if not _SESSION.acquire(blocking=False):
        return False
    try:
        jax.profiler.start_trace(log_dir)
    except BaseException:
        _SESSION.release()
        raise
    once = threading.Lock()

    def _stop() -> None:
        # Timer thread and atexit may both come: the first one stops.
        if not once.acquire(blocking=False):
            return
        try:
            jax.profiler.stop_trace()
        except Exception:  # pragma: no cover - profiler teardown races
            logging.getLogger(__name__).exception("stop_trace failed")
        finally:
            _SESSION.release()
            atexit.unregister(_stop)

    atexit.register(_stop)
    timer = threading.Timer(seconds, _stop)
    timer.daemon = True
    timer.start()
    return True


def device_memory_stats() -> dict[str, int]:
    """Per-device HBM statistics for the metrics log (SURVEY §5: device
    memory in the 30 s rollover). Backends without memory_stats (CPU)
    yield an empty dict."""
    import jax

    out: dict[str, int] = {}
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                out[f"{device.id}:{key}"] = int(stats[key])
    return out


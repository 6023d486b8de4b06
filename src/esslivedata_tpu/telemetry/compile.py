"""The compile-event instrument: jit-cache misses as a labeled histogram.

A compile stall (hundreds of ms to seconds of one-off XLA work) is
real user-visible p99 (every job commit or layout swap pays one on the
hot path unless it was warmed), so this module makes them a first-class
signal:

- ``livedata_jit_compiles_total{site,trigger}`` — count of cache
  misses per compile site (tick / mesh_tick / publish / step_many);
- ``livedata_jit_compile_seconds{site,trigger}`` — wall time of the
  miss round (trace + XLA compile + first execute, which is what the
  serving path actually stalls for).

``trigger`` says WHY the key missed — the question an operator chasing
a p99 spike actually asks:

- ``new_group``   — first program for this (histogrammer, member set):
  job commits, service start;
- ``layout_swap`` — same group, the layout digest changed (live LUT /
  geometry swap, ADR 0105);
- ``batch_shape`` — same group, the staged wire's signature changed
  (batch-size regime change);
- ``regroup``     — same members, some other key component changed
  (fuse-key tag churn, publisher signature change);
- ``evicted``     — every key dimension identical: the program was
  LRU-evicted and recompiled byte-for-byte (cache pressure, not key
  churn).

Classification compares the missing key against a small per-(site,
group-identity) memory of the last-seen key components; sites feed it
via :meth:`CompileEventRecorder.classify_and_record`. The memory is
bounded like the program caches it mirrors.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from collections.abc import Hashable

from .registry import REGISTRY

__all__ = ["COMPILE_EVENTS", "CompileEventRecorder"]

#: Compile stalls live between ~50 ms (tiny CPU programs) and tens of
#: seconds (large mesh programs); the default latency buckets already
#: span this, so both instruments share them.
_COMPILES_TOTAL = REGISTRY.counter(
    "livedata_jit_compiles_total",
    "jit-cache misses on the serving path, by compile site and trigger",
    labelnames=("site", "trigger"),
)
_COMPILE_SECONDS = REGISTRY.histogram(
    "livedata_jit_compile_seconds",
    "Wall time of jit-cache-miss rounds (trace + compile + first "
    "execute), by compile site and trigger",
    labelnames=("site", "trigger"),
)


class CompileEventRecorder:
    """Classifies and records compile events for every jit-cache site."""

    #: Group identities remembered for trigger classification; matches
    #: the program-cache bounds (TickCombiner max_programs=16).
    _MEMORY_MAX = 64

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (site, group identity) -> last-seen (layout digest, staged
        # signature, residual key)
        self._memory: OrderedDict[tuple, tuple] = OrderedDict()

    def record(self, site: str, trigger: str, seconds: float) -> None:
        """Record one already-classified compile event."""
        _COMPILES_TOTAL.inc(site=site, trigger=trigger)
        _COMPILE_SECONDS.observe(seconds, site=site, trigger=trigger)

    def classify(
        self,
        site: str,
        group: Hashable,
        *,
        layout_digest: Hashable = None,
        staged_sig: Hashable = None,
        residual: Hashable = None,
    ) -> str:
        """Name the trigger for a cache miss on ``group`` at ``site``
        and update the memory. ``group`` identifies WHO is compiling
        (histogrammer id + member set); the keyword components are the
        key dimensions that can churn (see module docstring)."""
        key = (site, group)
        seen = (layout_digest, staged_sig, residual)
        with self._lock:
            prev = self._memory.get(key)
            self._memory[key] = seen
            self._memory.move_to_end(key)
            while len(self._memory) > self._MEMORY_MAX:
                self._memory.popitem(last=False)
        if prev is None:
            return "new_group"
        prev_digest, prev_sig, prev_residual = prev
        if layout_digest != prev_digest:
            return "layout_swap"
        if staged_sig != prev_sig:
            return "batch_shape"
        if residual != prev_residual:
            return "regroup"
        # Every key dimension identical yet the cache missed: the
        # program was LRU-evicted and recompiled byte-for-byte — cache
        # pressure, a different problem than key churn.
        return "evicted"

    def classify_and_record(
        self,
        site: str,
        group: Hashable,
        seconds: float,
        *,
        layout_digest: Hashable = None,
        staged_sig: Hashable = None,
        residual: Hashable = None,
    ) -> str:
        trigger = self.classify(
            site,
            group,
            layout_digest=layout_digest,
            staged_sig=staged_sig,
            residual=residual,
        )
        self.record(site, trigger, seconds)
        return trigger

    # -- test/bench conveniences -------------------------------------------
    def total(
        self, site: str | None = None, *, trigger: str | None = None
    ) -> float:
        """Total recorded compile events, optionally filtered by site
        and/or trigger — what the bench's 'warmup compiles >= 1, steady
        state 0' guard and the layout_swap-classification asserts read
        (the ONE public read surface over the labeled counter)."""
        return sum(
            value
            for labels, value in _COMPILES_TOTAL.items()
            if (site is None or labels.get("site") == site)
            and (trigger is None or labels.get("trigger") == trigger)
        )


#: Process-wide recorder shared by every combiner/step site.
COMPILE_EVENTS = CompileEventRecorder()

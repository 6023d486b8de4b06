"""Deterministic fault injection for the serving path (ADR 0120).

The containment code claims to survive four fault classes: a
post-donation dispatch failure (``note_state_lost`` + re-seed, ADR
0113/0114/0118), wedged/slow SSE subscribers (bounded queues +
coalesce-to-keyframe, ADR 0117), slow-tick storms (watchdog +
batcher backpressure, ADR 0111/0116), and a consumer restart mid-window
(replay through the normal ingest path, ADR 0118). This module injects
exactly those faults — through hooks the production classes already
carry (``JobManager.set_chaos``, ``IngestPipeline.set_chaos``,
``BroadcastServer.set_chaos``) — behind a **seeded schedule**, so a
chaos run is an ordinary deterministic test: same spec, same seed,
same windows => same faults at the same ticks.

Two scheduling modes, combinable per site:

- ``at``: explicit fire ticks — ``{"tick_dispatch": {5, 17}}`` fails
  the 6th and 18th consultation of that site. Exact, reviewable; what
  the bench scenario and the tests use.
- ``rate``: a per-consultation Bernoulli draw from a per-site
  ``random.Random`` seeded with ``(seed, site)`` — reproducible
  *storms* whose density scales with run length.

Each site keeps its own consultation counter, so determinism holds per
site regardless of interleaving across sites. Counters and draws are
lock-guarded: sites are consulted from worker threads (decode worker,
step worker, subscriber drains).

Every fired injection counts into
``livedata_chaos_injections_total{site}`` — the SLO gate reads it to
prove the chaos actually ran (a green gate over a chaos run that
injected nothing proves nothing).

``ChaosError`` deliberately subclasses ``RuntimeError``: the
containment sites catch ``Exception`` and must treat an injected fault
exactly like a real one — no special-casing, or the drill stops
rehearsing the incident.
"""

from __future__ import annotations

import threading
import time
import zlib
from collections.abc import Mapping
from dataclasses import dataclass, field
from random import Random

from ..telemetry.registry import REGISTRY

__all__ = [
    "CHAOS_INJECTIONS",
    "ChaosError",
    "ChaosSchedule",
    "ChaosSpec",
    "SITES",
]

#: The known injection sites and who consults them:
#:
#: ==================  ====================================================
#: site                consulted by
#: ==================  ====================================================
#: ``tick_dispatch``   JobManager._run_tick_programs, AFTER the dispatch —
#:                     a fire is a post-donation failure (state_lost path)
#: ``slow_tick``       JobManager.process_jobs entry — a fire stalls the
#:                     window (slow-tick storm)
#: ``decode_stall``    IngestPipeline decode worker — a fire stalls the
#:                     decode stage (pipeline backpressure)
#: ``subscriber_stall``  Subscription.next_blob_meta — a fire stalls that
#:                     consumer's dequeue (slow/wedged SSE reader)
#: ``consumer_restart``  harness/load.py's drive loop — a fire pauses
#:                     ingest for ``restart_gap_windows`` (the consume
#:                     thread died and came back; accumulation must show
#:                     a gap, never a reset)
#: ``relay_upstream_drop``  fleet/relay.py's pump/worker loop — a fire
#:                     drops the relay's upstream subscription(s) so it
#:                     must reconnect and resync (ADR 0121); downstream
#:                     subscribers must see at most one resync keyframe
#:                     per stream and NO unsignaled reset
#: ==================  ====================================================
SITES = (
    "tick_dispatch",
    "slow_tick",
    "decode_stall",
    "subscriber_stall",
    "consumer_restart",
    "relay_upstream_drop",
)

CHAOS_INJECTIONS = REGISTRY.counter(
    "livedata_chaos_injections",
    "Faults fired by the chaos schedule (harness/chaos.py), by site",
    labelnames=("site",),
)


class ChaosError(RuntimeError):
    """An injected fault. Containment must treat it like any real
    failure (it arrives through the same ``except Exception`` paths)."""


@dataclass(frozen=True)
class ChaosSpec:
    """Declarative schedule: which sites fire when (see module docs).

    ``delay_s`` parameterizes the stall sites (how long a fired stall
    sleeps); raise-sites ignore it. Frozen so a spec can be embedded in
    a bench line / test id and re-run verbatim.
    """

    seed: int = 0
    #: site -> explicit consultation indices (0-based) that fire.
    at: Mapping[str, frozenset[int]] = field(default_factory=dict)
    #: site -> per-consultation fire probability in [0, 1].
    rate: Mapping[str, float] = field(default_factory=dict)
    #: site -> stall duration for delay sites (seconds).
    delay_s: Mapping[str, float] = field(default_factory=dict)
    #: windows of ingest silence per fired ``consumer_restart``.
    restart_gap_windows: int = 3

    def with_site(self, site: str, ticks) -> "ChaosSpec":
        """A copy with explicit fire ticks added for ``site``."""
        merged = dict(self.at)
        merged[site] = frozenset(ticks)
        return ChaosSpec(
            seed=self.seed,
            at=merged,
            rate=dict(self.rate),
            delay_s=dict(self.delay_s),
            restart_gap_windows=self.restart_gap_windows,
        )


class ChaosSchedule:
    """The live consultable form of a :class:`ChaosSpec`."""

    def __init__(self, spec: ChaosSpec | None = None, **kwargs) -> None:
        self.spec = spec if spec is not None else ChaosSpec(**kwargs)
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}
        self._fired: dict[str, int] = {}
        # Per-site RNG streams: (seed, site) keyed so adding a site to
        # the spec never shifts another site's draw sequence.
        self._rngs = {
            site: Random((self.spec.seed << 32) ^ zlib.crc32(site.encode()))
            for site in set(self.spec.rate)
        }

    # -- consultation -------------------------------------------------------
    def fires(self, site: str) -> bool:
        """Advance ``site``'s consultation counter; True when this
        consultation is scheduled to fault."""
        with self._lock:
            tick = self._counts.get(site, 0)
            self._counts[site] = tick + 1
            fired = tick in self.spec.at.get(site, ())
            rng = self._rngs.get(site)
            if not fired and rng is not None:
                fired = rng.random() < self.spec.rate.get(site, 0.0)
            if fired:
                self._fired[site] = self._fired.get(site, 0) + 1
        if fired:
            CHAOS_INJECTIONS.labels(site=site).inc()
        return fired

    def check(self, site: str) -> None:
        """Raise :class:`ChaosError` when ``site`` fires (raise-sites:
        ``tick_dispatch``)."""
        if self.fires(site):
            raise ChaosError(f"injected fault at {site}")

    def maybe_delay(self, site: str) -> None:
        """Sleep the site's configured stall when it fires (delay
        sites). Callers hold NO locks here by contract — the stall
        models slow work, not a lock convoy (graftlint JGL023)."""
        if self.fires(site):
            time.sleep(self.spec.delay_s.get(site, 0.05))

    # -- reporting ----------------------------------------------------------
    def injected(self) -> dict[str, int]:
        """Faults fired so far, by site (the harness report embeds it;
        the SLO gate cross-checks the registry counter)."""
        with self._lock:
            return dict(self._fired)

    def consultations(self) -> dict[str, int]:
        with self._lock:
            return dict(self._counts)

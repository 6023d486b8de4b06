"""Durability plane (ADR 0118): churn made invisible, state survivable.

Three pieces, composable and individually optional:

- :mod:`.warmup` — ``CompileWarmupService``: a background thread that
  AOT-lowers and compiles tick programs at job-commit (and regroup)
  time, seeding the :class:`~..ops.tick.TickCombiner` program LRU so
  the first post-commit tick is a cache hit — commit-time compile count
  on the hot path is 0 (measured by the ADR 0116 instrument) and
  first-tick latency equals steady state. (The persistent compilation
  cache that lets restarts skip XLA is placed by every runner at start,
  utils/runtime.py, whether or not warm-up is on.)
- :mod:`.checkpoint` — ``CheckpointPlane``: periodic, epoch-tagged
  device→host snapshots of rolling-histogram state plus per-stream
  Kafka offset bookmarks, written atomically under a manifest
  (write-tmp/fsync/rename — the JGL020 discipline), at a fixed
  interval.
- :mod:`.replay` — restore the newest consistent manifest on restart
  (stale manifests from before the last run-boundary reset are
  rejected), seek consumers to the bookmarks, and replay the gap
  through the normal ingest path. The ADR 0117 ``state_epoch``/delta
  discipline means restored jobs resume SSE subscribers with one
  keyframe — viewers see a gap, not a reset.
"""

from .checkpoint import CheckpointPlane
from .replay import load_latest_manifest, start_offsets
from .warmup import CompileWarmupService, WarmupRequest

__all__ = [
    "CheckpointPlane",
    "CompileWarmupService",
    "WarmupRequest",
    "load_latest_manifest",
    "start_offsets",
]

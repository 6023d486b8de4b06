"""CheckpointPlane: periodic, epoch-tagged state + offset checkpoints.

ADR 0107's :class:`~..core.state_snapshot.SnapshotStore` dumps device
state only at run boundaries and graceful shutdown — a crash (or device
loss) between boundaries still loses the whole accumulated run view,
and the restart pins consumers at the high watermark, so the gap is
gone too. This plane generalizes it into the periodic channel
(ADR 0118):

- **What a checkpoint is.** One manifest (JSON) naming, for every
  non-stopped job: the workflow id, source name, ADR 0107 fingerprint,
  ``state_epoch`` and generation start, and the job's state arrays in a
  sibling ``.npz`` — plus the per-topic Kafka offset **bookmarks** the
  ingest had fully processed when the states were fetched. Restore +
  seek-to-bookmark + normal consumption then replays the gap exactly
  once (:mod:`.replay`).
- **Atomicity.** Every file follows write-tmp/fsync/rename (graftlint
  JGL020), state files before the manifest, directory fsync after each
  rename: a crash at ANY point leaves the previous manifest (and the
  files it references) fully consistent — a reader never sees a torn
  or half-referenced checkpoint. The newest ``keep`` generations are
  retained; older manifests and unreferenced state files are garbage
  collected only after a successful write.
- **Cadence.** ``due()`` answers at the configured interval.
- **Staleness.** Run-boundary resets bump a persistent ``reset_seq``
  marker (``note_reset``, written atomically). A manifest written
  BEFORE the most recent reset is rejected by :func:`.replay.
  load_latest_manifest` — preserving ADR 0107's guarantee that old-run
  and new-run data can never blend, even when the process dies between
  the reset and the next checkpoint.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
import threading
import time
from pathlib import Path

import numpy as np

from ..telemetry.registry import REGISTRY

__all__ = ["CheckpointPlane", "MANIFEST_RE", "RESET_MARKER"]

logger = logging.getLogger(__name__)

MANIFEST_RE = re.compile(r"^manifest-(\d{8})\.json$")
RESET_MARKER = "reset.marker"

_CHECKPOINTS_TOTAL = REGISTRY.counter(
    "livedata_durability_checkpoints_total",
    "Checkpoints written (manifest + state files, atomically)",
)
_RESTORES_TOTAL = REGISTRY.counter(
    "livedata_durability_restores_total",
    "Job states restored from a checkpoint manifest, by reason "
    "(schedule = restart adoption, state_lost = mid-run donation-loss "
    "recovery)",
    labelnames=("reason",),
)


def fsync_dir(directory: Path) -> None:
    """fsync the directory so a rename is durable, not just ordered.
    Best-effort on filesystems without directory fds."""
    try:
        fd = os.open(directory, os.O_RDONLY)
    except OSError:  # pragma: no cover - exotic fs
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


# graft: protocol=checkpoint (ADR 0124: every fsync/os.replace below is
# a crash candidate in the model-checked write/GC protocol)
def atomic_write(path: Path, payload: bytes) -> None:
    """The JGL020 discipline: write a tmp sibling, flush, fsync,
    rename over the final name, fsync the directory."""
    tmp = path.with_name(path.name + ".tmp")
    with open(tmp, "wb") as fh:
        fh.write(payload)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)
    fsync_dir(path.parent)


def _slug(text: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "_", text)


class CheckpointPlane:
    """Periodic checkpoint writer + restore source for one directory."""

    def __init__(
        self,
        directory,
        *,
        interval_s: float = 30.0,
        keep: int = 2,
    ) -> None:
        self._dir = Path(directory)
        self._dir.mkdir(parents=True, exist_ok=True)
        self._interval_s = max(0.0, float(interval_s))
        self._keep = max(1, int(keep))
        self._lock = threading.Lock()
        self._last_wall: float | None = None
        self._last_bytes = 0
        self._epoch = self._newest_epoch()
        # The restore view over the newest consistent manifest, loaded
        # lazily (and once) — a restarted service restores many jobs
        # from one manifest read.
        self._restore_manifest: dict | None = None
        self._restore_loaded = False
        # Keyed per directory: a rebuilt plane (tests, restarts)
        # replaces its predecessor's collector instead of stacking.
        self._telemetry_key = f"durability:{self._dir}"
        REGISTRY.register_collector(self._telemetry_key, self._families)

    @property
    def directory(self) -> Path:
        return self._dir

    # -- cadence -----------------------------------------------------------
    def due(self, now: float | None = None) -> bool:
        """True when the next checkpoint should be taken: none taken
        yet, or the interval has passed since the last."""
        now = time.monotonic() if now is None else now
        with self._lock:
            last = self._last_wall
        return last is None or now - last >= self._interval_s

    # -- write side --------------------------------------------------------
    def _newest_epoch(self) -> int:
        epochs = [
            int(m.group(1))
            for p in self._dir.glob("manifest-*.json")
            if (m := MANIFEST_RE.match(p.name))
        ]
        return max(epochs, default=0)

    def note_reset(self, reset_seq: int) -> None:
        """Persist the run-boundary reset marker (atomic): manifests
        written before this sequence are stale from here on and will be
        rejected by replay — old-run state must never blend into the
        new run, even across a crash in the reset→checkpoint window."""
        current = self.reset_marker()
        if reset_seq <= current:
            return
        atomic_write(
            self._dir / RESET_MARKER,
            json.dumps({"reset_seq": int(reset_seq)}).encode(),
        )
        with self._lock:
            # The cached restore view predates the reset: a state_lost
            # re-seed between this reset and the next checkpoint must
            # NOT hand back pre-reset old-run arrays. Invalidate; the
            # next restore reloads through load_latest_manifest, whose
            # marker check rejects the stale generation.
            self._restore_manifest = None
            self._restore_loaded = False

    def reset_marker(self) -> int:
        try:
            return int(
                json.loads((self._dir / RESET_MARKER).read_bytes())[
                    "reset_seq"
                ]
            )
        except FileNotFoundError:
            return 0
        except Exception:
            logger.exception("unreadable reset marker; treating as 0")
            return 0

    def checkpoint(
        self,
        entries: list[dict],
        *,
        offsets: dict[str, int] | None = None,
        reset_seq: int = 0,
    ) -> Path | None:
        """Write one checkpoint generation atomically.

        ``entries`` come from ``JobManager.checkpoint_snapshot()``: each
        carries ``workflow_id``/``source_name``/``fingerprint``/
        ``state_epoch``/``generation_start_ns`` plus the host ``arrays``
        dict. State files land (fsynced) BEFORE the manifest that names
        them, so a crash anywhere in between leaves the previous
        generation intact. Returns the manifest path, or None when
        there was nothing to write (no entries — an idle service does
        not churn empty generations).
        """
        if not entries:
            return None
        # Serialization + fsync-bound writes run OUTSIDE the lock —
        # there is one writer by design (the service thread at
        # quiescent boundaries), and the lock otherwise only guards
        # the scalar telemetry/restore view, which a concurrent
        # /metrics scrape must not have to wait a whole fsync for.
        with self._lock:
            epoch = self._epoch + 1
        import io

        jobs = []
        total_bytes = 0
        for entry in entries:
            pair = hashlib.sha256(
                f"{entry['workflow_id']}\x00{entry['source_name']}"
                f"\x00{entry.get('job_number', '')}".encode()
            ).hexdigest()[:8]
            name = (
                f"state-{epoch:08d}-"
                f"{_slug(str(entry['workflow_id']))[:40]}-{pair}.npz"
            )
            buf = io.BytesIO()
            np.savez(buf, **entry["arrays"])
            payload = buf.getvalue()
            atomic_write(self._dir / name, payload)
            total_bytes += len(payload)
            jobs.append(
                {
                    "workflow_id": str(entry["workflow_id"]),
                    "source_name": entry["source_name"],
                    "job_number": str(entry.get("job_number", "")),
                    "fingerprint": entry["fingerprint"],
                    "state_epoch": int(entry["state_epoch"]),
                    "generation_start_ns": entry.get(
                        "generation_start_ns"
                    ),
                    "file": name,
                    "nbytes": len(payload),
                    "sha256": hashlib.sha256(payload).hexdigest(),
                }
            )
        manifest = {
            "epoch": epoch,
            "reset_seq": int(reset_seq),
            "created_at": time.time(),
            "offsets": dict(offsets or {}),
            "jobs": jobs,
        }
        path = self._dir / f"manifest-{epoch:08d}.json"
        atomic_write(
            path, json.dumps(manifest, sort_keys=True).encode()
        )
        with self._lock:
            self._epoch = epoch
            self._last_wall = time.monotonic()
            self._last_bytes = total_bytes
            # The restore view follows the write: a state-loss re-seed
            # later this process must read THIS generation, not a
            # stale (possibly empty) view cached at schedule time.
            self._restore_manifest = manifest
            self._restore_loaded = True
            self._gc_locked()
        _CHECKPOINTS_TOTAL.inc()
        logger.info(
            "checkpoint %d: %d job state(s), %d B, offsets for %d "
            "topic(s)",
            epoch,
            len(jobs),
            total_bytes,
            len(manifest["offsets"]),
        )
        return path

    def _gc_locked(self) -> None:
        """Drop generations beyond ``keep`` and state files nothing
        kept references — only ever AFTER a successful manifest write,
        so the newest consistent generation is always whole."""
        manifests = sorted(
            (
                (int(m.group(1)), p)
                for p in self._dir.glob("manifest-*.json")
                if (m := MANIFEST_RE.match(p.name))
            ),
            reverse=True,
        )
        kept, referenced = [], set()
        for epoch, path in manifests:
            if len(kept) < self._keep:
                try:
                    doc = json.loads(path.read_bytes())
                    referenced.update(j["file"] for j in doc["jobs"])
                    kept.append(epoch)
                    continue
                except Exception:
                    logger.warning("dropping unreadable manifest %s", path)
            try:
                path.unlink()
            except OSError:  # pragma: no cover - concurrent cleanup
                pass
        for state in self._dir.glob("state-*.npz"):
            if state.name not in referenced:
                try:
                    state.unlink()
                except OSError:  # pragma: no cover
                    pass

    # -- restore side ------------------------------------------------------
    def _load_restore_manifest(self) -> dict | None:
        with self._lock:
            if self._restore_loaded:
                return self._restore_manifest
        from .replay import load_latest_manifest

        manifest = load_latest_manifest(self._dir)
        with self._lock:
            if not self._restore_loaded:
                self._restore_manifest = manifest
                self._restore_loaded = True
            return self._restore_manifest

    def bookmarks(self) -> dict[str, int]:
        """The newest consistent manifest's per-topic offsets (empty
        when there is nothing to restore)."""
        manifest = self._load_restore_manifest()
        return dict(manifest["offsets"]) if manifest else {}

    def restore_job(self, job, *, adopt_meta: bool = True,
                    reason: str = "schedule") -> bool:
        """Restore ``job``'s workflow state from the newest consistent
        manifest. Fingerprint-gated exactly like ADR 0107: a changed
        geometry/binning refuses the arrays rather than adopting counts
        whose bins mean something else. ``adopt_meta`` additionally
        carries the checkpointed ``state_epoch`` and generation start
        onto the job (restart adoption: output time coords and the
        serving tier's epoch discipline continue seamlessly); the
        mid-run ``state_lost`` recovery path passes False — its epoch
        already bumped, and regressing it would let a delta stream
        splice across the rebuild.

        Unlike ADR 0107's one-shot files, a manifest is never consumed:
        the staleness gates are the reset marker and newest-wins, and a
        crash-looping service must keep restoring the same (still
        newest) checkpoint.
        """
        manifest = self._load_restore_manifest()
        if manifest is None or job.workflow is None:
            return False
        if manifest.get("reset_seq", 0) < self.reset_marker():
            # Belt over the note_reset invalidation above: whatever
            # view is cached, a manifest from before the most recent
            # run boundary never restores.
            return False
        wf = job.workflow
        if not (
            hasattr(wf, "state_fingerprint")
            and hasattr(wf, "restore_state")
        ):
            return False
        try:
            fingerprint = wf.state_fingerprint()
        except Exception:
            logger.exception("fingerprint failed for %s", job.job_id)
            return False
        # Exact job-identity match, INCLUDING the job number: crash
        # restarts re-schedule the same JobIds (ADR 0008 adoption), so
        # each job matches only its own entry — two concurrent
        # identical jobs keep distinct checkpoints, and a NEW job
        # committed later (fresh uuid) can never clone a predecessor's
        # accumulation. A restart that regenerates job numbers falls
        # through to the ADR 0107 snapshot-store channel, whose
        # configuration-keyed one-shot semantics cover that case.
        entry = next(
            (
                j
                for j in manifest["jobs"]
                if j["workflow_id"] == str(job.workflow_id)
                and j["source_name"] == job.job_id.source_name
                and j.get("job_number") == str(job.job_id.job_number)
            ),
            None,
        )
        if entry is None:
            return False
        if entry["fingerprint"] != fingerprint:
            logger.info(
                "checkpoint for %s ignored: fingerprint mismatch",
                job.job_id,
            )
            return False
        path = self._dir / entry["file"]
        try:
            payload = path.read_bytes()
            if hashlib.sha256(payload).hexdigest() != entry["sha256"]:
                logger.warning("checkpoint state %s corrupt; skipped", path)
                return False
            import io

            with np.load(io.BytesIO(payload)) as archive:
                arrays = {k: archive[k] for k in archive.files}
            if not wf.restore_state(arrays):
                return False
        except Exception:
            logger.exception("checkpoint restore failed for %s", job.job_id)
            return False
        if adopt_meta:
            job.adopt_checkpoint(
                state_epoch=entry["state_epoch"],
                generation_start_ns=entry.get("generation_start_ns"),
            )
        _RESTORES_TOTAL.inc(reason=reason)
        logger.info(
            "restored %s from checkpoint epoch %d (%s)",
            job.job_id,
            manifest["epoch"],
            reason,
        )
        return True

    # -- telemetry ---------------------------------------------------------
    def _families(self):
        from ..telemetry.registry import MetricFamily, Sample

        with self._lock:
            last_wall = self._last_wall
            last_bytes = self._last_bytes
            epoch = self._epoch
        age = MetricFamily(
            "livedata_durability_snapshot_age_seconds",
            "gauge",
            "Seconds since the last checkpoint this process wrote "
            "(-1 = none yet this process)",
        )
        age.samples = [
            Sample(
                "",
                (),
                -1.0
                if last_wall is None
                else time.monotonic() - last_wall,
            )
        ]
        size = MetricFamily(
            "livedata_durability_snapshot_bytes",
            "gauge",
            "State bytes in the last checkpoint generation",
        )
        size.samples = [Sample("", (), float(last_bytes))]
        gen = MetricFamily(
            "livedata_durability_checkpoint_epoch",
            "gauge",
            "Newest checkpoint generation in the directory",
        )
        gen.samples = [Sample("", (), float(epoch))]
        return [age, size, gen]

    def close(self) -> None:
        REGISTRY.unregister_collector(self._telemetry_key, self._families)

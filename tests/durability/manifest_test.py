"""CheckpointPlane manifest semantics: atomicity under injected
crashes, newest-consistent selection, run-boundary staleness, GC."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from esslivedata_tpu.durability import CheckpointPlane, load_latest_manifest
from esslivedata_tpu.durability.checkpoint import RESET_MARKER


def entries(tag: float, n: int = 2) -> list[dict]:
    return [
        {
            "workflow_id": f"wf{i}",
            "source_name": f"src{i}",
            "fingerprint": f"fp{i}",
            "state_epoch": 0,
            "generation_start_ns": 123,
            "arrays": {"folded": np.full(8, tag), "window": np.zeros(8)},
        }
        for i in range(n)
    ]


class TestAtomicity:
    def test_crash_between_write_and_rename_keeps_previous(
        self, tmp_path, monkeypatch
    ):
        plane = CheckpointPlane(tmp_path, interval_s=0)
        plane.checkpoint(entries(1.0), offsets={"t": 10}, reset_seq=0)
        assert load_latest_manifest(tmp_path)["offsets"] == {"t": 10}

        # Injected crash: the manifest's tmp file is fully written and
        # fsynced, the rename never happens. The previous generation
        # must stay the restorable one, and the torn tmp is inert.
        real_replace = os.replace

        def crash_on_manifest(src, dst):
            if "manifest-" in str(dst):
                raise OSError("simulated crash before rename")
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", crash_on_manifest)
        with pytest.raises(OSError):
            plane.checkpoint(entries(2.0), offsets={"t": 20}, reset_seq=0)
        monkeypatch.undo()
        doc = load_latest_manifest(tmp_path)
        assert doc["epoch"] == 1 and doc["offsets"] == {"t": 10}
        # A fresh plane over the same directory (the restarted process)
        # resumes the epoch sequence past the torn attempt's files.
        plane2 = CheckpointPlane(tmp_path, interval_s=0)
        plane2.checkpoint(entries(3.0), offsets={"t": 30}, reset_seq=0)
        assert load_latest_manifest(tmp_path)["offsets"] == {"t": 30}

    def test_crash_during_state_write_keeps_previous(
        self, tmp_path, monkeypatch
    ):
        plane = CheckpointPlane(tmp_path, interval_s=0)
        plane.checkpoint(entries(1.0), offsets={"t": 10}, reset_seq=0)
        real_replace = os.replace

        def crash_on_state(src, dst):
            if "state-00000002" in str(dst):
                raise OSError("simulated crash mid state write")
            return real_replace(src, dst)

        monkeypatch.setattr(os, "replace", crash_on_state)
        with pytest.raises(OSError):
            plane.checkpoint(entries(2.0), offsets={"t": 20}, reset_seq=0)
        monkeypatch.undo()
        assert load_latest_manifest(tmp_path)["offsets"] == {"t": 10}

    def test_missing_state_file_falls_back_to_older_generation(
        self, tmp_path
    ):
        plane = CheckpointPlane(tmp_path, interval_s=0, keep=3)
        plane.checkpoint(entries(1.0), offsets={"t": 10}, reset_seq=0)
        plane.checkpoint(entries(2.0), offsets={"t": 20}, reset_seq=0)
        victim = json.loads(
            (tmp_path / "manifest-00000002.json").read_bytes()
        )["jobs"][0]["file"]
        (tmp_path / victim).unlink()
        assert load_latest_manifest(tmp_path)["offsets"] == {"t": 10}

    def test_corrupt_state_payload_falls_back(self, tmp_path):
        plane = CheckpointPlane(tmp_path, interval_s=0, keep=3)
        plane.checkpoint(entries(1.0), offsets={"t": 10}, reset_seq=0)
        plane.checkpoint(entries(2.0), offsets={"t": 20}, reset_seq=0)
        victim = json.loads(
            (tmp_path / "manifest-00000002.json").read_bytes()
        )["jobs"][0]["file"]
        (tmp_path / victim).write_bytes(b"rotted")
        assert load_latest_manifest(tmp_path)["offsets"] == {"t": 10}

    def test_empty_entries_write_nothing(self, tmp_path):
        plane = CheckpointPlane(tmp_path, interval_s=0)
        assert plane.checkpoint([], offsets={"t": 1}, reset_seq=0) is None
        assert load_latest_manifest(tmp_path) is None


class TestStaleness:
    def test_reset_marker_rejects_pre_reset_manifest(self, tmp_path):
        """ADR 0107's no-old-run-blending guarantee across a crash in
        the reset -> next-checkpoint window: a manifest written before
        the run boundary must never restore."""
        plane = CheckpointPlane(tmp_path, interval_s=0)
        plane.checkpoint(entries(1.0), offsets={"t": 10}, reset_seq=0)
        plane.note_reset(1)  # run boundary fired, process dies here
        assert load_latest_manifest(tmp_path) is None

    def test_post_reset_checkpoint_restorable(self, tmp_path):
        plane = CheckpointPlane(tmp_path, interval_s=0)
        plane.checkpoint(entries(1.0), offsets={"t": 10}, reset_seq=0)
        plane.note_reset(1)
        plane.checkpoint(entries(2.0), offsets={"t": 20}, reset_seq=1)
        doc = load_latest_manifest(tmp_path)
        assert doc["offsets"] == {"t": 20} and doc["reset_seq"] == 1

    def test_restarted_manager_seeds_reset_seq_from_marker(self, tmp_path):
        """A process restarting AFTER a run-boundary reset must stamp
        new manifests at (or past) the persisted marker — otherwise
        every post-restart checkpoint would carry reset_seq 0 < marker
        and be rejected as stale forever, silently disabling the whole
        plane from the second restart on."""
        from durability_helpers import (
            make_manager,
            make_windows,
            run_window,
        )

        plane = CheckpointPlane(tmp_path, interval_s=0)
        plane.note_reset(2)  # run 1 saw two boundaries, then died
        restarted = make_manager(
            durability=plane, detector_jobs=1, monitor_jobs=0
        )
        assert restarted.reset_seq == 2
        windows = make_windows(2)
        run_window(restarted, windows, 0)
        plane.checkpoint(
            restarted.checkpoint_snapshot(),
            offsets={"t": 1},
            reset_seq=restarted.reset_seq,
        )
        assert load_latest_manifest(tmp_path) is not None
        # And the late-attach path (set_durability) seeds too.
        late = make_manager(detector_jobs=1, monitor_jobs=0)
        late.set_durability(plane)
        assert late.reset_seq == 2
        plane.close()

    def test_marker_is_monotone(self, tmp_path):
        plane = CheckpointPlane(tmp_path, interval_s=0)
        plane.note_reset(3)
        plane.note_reset(1)  # late/duplicate notification cannot regress
        assert plane.reset_marker() == 3
        assert json.loads(
            (tmp_path / RESET_MARKER).read_bytes()
        ) == {"reset_seq": 3}


class TestRetention:
    def test_gc_keeps_newest_generations_and_their_states(self, tmp_path):
        plane = CheckpointPlane(tmp_path, interval_s=0, keep=2)
        for gen in range(4):
            plane.checkpoint(
                entries(float(gen)), offsets={"t": gen}, reset_seq=0
            )
        manifests = sorted(p.name for p in tmp_path.glob("manifest-*.json"))
        assert manifests == [
            "manifest-00000003.json",
            "manifest-00000004.json",
        ]
        referenced = {
            job["file"]
            for name in manifests
            for job in json.loads((tmp_path / name).read_bytes())["jobs"]
        }
        assert {p.name for p in tmp_path.glob("state-*.npz")} == referenced

    def test_due_respects_interval(self, tmp_path):
        plane = CheckpointPlane(tmp_path, interval_s=10.0)
        assert plane.due()  # nothing written yet
        plane.checkpoint(entries(1.0), offsets={}, reset_seq=0)
        import time

        now = time.monotonic()
        assert not plane.due(now + 5)
        assert plane.due(now + 10)
        assert plane.due(now + 11)

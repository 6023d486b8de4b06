"""Start-up helpers every device-using process calls (utils/runtime.py)."""

import logging
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from esslivedata_tpu.utils import runtime

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the
    cache settings are process-global and must not leak into the suite."""
    calls = {}
    monkeypatch.setattr(jax.config, "update", calls.__setitem__)
    return calls


class TestPersistentCompilationCache:
    def test_env_places_the_cache_and_code_sets_no_directory(
        self, monkeypatch, config_updates, tmp_path
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert runtime.enable_persistent_compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in config_updates
        # The two thresholds still make every entry cacheable.
        assert config_updates == {
            "jax_persistent_cache_min_entry_size_bytes": -1,
            "jax_persistent_cache_min_compile_time_secs": 0.0,
        }

    @pytest.mark.parametrize("unset", [None, ""])
    def test_without_env_one_fixed_path_inside_the_checkout(
        self, monkeypatch, config_updates, unset
    ):
        if unset is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", unset)
        expected = str(REPO / ".jax_cache")
        assert runtime.enable_persistent_compilation_cache() == expected
        assert config_updates["jax_compilation_cache_dir"] == expected


class TestDeviceIdentity:
    def test_names_the_pinned_cpu(self, caplog):
        with caplog.at_level(logging.INFO, logger=runtime.__name__):
            identity = runtime.log_device_identity()
        assert identity == {
            "platform": "cpu",
            "device_kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }
        # tests/conftest.py ASKED for the CPU: the line is not a warning.
        [record] = caplog.records
        assert record.levelno == logging.INFO
        assert "platform=cpu" in record.getMessage()

    def test_unasked_cpu_is_a_warning(self, monkeypatch, caplog):
        monkeypatch.setattr(
            type(jax.config), "jax_platforms", property(lambda _self: None)
        )
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        with caplog.at_level(logging.INFO, logger=runtime.__name__):
            runtime.log_device_identity()
        [record] = caplog.records
        assert record.levelno == logging.WARNING
        assert "without being asked" in record.getMessage()


class _FakeLibc:
    """Records ``mallopt`` calls instead of changing this process's
    allocator; answers ``refuse`` for the parameter it names."""

    def __init__(self, refuse=None):
        self.calls = []
        self._refuse = refuse

    def mallopt(self, param, value):
        self.calls.append((param, value))
        return 0 if param == self._refuse else 1


_MALLOC_ENV = ("GLIBC_TUNABLES", *runtime._MALLOC_ENV)

# A thread makes and frees, window after window, what a camera's
# publish does: two fetched 16 MiB images, two encoded messages of their
# size, a flat wire. Printed: the page faults of the last windows.
_PROBE = """
import resource, sys, threading
import numpy as np
from esslivedata_tpu.utils import runtime
if sys.argv[1] == "keep":
    assert runtime.keep_freed_host_memory()
kept = np.empty((1 << 24) + (1 << 20), np.uint8)
faults = []
def windows():
    for window in range(12):
        before = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
        images = [np.full(1 << 22, window, np.uint32) for _ in range(2)]
        messages = []
        for image in images:
            kept[: image.nbytes] = image.view(np.uint8)
            messages.append(kept.tobytes())
        flat = np.full(3 << 20, window, np.int32).copy()
        del images, messages, flat
        faults.append(resource.getrusage(resource.RUSAGE_SELF).ru_minflt - before)
thread = threading.Thread(target=windows)
thread.start()
thread.join()
print(max(faults[4:]))
"""


class TestKeepFreedHostMemory:
    @pytest.fixture(autouse=True)
    def _untuned(self, monkeypatch):
        for name in _MALLOC_ENV:
            monkeypatch.delenv(name, raising=False)

    def test_sets_the_three_thresholds_on_glibc(self, monkeypatch):
        libc = _FakeLibc()
        monkeypatch.setattr(runtime, "_glibc", lambda: libc)
        assert runtime.keep_freed_host_memory() is True
        assert libc.calls == [(-3, 32 << 20), (-1, 1 << 30), (-2, 64 << 20)]

    @pytest.mark.parametrize(
        ("name", "value"),
        [
            ("MALLOC_TRIM_THRESHOLD_", "131072"),
            ("MALLOC_MMAP_THRESHOLD_", "131072"),
            ("MALLOC_TOP_PAD_", "0"),
            ("GLIBC_TUNABLES", "glibc.malloc.trim_threshold=131072"),
        ],
    )
    def test_the_environments_tuning_stands(self, monkeypatch, name, value):
        libc = _FakeLibc()
        monkeypatch.setattr(runtime, "_glibc", lambda: libc)
        monkeypatch.setenv(name, value)
        assert runtime.keep_freed_host_memory() is False
        assert libc.calls == []

    def test_another_c_library_is_left_alone(self, monkeypatch):
        monkeypatch.setattr(runtime, "_glibc", lambda: None)
        assert runtime.keep_freed_host_memory() is False

    def test_a_refused_setting_is_reported(self, monkeypatch, caplog):
        libc = _FakeLibc(refuse=-1)
        monkeypatch.setattr(runtime, "_glibc", lambda: libc)
        with caplog.at_level(logging.WARNING, logger=runtime.__name__):
            assert runtime.keep_freed_host_memory() is False
        assert "refused" in caplog.text

    @pytest.mark.skipif(runtime._glibc() is None, reason="glibc's malloc")
    def test_a_windows_arrays_are_faulted_in_once(self):
        """In a process of its own: glibc's defaults fault a window's
        large arrays in again every window; kept, they are not."""

        def faults(mode):
            out = subprocess.run(
                [sys.executable, "-c", _PROBE, mode],
                capture_output=True, text=True, check=True,
                env={**os.environ, "PYTHONPATH": str(REPO / "src")},
            )
            return int(out.stdout.split()[-1])

        pages = (1 << 24) // 4096  # of one 16 MiB image
        assert faults("keep") < pages // 10
        assert faults("default") > pages

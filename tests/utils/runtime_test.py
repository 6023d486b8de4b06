"""Start-up helpers every device-using process calls (utils/runtime.py)."""

import logging
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

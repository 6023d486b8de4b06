"""Fixtures of the benchmark's tests."""

from __future__ import annotations

from pathlib import Path

import pytest
from bench_support import overlay_fixture


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root")
    overlay_fixture(root)
    return root

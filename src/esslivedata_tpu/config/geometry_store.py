"""Geometry-artifact registry: dated NeXus files, cached, date-resolved.

Mirrors the reference's geometry pipeline
(preprocessors/detector_data.py:66-127): every instrument has one or more
geometry files named ``geometry-<instrument>-<YYYY-MM-DD>.nxs``, the date
being the start of the file's validity window; the file applying at a
given date is the newest one whose date is not after it. Files land in a
cache directory, overridable with ``LIVEDATA_DATA_DIR`` (an operator can
drop a hand-built artifact there and it wins over the registry).

Where the reference *downloads* artifacts with pooch, this environment has
no egress, so a cache miss *synthesizes* the file from the instrument's
declarative NeXus plan (``nexus_plans.py``). The consumer contract is
byte-for-byte the same — a real ESS file copied into the cache is used
as-is.
"""

from __future__ import annotations

import datetime as _dt
import logging
import os
import re
from pathlib import Path

import numpy as np

__all__ = [
    "GEOMETRY_REGISTRY",
    "data_dir",
    "geometry_filename",
    "geometry_path",
    "load_detector_geometry",
    "load_logical_layout",
]

logger = logging.getLogger(__name__)

#: filename -> None (synthesized) or an expected md5 of a pinned real
#: artifact. Multiple dated entries per instrument express validity
#: windows; files are never replaced in place (new date = new file).
GEOMETRY_REGISTRY: dict[str, str | None] = {
    "geometry-loki-2026-01-01.nxs": None,
    # the nine straw-tube banks beside the toy plane: a new date, so that
    # a cache that holds the older file does not answer for them
    "geometry-loki-2026-09-01.nxs": None,
    "geometry-dream-2026-01-01.nxs": None,
    "geometry-bifrost-2026-01-01.nxs": None,
    # 45 triplets of 300 pixels where the older file has 9 banks of
    # 3000: a new date, for the same reason as LOKI's
    "geometry-bifrost-2026-10-01.nxs": None,
    "geometry-estia-2026-01-01.nxs": None,
    "geometry-nmx-2026-01-01.nxs": None,
    "geometry-odin-2026-01-01.nxs": None,
    "geometry-tbl-2026-01-01.nxs": None,
    "geometry-dummy-2026-01-01.nxs": None,
}

def _name_pattern(instrument: str) -> re.Pattern:
    """Exact-match pattern for one instrument's dated artifacts: anchored,
    so 'dummy' never matches an operator-installed 'dummy-hr' file."""
    return re.compile(
        rf"^geometry-{re.escape(instrument)}-(\d{{4}}-\d{{2}}-\d{{2}})\.nxs$"
    )


def data_dir() -> Path:
    """The geometry data directory (LIVEDATA_DATA_DIR or the scratch
    default) — where artifacts are cached, and where operators drop
    hand-built dated files (they join date resolution automatically)."""
    return _cache_dir()


def _cache_dir() -> Path:
    override = os.environ.get("LIVEDATA_DATA_DIR")
    if override:
        return Path(override)
    # Per-user cache (XDG), mode 0o700: a world-scratch default would let
    # another local user pre-plant artifacts the loader silently trusts.
    try:
        xdg = os.environ.get("XDG_CACHE_HOME")
        # Path.home() raises RuntimeError for a UID with no passwd entry
        # (common in containers) — that case takes the fallback too.
        base = Path(xdg) if xdg else Path.home() / ".cache"
        target = base / "esslivedata-tpu" / "geometry"
        target.mkdir(parents=True, exist_ok=True, mode=0o700)
        return target
    except (OSError, RuntimeError):
        import tempfile

        fallback = Path(tempfile.gettempdir()) / "esslivedata-tpu" / "geometry"
        logger.warning(
            "No usable per-user cache; falling back to world scratch %s "
            "— set LIVEDATA_DATA_DIR for a trusted location",
            fallback,
        )
        fallback.mkdir(parents=True, exist_ok=True, mode=0o700)
        return fallback


def geometry_filename(
    instrument: str, date: _dt.date | None = None
) -> str:
    """The registry filename valid at ``date`` (default: today).

    The newest entry whose embedded date is <= ``date`` wins — identical
    date-LUT semantics to the reference's ``get_nexus_geometry_filename``.
    """
    date = date or _dt.date.today()
    # Registry entries plus any dated files an operator dropped into the
    # data directory (scripts/fetch_geometry.py install): both join date
    # resolution, so installing a new artifact needs no code change.
    names = set(GEOMETRY_REGISTRY)
    try:
        names.update(p.name for p in _cache_dir().glob("geometry-*.nxs"))
    except OSError:  # pragma: no cover - unreadable data dir
        pass
    pattern = _name_pattern(instrument)
    candidates: list[tuple[_dt.date, str]] = []
    for name in names:
        m = pattern.match(name)
        if not m:
            continue
        candidates.append((_dt.date.fromisoformat(m.group(1)), name))
    if not candidates:
        raise ValueError(f"No geometry files registered for {instrument!r}")
    candidates.sort()
    valid = [name for d, name in candidates if d <= date]
    if not valid:
        raise ValueError(
            f"No geometry file for {instrument!r} valid at {date} "
            f"(earliest is {candidates[0][0]})"
        )
    return valid[-1]


def geometry_path(
    instrument: str, date: _dt.date | None = None
) -> Path:
    """Resolve (and materialize if needed) the geometry artifact path."""
    name = geometry_filename(instrument, date)
    path = _cache_dir() / name
    if path.exists():
        _verify_pin(path, name)
        return path
    if GEOMETRY_REGISTRY.get(name) is not None:
        # A pinned entry names a specific real artifact; synthesizing a
        # local stand-in under that name would hand the consumer wrong
        # geometry once and then fail the pin check forever after.
        raise ValueError(
            f"Geometry artifact {name} is pinned in the registry but not "
            f"present in {path.parent}; install it with "
            f"scripts/fetch_geometry.py"
        )
    import os as _os
    import tempfile

    from .nexus_plans import plan_for
    from .nexus_synthesis import write_nexus

    logger.info("Synthesizing geometry artifact %s", path)
    # Unique temp file per writer: several services resolving the same
    # missing artifact concurrently must not truncate each other mid-write;
    # whichever finishes last atomically installs a *complete* file.
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp_name = tempfile.mkstemp(
        dir=path.parent, prefix=path.name, suffix=".partial"
    )
    _os.close(fd)
    tmp = Path(tmp_name)
    try:
        write_nexus(plan_for(instrument), tmp)
        tmp.replace(path)
    finally:
        tmp.unlink(missing_ok=True)
    return path


def _verify_pin(path: Path, name: str) -> None:
    """Check a cached file against its registry md5 pin, when one exists.

    Synthesized entries (pin None) and operator-dropped files outside the
    registry are trusted as-is — the pin protects exactly the case where a
    known real artifact could have been swapped in the cache.
    """
    expected = GEOMETRY_REGISTRY.get(name)
    if expected is None:
        return
    import hashlib

    digest = hashlib.md5(path.read_bytes()).hexdigest()
    if digest != expected:
        raise ValueError(
            f"Geometry artifact {path} fails its registry pin "
            f"(md5 {digest} != {expected}); delete the cached file or fix "
            f"the registry entry"
        )


def load_detector_geometry(
    path: str | Path, bank: str
) -> tuple[np.ndarray, np.ndarray]:
    """(positions [n, 3] metres, pixel ids [n]) of a geometric bank."""
    import h5py

    with h5py.File(path, "r") as f:
        det = f[f"/entry/instrument/{bank}"]
        ids = np.asarray(det["detector_number"]).reshape(-1)
        xyz = [
            np.asarray(det[k], dtype=np.float64).reshape(-1)
            for k in ("x_pixel_offset", "y_pixel_offset", "z_pixel_offset")
        ]
    return np.stack(xyz, axis=1), ids


def load_logical_layout(path: str | Path, bank: str) -> np.ndarray:
    """The N-d ``detector_number`` layout of a logical bank."""
    import h5py

    with h5py.File(path, "r") as f:
        return np.asarray(f[f"/entry/instrument/{bank}/detector_number"])

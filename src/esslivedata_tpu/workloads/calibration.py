"""Calibration-table plane: versioned per-pixel LUTs for the workload
families (ADR 0122).

The reference instruments carry per-pixel calibration alongside geometry
— GSAS TOF→d coefficients (difc/difa/tzero) for powder focusing,
flat-field/efficiency maps for imaging — loaded from calibration files
and applied inside the reduction. Here that data becomes a first-class
plane with the same invalidation discipline every other device-resident
constant in this codebase follows (ADR 0110/0113):

- A :class:`CalibrationTable` is **immutable and content-fingerprinted**:
  its ``digest`` covers name, version and every column's bytes. Consumers
  fold the digest into their ``layout_digest``/``stage_key``/``fuse_key``
  (and publish ``static_token``), so *swapping* a calibration re-keys
  staged wires, tick programs and static-output caches by construction —
  the swap can never serve bytes computed under the old table
  (graftlint JGL027 polices writes that bypass this path).
- Tables reach the device through :func:`staged_column`, a bounded
  process-wide cache keyed by (digest, column, device): one transfer per
  table per mesh slice, however many jobs consume it — the stage-once
  rule applied to calibration constants.
- :class:`CalibrationStore` keeps the versioned registry (newest wins,
  explicit versions addressable) so a service can hold several epochs of
  one instrument's calibration and roll between them.

:class:`CalibratedHistogrammer` is the plane's first kernel customer:
an :class:`~..ops.histogram.EventHistogrammer` whose host flatten runs
per-pixel TOF→d-spacing conversion (``d = (toa - tzero_p) / difc_p``,
with the full GSAS quadratic when ``difa`` is present) before binning —
so live powder focusing rides the 4-byte flat wire, the fused/tick
dispatch layers and mesh placement exactly like a detector view, and a
calibration swap is a host-side table replacement whose digest re-keys
the jitted tick program cleanly (warm-up, ADR 0118, can AOT-compile the
swapped program off the hot path).
"""

from __future__ import annotations

import hashlib
import json
import logging
import threading
from collections import OrderedDict
from collections.abc import Mapping
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from ..ops.event_batch import device_token, sanitize_pixel_id
from ..ops.histogram import EventHistogrammer
from ..telemetry.instruments import CALIBRATION_SWAPS

__all__ = [
    "CalibratedHistogrammer",
    "CalibrationStore",
    "CalibrationTable",
    "load_calibration",
    "save_calibration",
    "staged_column",
]

logger = logging.getLogger(__name__)


def _columns_digest(name: str, version: int, columns: Mapping[str, np.ndarray]) -> str:
    h = hashlib.sha1()
    h.update(f"{name}:{version}:".encode())
    for key in sorted(columns):
        arr = columns[key]
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(np.int64(arr.ndim).tobytes())
        h.update(np.asarray(arr.shape, dtype=np.int64).tobytes())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


@dataclass(frozen=True)
class CalibrationTable:
    """One immutable, versioned set of named per-pixel columns.

    ``columns`` maps column name -> numpy array (read-only views so the
    digest cannot rot under a caller's in-place edit); ``digest`` is the
    content fingerprint every staging/compile key derives from. Two
    tables with equal digests are byte-interchangeable everywhere.
    """

    name: str
    version: int
    columns: Mapping[str, np.ndarray]
    digest: str = field(default="", compare=False)

    def __post_init__(self) -> None:
        if not self.name:
            raise ValueError("calibration name must be non-empty")
        frozen: dict[str, np.ndarray] = {}
        for key, arr in self.columns.items():
            arr = np.asarray(arr)
            if arr.size == 0:
                raise ValueError(f"calibration column {key!r} is empty")
            # An OWNED copy, then frozen: a read-only VIEW would still
            # share memory with the caller's writable array, and an
            # in-place edit there would silently rot the digest every
            # staging/compile key hangs off — the exact staleness class
            # this class exists to make impossible.
            owned = np.array(arr, copy=True)
            owned.setflags(write=False)
            frozen[key] = owned
        object.__setattr__(self, "columns", frozen)
        object.__setattr__(
            self,
            "digest",
            _columns_digest(self.name, int(self.version), frozen),
        )

    def column(self, key: str) -> np.ndarray:
        try:
            return self.columns[key]
        except KeyError:
            raise KeyError(
                f"calibration {self.name!r} v{self.version} has no column "
                f"{key!r} (has: {sorted(self.columns)})"
            ) from None

    def require(self, *keys: str) -> None:
        missing = [k for k in keys if k not in self.columns]
        if missing:
            raise ValueError(
                f"calibration {self.name!r} v{self.version} is missing "
                f"required column(s) {missing}"
            )

    def with_columns(self, **columns: np.ndarray) -> CalibrationTable:
        """A new table (version + 1) with the given columns replaced —
        the recalibration constructor: content changes always mean a new
        version, hence a new digest."""
        merged = dict(self.columns)
        merged.update(columns)
        return CalibrationTable(
            name=self.name, version=self.version + 1, columns=merged
        )


def load_calibration(path: str | Path) -> CalibrationTable:
    """Load a table from a ``.npz`` (NeXus-style flat arrays plus
    ``__name__``/``__version__`` scalars) or ``.json`` file."""
    path = Path(path)
    if path.suffix == ".json":
        payload = json.loads(path.read_text())
        return CalibrationTable(
            name=str(payload["name"]),
            version=int(payload.get("version", 1)),
            columns={
                k: np.asarray(v) for k, v in payload["columns"].items()
            },
        )
    with np.load(path) as data:
        columns = {
            k: np.array(data[k])
            for k in data.files
            if not k.startswith("__")
        }
        name = (
            str(data["__name__"]) if "__name__" in data.files else path.stem
        )
        version = (
            int(data["__version__"]) if "__version__" in data.files else 1
        )
    return CalibrationTable(name=name, version=version, columns=columns)


def save_calibration(path: str | Path, table: CalibrationTable) -> None:
    """Write a table in the ``load_calibration`` ``.npz``/``.json``
    format (round-trips digest-identical)."""
    path = Path(path)
    if path.suffix == ".json":
        path.write_text(
            json.dumps(
                {
                    "name": table.name,
                    "version": table.version,
                    "columns": {
                        k: np.asarray(v).tolist()
                        for k, v in table.columns.items()
                    },
                }
            )
        )
        return
    np.savez(
        path,
        __name__=np.asarray(table.name),
        __version__=np.asarray(table.version),
        **{k: np.asarray(v) for k, v in table.columns.items()},
    )


class CalibrationStore:
    """Versioned in-process registry: add tables, address them by
    (name, version) or take the newest per name."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._tables: dict[str, dict[int, CalibrationTable]] = {}

    def add(self, table: CalibrationTable) -> CalibrationTable:
        with self._lock:
            versions = self._tables.setdefault(table.name, {})
            existing = versions.get(table.version)
            if existing is not None and existing.digest != table.digest:
                raise ValueError(
                    f"calibration {table.name!r} v{table.version} already "
                    "registered with different content — recalibrations "
                    "must take a new version"
                )
            versions[table.version] = table
        return table

    def get(self, name: str, version: int) -> CalibrationTable:
        with self._lock:
            try:
                return self._tables[name][version]
            except KeyError:
                raise KeyError(
                    f"no calibration {name!r} v{version}"
                ) from None

    def latest(self, name: str) -> CalibrationTable:
        with self._lock:
            versions = self._tables.get(name)
            if not versions:
                raise KeyError(f"no calibration named {name!r}")
            return versions[max(versions)]

    def versions(self, name: str) -> list[int]:
        with self._lock:
            return sorted(self._tables.get(name, ()))

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._tables)

    def load_dir(self, directory: str | Path) -> int:
        """Register every ``*.npz``/``*.json`` table under a directory;
        returns how many loaded (bad files are logged and skipped — one
        corrupt calibration must not take the whole plane down)."""
        count = 0
        for path in sorted(Path(directory).glob("*")):
            if path.suffix not in (".npz", ".json"):
                continue
            try:
                self.add(load_calibration(path))
                count += 1
            except Exception:
                logger.exception("skipping unreadable calibration %s", path)
        return count


# -- device staging (stage-once for calibration constants) ------------------
#: digest+column+device -> device array. Bounded: calibration sets are
#: config-scale (a few per instrument), so a small LRU holds the working
#: set while letting retired epochs free their HBM.
_STAGED_MAX = 32
_staged_lock = threading.Lock()
_staged: OrderedDict[tuple, object] = OrderedDict()


def staged_column(
    table: CalibrationTable, column: str, *, device=None, dtype=None
):
    """The device-resident copy of one calibration column, staged ONCE
    per (table digest, column, device) process-wide — however many jobs
    (or mesh slices) consume the same calibration epoch. The key is the
    content digest, so a swapped table can never hit the old entry."""
    import jax
    import jax.numpy as jnp

    key = (
        table.digest,
        column,
        device_token(device),
        None if dtype is None else np.dtype(dtype).str,
    )
    with _staged_lock:
        cached = _staged.get(key)
        if cached is not None:
            _staged.move_to_end(key)
            return cached
    host = np.asarray(table.column(column))
    if dtype is not None:
        host = host.astype(dtype)
    arr = jnp.asarray(host) if device is None else jax.device_put(host, device)
    with _staged_lock:
        _staged[key] = arr
        _staged.move_to_end(key)
        while len(_staged) > _STAGED_MAX:
            _staged.popitem(last=False)
    return arr


# -- the plane's first kernel customer --------------------------------------
class CalibratedHistogrammer(EventHistogrammer):
    """Per-pixel-calibrated focusing kernel: events bin on a DERIVED
    axis (TOF→d-spacing via GSAS difc/difa/tzero) instead of raw TOA.

    The conversion runs in the host flatten (one numpy pass fused with
    binning), so the wire stays the 4-byte flat-index fast path and the
    device program is the unchanged flat scatter — the whole calibrated
    family inherits fused stepping, the one-dispatch tick program
    (ADR 0114), mesh placement (ADR 0115) and the publish machinery
    (ADR 0113) without a line of new device code.

    ``d_edges`` is the derived axis (angstrom); ``bank_ids`` optionally
    assigns each pixel a screen row (focussed-per-bank output), giving
    the ADR 0113 static-output split a second big customer via the
    consuming workflow. Keys: ``layout_digest``/``stage_key``/
    ``fuse_key`` all fold in the calibration digest, so
    :meth:`swap_calibration` re-keys staging and every jitted tick
    program cleanly — same discipline as a projection-LUT swap.
    """

    _REQUIRED = ("difc",)

    def __init__(
        self,
        *,
        calibration: CalibrationTable,
        d_edges: np.ndarray,
        bank_ids: np.ndarray | None = None,
        n_banks: int | None = None,
        method: str = "scatter",
        **kwargs,
    ) -> None:
        calibration.require(*self._REQUIRED)
        if bank_ids is not None:
            bank_ids = np.asarray(bank_ids, dtype=np.int32)
            if n_banks is None:
                n_banks = int(bank_ids.max(initial=0)) + 1
            if bank_ids.min(initial=0) < 0 or bank_ids.max(initial=0) >= n_banks:
                raise ValueError("bank_ids must lie in [0, n_banks)")
        self._calib = calibration
        self._bank_ids = bank_ids
        self._adopt_columns(calibration)
        #: Cached combined fingerprint; dropped by swap_calibration so
        #: every staging/fusion/static key re-derives (JGL027 contract).
        self._cal_digest_cache: str | None = None
        super().__init__(
            toa_edges=np.asarray(d_edges, dtype=np.float64),
            n_screen=1 if bank_ids is None else int(n_banks),
            method=method,
            **kwargs,
        )
        if not self.supports_host_flatten:
            # Per-pixel weights / replica LUTs route the base class to
            # the raw DEVICE path, which would bin raw TOA nanoseconds
            # against the derived (d-spacing) edges — silently garbage.
            # Every calibrated step must take the host flatten.
            raise ValueError(
                "CalibratedHistogrammer requires a host-flattenable "
                "configuration (no pixel_weights/replica LUTs): the "
                "TOF->d conversion lives in the host flatten"
            )

    def _adopt_columns(self, table: CalibrationTable) -> None:
        """Unpack the hot-path column views (float32 — the flatten's
        working precision; 8 ns at ESS frame scale, far below any d
        bin). Called only from __init__ and swap_calibration."""
        difc = np.asarray(table.column("difc"), dtype=np.float32).reshape(-1)
        if self._bank_ids is not None and self._bank_ids.shape != difc.shape:
            raise ValueError("bank_ids must match difc length")
        self._difc = difc
        tzero = table.columns.get("tzero")
        self._tzero = (
            None
            if tzero is None
            else np.asarray(tzero, dtype=np.float32).reshape(-1)
        )
        difa = table.columns.get("difa")
        self._difa = (
            None
            if difa is None
            else np.asarray(difa, dtype=np.float32).reshape(-1)
        )
        for name, col in (("tzero", self._tzero), ("difa", self._difa)):
            if col is not None and col.shape != difc.shape:
                raise ValueError(f"{name} must match difc length")

    # -- calibration identity ------------------------------------------------
    @property
    def calibration(self) -> CalibrationTable:
        return self._calib

    @property
    def layout_digest(self) -> str:
        """Bin edges + bank routing + the CALIBRATION content: everything
        that determines where an event lands. The publish static token
        and every staging/fusion key hang off this, so a calibration
        swap invalidates them all at once."""
        if self._cal_digest_cache is None:
            h = hashlib.sha1()
            h.update(self._proj.layout_digest.encode())
            h.update(self._calib.digest.encode())
            if self._bank_ids is not None:
                h.update(self._bank_ids.tobytes())
            self._cal_digest_cache = h.hexdigest()
        return self._cal_digest_cache

    @property
    def stage_key(self) -> tuple:
        # The staged flat wire depends on the calibrated projection, not
        # just the raw layout — two calibration epochs must never share
        # a staged array (ADR 0110's keys-capture-everything rule).
        return ("calflat", self.layout_digest)

    @property
    def partition_key(self) -> tuple:
        return (
            "calpart",
            self.layout_digest,
            self._bpb,
            self._p2_chunk,
            self._p2_compact,
        )

    @property
    def fuse_key(self) -> tuple:
        # The combined digest (calibration + bank routing + axis), not
        # just the table digest: two jobs differing only in bank_ids
        # flatten differently and must never fuse.
        return ("cal", self.layout_digest) + EventHistogrammer.fuse_key.fget(
            self
        )

    def swap_calibration(self, table: CalibrationTable) -> bool:
        """Install a new calibration epoch WITHOUT touching device code.

        The d bin space is unchanged, so accumulated counts keep their
        meaning and persist (the qshared recalibration rule); the digest
        changes, so the next window's staging misses cleanly, the tick
        program re-keys (compile classified ``layout_swap`` by the
        ADR 0116 instrument — or pre-compiled off the hot path when
        warm-up is attached, ADR 0118) and publish statics refetch under
        the new token. Returns False (no state touched) when the table
        is not drop-in compatible (different pixel count / missing
        columns)."""
        try:
            table.require(*self._REQUIRED)
            difc = np.asarray(table.column("difc")).reshape(-1)
            if difc.shape != self._difc.shape:
                return False
            old = (self._calib, self._difc, self._tzero, self._difa)
            self._calib = table
            try:
                self._adopt_columns(table)
            except ValueError:
                self._calib, self._difc, self._tzero, self._difa = old
                return False
        except (KeyError, ValueError):
            return False
        self._cal_digest_cache = None
        CALIBRATION_SWAPS.inc(kind="tof_dspacing")
        return True

    # -- calibrated host flatten --------------------------------------------
    def flatten_host(
        self,
        pixel_id: np.ndarray,
        toa: np.ndarray,
        *,
        out: np.ndarray | None = None,
    ) -> np.ndarray:
        """TOF→d per event, then bin into the derived axis — one numpy
        pass shaped exactly like the base flatten (invalid events land
        in the dump bin). ``d = (toa - tzero_p) / difc_p`` (GSAS
        ``difa`` quadratic when present: the positive root of
        ``difa d^2 + difc d + tzero = toa``)."""
        pixel_id = sanitize_pixel_id(pixel_id)
        toa = np.asarray(toa, dtype=np.float32)
        n_pix = self._difc.shape[0]
        p_ok = (pixel_id >= 0) & (pixel_id < n_pix)
        pid = np.clip(pixel_id, 0, n_pix - 1)
        difc = self._difc[pid]
        tof = toa if self._tzero is None else toa - self._tzero[pid]
        with np.errstate(divide="ignore", invalid="ignore"):
            if self._difa is None:
                d = tof / difc
                ok = p_ok & (difc > 0)
            else:
                difa = self._difa[pid]
                disc = difc * difc + 4.0 * difa * tof
                quad = np.abs(difa) > 1e-20
                d = np.where(
                    quad,
                    (-difc + np.sqrt(np.maximum(disc, 0.0)))
                    / np.where(quad, 2.0 * difa, 1.0),
                    tof / difc,
                )
                ok = p_ok & (difc > 0) & (disc >= 0)
        ok &= np.isfinite(d)
        proj = self._proj
        if proj.uniform:
            db = ((d - np.float32(proj.lo)) * np.float32(proj.inv_width)).astype(
                np.int32
            )
            ok &= (d >= np.float32(proj.lo)) & (d < np.float32(proj.hi))
            np.clip(db, 0, self._n_toa - 1, out=db)
        else:
            db = (
                np.searchsorted(
                    self._edges_f32, d.astype(np.float32), side="right"
                ).astype(np.int32)
                - 1
            )
            ok &= (db >= 0) & (db < self._n_toa)
            np.clip(db, 0, self._n_toa - 1, out=db)
        if self._bank_ids is not None:
            row = self._bank_ids[pid]
            flat_vals = row.astype(np.int32) * np.int32(self._n_toa) + db
        else:
            flat_vals = db
        if out is not None:
            np.copyto(out, flat_vals, casting="unsafe")
            flat = out
        else:
            flat = flat_vals.astype(np.int32, copy=False)
        flat[~ok] = self._n_bins
        return flat

    def flatten_partition_host(
        self, pixel_id: np.ndarray, toa: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        # The base's fused native pass computes RAW-toa indices; the
        # calibrated axis must always go flatten -> generic partition.
        from ..ops.pallas_hist2d import partition_events_host

        return partition_events_host(
            self.flatten_host(pixel_id, toa),
            self._n_bins + 1,
            bpb=self._bpb,
            chunk=self._p2_chunk,
            compact=self._p2_compact,
        )

    # The raw device path would bin raw TOA by the derived-axis edges;
    # every calibrated step must route through the host flatten.
    def step(self, state, batch):
        return self.step_flat(
            state, self.flatten_host(batch.pixel_id, batch.toa)
        )

    def step_arrays(self, state, pixel_id, toa):
        return self.step_flat(
            state,
            self.flatten_host(np.asarray(pixel_id), np.asarray(toa)),
        )

    # -- derived-axis acceptance --------------------------------------------
    def acceptance(
        self, toa_lo: float = 0.0, toa_hi: float | None = None
    ) -> np.ndarray:
        """Per-derived-bin instrument acceptance from the calibration
        itself: how many pixels' valid TOA range covers each d bin
        (the live analog of a vanadium normalization — same move as
        ``workflows.powder.vanadium_acceptance``, but read off the
        difc/tzero columns instead of a precompiled map). ``toa_lo``/
        ``toa_hi`` bound the physically reachable event TOAs (the frame
        window); ``None`` leaves the high side open. Scaled to mean 1
        over populated bins; zero-acceptance bins stay 0 and are masked
        at division time. Shape ``[n_banks, n_d]``."""
        edges = self._edges  # derived-axis (d) edges, float64
        n_d = self._n_toa
        difc = self._difc.astype(np.float64)
        valid = difc > 0
        tzero = (
            np.zeros_like(difc)
            if self._tzero is None
            else self._tzero.astype(np.float64)
        )
        with np.errstate(divide="ignore", invalid="ignore"):
            d_lo = (toa_lo - tzero) / difc
            d_hi = (
                np.full_like(difc, edges[-1])
                if toa_hi is None
                else (toa_hi - tzero) / difc
            )
        lo_bin = np.clip(
            np.searchsorted(edges, np.maximum(d_lo, edges[0]), side="right") - 1,
            0,
            n_d,
        )
        hi_bin = np.clip(
            np.searchsorted(edges, np.minimum(d_hi, edges[-1]), side="left"),
            0,
            n_d,
        )
        banks = (
            np.zeros_like(difc, dtype=np.int32)
            if self._bank_ids is None
            else self._bank_ids
        )
        n_banks = self._n_screen
        counts = np.zeros((n_banks, n_d + 1), dtype=np.float64)
        # Interval coverage via a per-bank difference array: O(n_pixel).
        sel = valid & (hi_bin > lo_bin)
        np.add.at(counts, (banks[sel], lo_bin[sel]), 1.0)
        np.add.at(counts, (banks[sel], hi_bin[sel]), -1.0)
        counts = np.cumsum(counts, axis=1)[:, :n_d]
        populated = counts > 0
        if populated.any():
            counts[populated] /= counts[populated].mean()
        return counts

"""Projection tables: physical pixel -> 2-D screen bin.

The reference computes per-event screen coordinates with numpy fancy
indexing per batch (GeometricProjector, projectors.py:47-100, chosen over
sc.bins_like for 2-10x speed). On TPU the projection is hoisted out of the
per-batch path entirely: geometry is compiled *once* into an int32 gather
table ``lut[replica, pixel] -> flat screen bin`` and per-batch work is a
single device gather fused into the scatter kernel. Position-noise replicas
(the reference's gaussian antialiasing of coarse pixels onto fine screens)
are extra LUT rows at 1/R weight.

Geometry recompute (moved detector, new noise draw) = rebuild the table on
host and swap it in — the stream never stalls (SURVEY.md section 7 "hard
parts" item 5).
"""

from __future__ import annotations

import time
import weakref
from dataclasses import dataclass

import numpy as np

from ...telemetry.instruments import TABLE_BUILD_SECONDS, TABLE_BYTES
from ...utils.labeled import Variable

__all__ = [
    "LogicalView",
    "NdLogicalView",
    "ProjectionTable",
    "project_geometric",
    "project_logical",
    "project_logical_nd",
]


@dataclass(frozen=True)
class ProjectionTable:
    """Pixel -> screen-bin gather table plus screen geometry."""

    lut: np.ndarray  # int32 [n_replica, n_pixel_id_space] -> flat bin or -1
    ny: int
    nx: int
    y_edges: Variable
    x_edges: Variable
    x_name: str = "x"
    y_name: str = "y"

    @property
    def n_screen(self) -> int:
        return self.ny * self.nx

    @property
    def n_replica(self) -> int:
        return int(self.lut.shape[0])


def _built(table: ProjectionTable, began: float) -> ProjectionTable:
    """``table``, counted: set-up has no span, so what the LUT cost to
    build (from ``began``, a ``perf_counter`` reading) and the bytes it
    holds while it lives go under the table instruments' family
    ``projection``."""
    TABLE_BUILD_SECONDS.inc(time.perf_counter() - began, family="projection")
    nbytes = table.lut.nbytes
    TABLE_BYTES.inc(nbytes, family="projection")
    weakref.finalize(table, TABLE_BYTES.dec, nbytes, family="projection")
    return table


def _bin_2d(
    xc: np.ndarray,
    yc: np.ndarray,
    x_edges: np.ndarray,
    y_edges: np.ndarray,
    nx: int,
    ny: int,
) -> np.ndarray:
    xi = np.searchsorted(x_edges, xc, side="right") - 1
    yi = np.searchsorted(y_edges, yc, side="right") - 1
    ok = (xi >= 0) & (xi < nx) & (yi >= 0) & (yi < ny)
    flat = np.where(ok, yi * nx + xi, -1).astype(np.int32)
    return flat


def project_geometric(
    positions: np.ndarray,
    pixel_ids: np.ndarray,
    *,
    mode: str = "xy_plane",
    resolution: tuple[int, int] = (128, 128),
    noise_sigma: float = 0.0,
    n_replica: int = 1,
    extent: tuple[float, float, float, float] | None = None,
    seed: int = 0,
    unit: str = "m",
) -> ProjectionTable:
    """Build a projection table from 3-D pixel positions.

    Parameters
    ----------
    positions:
        [n, 3] pixel centers (x, y, z).
    pixel_ids:
        [n] detector numbers addressing events' pixel_id space.
    mode:
        'xy_plane' — project along z onto the xy plane;
        'cylinder_mantle_z' — unroll a cylinder around z: (phi*r_mean, z).
    resolution:
        (ny, nx) screen bins.
    noise_sigma:
        Gaussian position noise in position units; with ``n_replica`` > 1
        each pixel gets R jittered screen assignments at weight 1/R,
        antialiasing coarse pixels onto fine screens (reference
        projectors.py:47 replicas).
    extent:
        Optional (x_min, x_max, y_min, y_max) screen bounds; default = data
        bounds of the *unjittered* projection.
    """
    began = time.perf_counter()
    positions = np.asarray(positions, dtype=np.float64)
    pixel_ids = np.asarray(pixel_ids)
    if positions.ndim != 2 or positions.shape[1] != 3:
        raise ValueError("positions must be [n, 3]")
    if positions.shape[0] != pixel_ids.shape[0]:
        raise ValueError("positions and pixel_ids must have equal length")
    ny, nx = resolution

    def project(pos: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        if mode == "xy_plane":
            return pos[:, 0], pos[:, 1]
        if mode == "cylinder_mantle_z":
            r = np.hypot(pos[:, 0], pos[:, 1])
            phi = np.arctan2(pos[:, 1], pos[:, 0])
            return phi * float(np.mean(r)), pos[:, 2]
        raise ValueError(f"Unknown projection mode {mode!r}")

    x0, y0 = project(positions)
    if extent is None:
        pad_x = (x0.max() - x0.min()) / nx if x0.max() > x0.min() else 1.0
        pad_y = (y0.max() - y0.min()) / ny if y0.max() > y0.min() else 1.0
        extent = (
            float(x0.min() - 0.5 * pad_x),
            float(x0.max() + 0.5 * pad_x),
            float(y0.min() - 0.5 * pad_y),
            float(y0.max() + 0.5 * pad_y),
        )
    x_edges = np.linspace(extent[0], extent[1], nx + 1)
    y_edges = np.linspace(extent[2], extent[3], ny + 1)

    n_id_space = int(pixel_ids.max()) + 1
    rng = np.random.default_rng(seed)
    if noise_sigma > 0.0 and n_replica > 1:
        luts = []
        for _ in range(n_replica):
            jitter = rng.normal(0.0, noise_sigma, positions.shape)
            xj, yj = project(positions + jitter)
            luts.append(_bin_2d(xj, yj, x_edges, y_edges, nx, ny))
        flat_rep = np.stack(luts)  # [R, n]
    else:
        flat_rep = _bin_2d(x0, y0, x_edges, y_edges, nx, ny)[None, :]

    lut = np.full((flat_rep.shape[0], n_id_space), -1, dtype=np.int32)
    lut[:, pixel_ids] = flat_rep
    return _built(
        ProjectionTable(
            lut=lut,
            ny=ny,
            nx=nx,
            y_edges=Variable(y_edges, ("y",), unit),
            x_edges=Variable(x_edges, ("x",), unit),
        ),
        began,
    )


@dataclass(frozen=True)
class NdLogicalView:
    """N-d fold -> slice -> display spec for voxel detectors (DREAM).

    The reference expresses these as scipp fold/transpose/slice/flatten
    transforms re-applied per cycle (dream/views.py); here the whole view
    collapses into the pixel->screen LUT built once: ``sizes`` folds the
    flat detector_number array, ``select`` slices dims to a fixed index
    (other voxels drop out), ``y``/``x`` dims composite into screen
    rows/cols, and any remaining dim is summed — many voxels landing on one
    screen bin, which the scatter-add performs for free.
    """

    sizes: dict[str, int]
    y: tuple[str, ...]
    x: tuple[str, ...] = ()
    select: dict[str, int] = None  # type: ignore[assignment]

    def __post_init__(self) -> None:
        object.__setattr__(self, "select", dict(self.select or {}))
        names = set(self.sizes)
        for dim in (*self.y, *self.x, *self.select):
            if dim not in names:
                raise ValueError(f"view dim {dim!r} not in sizes {names}")
        if set(self.y) & set(self.x):
            raise ValueError("y and x dims must be disjoint")
        for dim, index in self.select.items():
            if not 0 <= index < self.sizes[dim]:
                raise ValueError(
                    f"select[{dim!r}]={index} out of range {self.sizes[dim]}"
                )


def project_logical_nd(
    detector_numbers: np.ndarray, view: NdLogicalView
) -> ProjectionTable:
    """Build a projection table from an N-d voxel layout.

    ``detector_numbers`` is flat (C-order over ``view.sizes``) or already
    shaped to those sizes.
    """
    began = time.perf_counter()
    shape = tuple(view.sizes.values())
    det = np.asarray(detector_numbers).reshape(shape)
    dims = list(view.sizes)
    index = np.indices(shape)
    per_dim = {d: index[i] for i, d in enumerate(dims)}

    keep = np.ones(shape, dtype=bool)
    for dim, sel in view.select.items():
        keep &= per_dim[dim] == sel

    def composite(parts: tuple[str, ...]) -> tuple[np.ndarray, int]:
        idx = np.zeros(shape, dtype=np.int64)
        total = 1
        for dim in parts:
            idx = idx * view.sizes[dim] + per_dim[dim]
            total *= view.sizes[dim]
        return idx, total

    row, ny = composite(view.y)
    col, nx = composite(view.x)
    screen = np.where(keep, row * nx + col, -1).astype(np.int32)

    n_id_space = int(det.max()) + 1
    lut = np.full((1, n_id_space), -1, dtype=np.int32)
    lut[0, det.reshape(-1)] = screen.reshape(-1)
    return _built(
        ProjectionTable(
            lut=lut,
            ny=ny,
            nx=nx,
            y_edges=Variable(np.arange(ny + 1, dtype=np.float64) - 0.5, ("y",), ""),
            x_edges=Variable(np.arange(nx + 1, dtype=np.float64) - 0.5, ("x",), ""),
        ),
        began,
    )


@dataclass(frozen=True)
class LogicalView:
    """Fold/transpose/slice spec for detectors whose detector_number layout
    is already a grid (reference LogicalProjector: fold/slice/sum)."""

    fold: tuple[int, int]  # (ny, nx)
    transpose: bool = False
    flip_y: bool = False
    flip_x: bool = False


def project_logical(
    detector_numbers: np.ndarray,
    view: LogicalView | None = None,
) -> ProjectionTable:
    """Build a projection table from a 2-D detector_number grid.

    ``detector_numbers`` is the instrument's [ny, nx] grid (or flat array
    with ``view.fold``). Screen bin (y, x) simply *is* the grid position —
    the identity-layout fast path the reference implements as fold/slice
    transforms.
    """
    began = time.perf_counter()
    det = np.asarray(detector_numbers)
    if det.ndim == 1:
        if view is None:
            raise ValueError("flat detector_numbers require a LogicalView.fold")
        det = det.reshape(view.fold)
    if view is not None:
        if view.transpose:
            det = det.T
        if view.flip_y:
            det = det[::-1, :]
        if view.flip_x:
            det = det[:, ::-1]
    ny, nx = det.shape
    n_id_space = int(det.max()) + 1
    lut = np.full((1, n_id_space), -1, dtype=np.int32)
    yy, xx = np.meshgrid(np.arange(ny), np.arange(nx), indexing="ij")
    lut[0, det.reshape(-1)] = (yy * nx + xx).reshape(-1).astype(np.int32)
    return _built(
        ProjectionTable(
            lut=lut,
            ny=ny,
            nx=nx,
            y_edges=Variable(np.arange(ny + 1, dtype=np.float64) - 0.5, ("y",), ""),
            x_edges=Variable(np.arange(nx + 1, dtype=np.float64) - 0.5, ("x",), ""),
        ),
        began,
    )

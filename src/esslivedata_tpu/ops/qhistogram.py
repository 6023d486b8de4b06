"""Q-space event histogrammer: the SANS I(Q) hot kernel.

The reference computes I(Q) through esssans' sciline pipeline on CPU
(reference: instruments/loki/factories.py:21-120 wiring esssans). The
TPU-native shape: all per-event physics — pixel geometry (scattering angle,
flight path) and TOF->wavelength conversion — is *precompiled on the host*
into a dense int32 map ``qmap[pixel, toa_bin] -> Q bin``; the per-batch
device work is then gather + scatter-add, identical in cost to the plain
2-D histogram. A geometry or wavelength-calibration change rebuilds the map
on host and swaps it in without stalling the stream.
"""

from __future__ import annotations

import functools
import itertools
import time
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.instruments import (
    Q_BINCOUNT_STEPS,
    Q_LOOKUP_STEPS,
    TABLE_BUILD_SECONDS,
    TABLE_BYTES,
)
from ..telemetry.trace import TRACER
from .event_batch import EventBatch, stage_for, stage_raw

__all__ = [
    "QHistogrammer",
    "QState",
    "PixelBinMap",
    "build_dspacing_map",
    "build_elastic_q2d_map",
    "build_qe_map",
    "build_qz_map",
    "build_sans_qmap",
    "build_wavelength_map",
    "table_scatter_delta",
]

#: meV per (m/s)^2 — E = 1/2 m_n v^2 in neutron units.
E_FROM_V2 = 5.227037e-6
#: 1/angstrom per (m/s) — k = m_n v / hbar in neutron units.
K_FROM_V = 1.58825e-3
#: h / m_n in neutron units: lambda[angstrom] = H_OVER_MN * t[s] / L[m].
H_OVER_MN = 3956.034

#: Pixels per chunk in the host map builders: bounds peak intermediate
#: memory to chunk * n_toa floats regardless of bank size.
_MAP_CHUNK = 65536


class QState(NamedTuple):
    cumulative: jax.Array  # [n_q]
    window: jax.Array  # [n_q]
    monitor_cumulative: jax.Array  # scalar
    monitor_window: jax.Array  # scalar


class PixelBinMap(NamedTuple):
    """A (pixel, toa-bin) -> bin table over the bank's own id range.

    ``table`` rows cover ``[id_base, id_base + n_rows)`` — NOT the global
    pixel-id space; the kernel subtracts ``id_base`` before the lookup.
    DREAM's banks sit hundreds of thousands of ids into a shared
    sequential space, and a globally-indexed table would be ~95% dead
    rows of device memory. ``table`` is int16 when the bin count fits
    (halving HBM for LOKI/DREAM-scale maps), int32 otherwise; -1 = drop.
    ``family`` names the builder that made it, as the label of
    ``livedata_table_bytes`` / ``livedata_table_build_seconds_total``.
    """

    table: np.ndarray
    id_base: int
    family: str = "q"


def _table_build(family: str):
    """A map builder whose host time counts into
    ``livedata_table_build_seconds_total{family}`` and whose map
    carries the family's name."""

    def wrap(build):
        @functools.wraps(build)
        def timed(**kwargs) -> PixelBinMap:
            began = time.perf_counter()
            built = build(**kwargs)._replace(family=family)
            TABLE_BUILD_SECONDS.inc(time.perf_counter() - began, family=family)
            return built

        return timed

    return wrap


def _toa_centers_s(toa_edges: np.ndarray, toa_offset_ns: float) -> np.ndarray:
    edges = np.asarray(toa_edges, dtype=np.float64)
    return ((edges[:-1] + edges[1:]) / 2.0 + toa_offset_ns) * 1e-9


def _assemble_map(
    pixel_ids: np.ndarray, row_bins: np.ndarray, n_bins: int
) -> PixelBinMap:
    """Scatter per-declared-pixel rows into the bank-local id table."""
    ids = np.asarray(pixel_ids)
    id_base = int(ids.min())
    n_rows = int(ids.max()) - id_base + 1
    dtype = np.int16 if n_bins < np.iinfo(np.int16).max else np.int32
    table = np.full((n_rows, row_bins.shape[1]), -1, dtype=dtype)
    table[ids - id_base] = row_bins.astype(dtype)
    return PixelBinMap(table=table, id_base=id_base)


@_table_build("sans_iq")
def build_sans_qmap(
    *,
    positions: np.ndarray,  # [n_pixel, 3] in m, sample at origin, beam +z
    pixel_ids: np.ndarray,
    toa_edges: np.ndarray,  # ns within pulse
    q_edges: np.ndarray,  # 1/angstrom
    l1: float = 23.0,  # source->sample flight path (m)
    toa_offset_ns: float = 0.0,
    beam_center: tuple[float, float] = (0.0, 0.0),  # (x, y) in m
) -> PixelBinMap:
    """Precompile per-event physics into a bank-local ``PixelBinMap``
    (``table[pixel_id - id_base, toa_bin]``).

    lambda[angstrom] = (h / m_n) * t / L  with t the time of flight and
    L = l1 + l2(pixel); Q = 4 pi sin(theta/2) / lambda with theta the
    scattering angle off the +z beam axis. ``beam_center`` shifts the
    full pixel position vector (the reference's BeamCenterXY,
    loki/specs.py:63-85) so the beam axis passes through the measured
    center — this moves both the scattering angle AND the l2 flight
    path (hence the wavelength mapping), matching the convention of
    reducing against beam-center-corrected positions. Entries mapping
    outside ``q_edges`` are -1 (dropped by the kernel).
    """
    positions = np.asarray(positions, dtype=np.float64)
    bx, by = beam_center
    if bx or by:
        positions = positions - np.array([bx, by, 0.0])
    l2 = np.linalg.norm(positions, axis=1)  # sample->pixel (m)
    r_perp = np.hypot(positions[:, 0], positions[:, 1])
    theta = np.arctan2(r_perp, positions[:, 2])  # scattering angle
    k_factor = 4.0 * np.pi * np.sin(theta / 2.0)  # [n_pixel]

    toa_centers_s = _toa_centers_s(toa_edges, toa_offset_ns)
    L = l1 + l2  # [n_pixel]
    n_pixel = L.size
    q_bin = np.empty((n_pixel, toa_centers_s.size), dtype=np.int32)
    for lo in range(0, n_pixel, _MAP_CHUNK):
        sl = slice(lo, min(lo + _MAP_CHUNK, n_pixel))
        lam = H_OVER_MN * toa_centers_s[None, :] / L[sl, None]
        with np.errstate(divide="ignore", invalid="ignore"):
            q = k_factor[sl, None] / lam  # 1/angstrom
        qb = np.searchsorted(q_edges, q, side="right") - 1
        qb[(q < q_edges[0]) | (q >= q_edges[-1]) | ~np.isfinite(q)] = -1
        q_bin[sl] = qb
    return _assemble_map(pixel_ids, q_bin, len(q_edges) - 1)


@_table_build("dspacing")
def build_dspacing_map(
    *,
    two_theta: np.ndarray,  # [n_pixel] scattering angle (rad)
    l_total: np.ndarray,  # [n_pixel] moderator->sample->pixel path (m)
    pixel_ids: np.ndarray,
    toa_edges: np.ndarray,  # ns since pulse
    d_edges: np.ndarray,  # angstrom
    toa_offset_ns: float = 0.0,
) -> PixelBinMap:
    """Precompile powder-diffraction physics into
    ``map[pixel, toa_bin] -> d bin``.

    Bragg: ``lambda = (h / m_n) t / L`` and ``d = lambda / (2 sin
    theta)`` with ``theta`` half the scattering angle — each pixel's TOF
    axis is a fixed d-spacing axis, so the whole conversion is a table.
    Out-of-range or unphysical entries map to -1 (dropped).
    """
    two_theta = np.asarray(two_theta, dtype=np.float64)
    l_total = np.asarray(l_total, dtype=np.float64)
    toa_centers_s = _toa_centers_s(toa_edges, toa_offset_ns)
    inv_2sin = 1.0 / (2.0 * np.sin(two_theta / 2.0))
    n_pixel = l_total.size
    d_bin = np.empty((n_pixel, toa_centers_s.size), dtype=np.int32)
    for lo in range(0, n_pixel, _MAP_CHUNK):
        sl = slice(lo, min(lo + _MAP_CHUNK, n_pixel))
        with np.errstate(divide="ignore", invalid="ignore"):
            lam = H_OVER_MN * toa_centers_s[None, :] / l_total[sl, None]
            d = lam * inv_2sin[sl, None]
        db = np.searchsorted(d_edges, d, side="right") - 1
        db[~(np.isfinite(d) & (db >= 0) & (d < d_edges[-1]))] = -1
        d_bin[sl] = db
    return _assemble_map(pixel_ids, d_bin, len(d_edges) - 1)


@_table_build("qz")
def build_qz_map(
    *,
    grazing_angle: np.ndarray,  # [n_pixel] incidence+reflection angle (rad)
    l_total: np.ndarray,  # [n_pixel] moderator->sample->pixel path (m)
    pixel_ids: np.ndarray,
    toa_edges: np.ndarray,  # ns since pulse
    qz_edges: np.ndarray,  # 1/angstrom
    toa_offset_ns: float = 0.0,
) -> PixelBinMap:
    """Precompile specular-reflectometry physics into
    ``map[pixel, toa_bin] -> Qz bin``.

    ``Q_z = 4 pi sin(theta) / lambda`` with ``theta`` the grazing angle
    the pixel observes for the CURRENT sample rotation — unlike the
    other maps this one depends on a motor position, so the workflow
    rebuilds it when the sample angle moves (the stream is untouched;
    a rebuild swaps tables between batches). Non-reflecting pixels
    (theta <= 0) and out-of-range Qz map to -1.
    """
    grazing_angle = np.asarray(grazing_angle, dtype=np.float64)
    l_total = np.asarray(l_total, dtype=np.float64)
    toa_centers_s = _toa_centers_s(toa_edges, toa_offset_ns)
    k_factor = 4.0 * np.pi * np.sin(grazing_angle)  # [n_pixel]
    n_pixel = l_total.size
    qz_bin = np.empty((n_pixel, toa_centers_s.size), dtype=np.int32)
    for lo in range(0, n_pixel, _MAP_CHUNK):
        sl = slice(lo, min(lo + _MAP_CHUNK, n_pixel))
        with np.errstate(divide="ignore", invalid="ignore"):
            lam = H_OVER_MN * toa_centers_s[None, :] / l_total[sl, None]
            qz = k_factor[sl, None] / lam
        qb = np.searchsorted(qz_edges, qz, side="right") - 1
        ok = (
            np.isfinite(qz)
            & (grazing_angle[sl, None] > 0)
            & (qb >= 0)
            & (qz < qz_edges[-1])
        )
        qb[~ok] = -1
        qz_bin[sl] = qb
    return _assemble_map(pixel_ids, qz_bin, len(qz_edges) - 1)


@_table_build("qe")
def build_qe_map(
    *,
    two_theta: np.ndarray,  # [n_pixel] scattering angle (rad)
    ef_mev: np.ndarray,  # [n_pixel] analyzer-selected final energy
    l2: np.ndarray,  # [n_pixel] sample->analyzer->detector path (m)
    pixel_ids: np.ndarray,
    toa_edges: np.ndarray,  # ns since pulse
    q_edges: np.ndarray,  # 1/angstrom
    e_edges: np.ndarray,  # meV energy transfer (Ei - Ef)
    l1: float = 162.0,  # ESS source->sample for BIFROST
    toa_offset_ns: float = 0.0,
) -> PixelBinMap:
    """Precompile indirect-geometry spectrometer physics into
    ``map[pixel, toa_bin] -> flat (Q, E) bin`` (row-major, ``n_e`` fast).

    The analyzer crystal fixes the final energy per pixel, so the final
    leg's flight time is a per-pixel constant: ``t2 = l2 / v(Ef)``.
    Subtracting it from the arrival time gives the incident velocity
    ``vi = l1 / (t - t2)``, hence ``Ei``, the energy transfer
    ``dE = Ei - Ef`` and the momentum transfer
    ``|Q|^2 = ki^2 + kf^2 - 2 ki kf cos(2theta)``. Events whose (Q, E)
    falls outside the edges — or that arrive before the final leg alone
    could deliver them — map to -1 (dropped by the kernel). Like the
    SANS map, a geometry/calibration change rebuilds on host and swaps
    in without touching the stream.
    """
    two_theta = np.asarray(two_theta, dtype=np.float64)
    ef = np.asarray(ef_mev, dtype=np.float64)
    l2 = np.asarray(l2, dtype=np.float64)
    vf = np.sqrt(ef / E_FROM_V2)  # [n_pixel]
    t2 = l2 / vf  # s, per-pixel constant final leg
    toa_centers_s = _toa_centers_s(toa_edges, toa_offset_ns)
    n_e = len(e_edges) - 1
    n_pixel = l2.size
    flat_bin = np.empty((n_pixel, toa_centers_s.size), dtype=np.int32)
    for lo in range(0, n_pixel, _MAP_CHUNK):
        sl = slice(lo, min(lo + _MAP_CHUNK, n_pixel))
        t1 = toa_centers_s[None, :] - t2[sl, None]
        with np.errstate(divide="ignore", invalid="ignore"):
            vi = l1 / t1
            ei = E_FROM_V2 * vi * vi
            de = ei - ef[sl, None]
            ki = K_FROM_V * vi
            kf = (K_FROM_V * vf)[sl, None]
            q = np.sqrt(
                np.maximum(
                    ki * ki
                    + kf * kf
                    - 2.0 * ki * kf * np.cos(two_theta)[sl, None],
                    0.0,
                )
            )
        qb = np.searchsorted(q_edges, q, side="right") - 1
        eb = np.searchsorted(e_edges, de, side="right") - 1
        ok = (
            (t1 > 0)
            & np.isfinite(q)
            & np.isfinite(de)
            & (qb >= 0)
            & (q < q_edges[-1])
            & (eb >= 0)
            & (de < e_edges[-1])
        )
        flat = qb * n_e + eb
        flat[~ok] = -1
        flat_bin[sl] = flat
    return _assemble_map(
        pixel_ids, flat_bin, (len(q_edges) - 1) * n_e
    )


@_table_build("wavelength")
def build_wavelength_map(
    *,
    l_total: np.ndarray,  # [n_pixel] moderator->sample->pixel path (m)
    pixel_ids: np.ndarray,
    toa_edges: np.ndarray,  # ns since pulse
    wavelength_edges: np.ndarray,  # angstrom
    toa_offset_ns: float = 0.0,
) -> PixelBinMap:
    """Precompile the per-pixel TOF->wavelength conversion into
    ``map[pixel, toa_bin] -> wavelength bin``.

    The monitor workflow can relabel its axis because one flight path
    serves all events; a position-resolved detector has a different L
    per pixel, so the same arrival time means a different wavelength in
    every pixel — exactly the (pixel, toa) -> bin shape of this family
    (the reference reaches wavelength via its unwrap LUT providers,
    monitor_workflow.py:169 / detector_view providers).
    """
    l_total = np.asarray(l_total, dtype=np.float64)
    toa_centers_s = _toa_centers_s(toa_edges, toa_offset_ns)
    n_pixel = l_total.size
    w_bin = np.empty((n_pixel, toa_centers_s.size), dtype=np.int32)
    for lo in range(0, n_pixel, _MAP_CHUNK):
        sl = slice(lo, min(lo + _MAP_CHUNK, n_pixel))
        with np.errstate(divide="ignore", invalid="ignore"):
            lam = H_OVER_MN * toa_centers_s[None, :] / l_total[sl, None]
        wb = np.searchsorted(wavelength_edges, lam, side="right") - 1
        ok = (
            np.isfinite(lam)
            & (wb >= 0)
            & (lam < wavelength_edges[-1])
        )
        wb[~ok] = -1
        w_bin[sl] = wb
    return _assemble_map(pixel_ids, w_bin, len(wavelength_edges) - 1)


@_table_build("elastic_q2d")
def build_elastic_q2d_map(
    *,
    two_theta: np.ndarray,  # [n_pixel] scattering angle (rad)
    azimuth: np.ndarray,  # [n_pixel] out-of-plane azimuth (rad)
    ef_mev: np.ndarray,  # [n_pixel] analyzer-selected final energy
    l2: np.ndarray,  # [n_pixel] sample->analyzer->detector path (m)
    pixel_ids: np.ndarray,
    toa_edges: np.ndarray,  # ns since pulse
    axis1: str,  # "Qx" | "Qy" | "Qz"
    axis1_edges: np.ndarray,  # 1/angstrom
    axis2: str,
    axis2_edges: np.ndarray,
    l1: float = 162.0,
    e_window_mev: float = 0.25,
    toa_offset_ns: float = 0.0,
) -> PixelBinMap:
    """Precompile the elastic-line Q-space map (reference: bifrost
    specs.py:376 elastic_qmap) into ``map[pixel, toa_bin] -> flat
    (axis1, axis2) bin`` (row-major, axis2 fast).

    With ki along +z and kf along the pixel's direction
    ``(sin 2theta cos phi, sin 2theta sin phi, cos 2theta)``,
    ``Q = k_i - k_f`` componentwise:
    ``Qx = -kf sin(2theta) cos(phi)``, ``Qy = -kf sin(2theta) sin(phi)``,
    ``Qz = ki - kf cos(2theta)``. Only quasi-elastic entries
    (|Ei - Ef| <= e_window_mev) map to a bin — each TOA bin has a
    definite Ei via the indirect-geometry timing, so the elastic cut is
    part of the precompiled table, not a per-event branch.
    """
    if axis1 == axis2:
        raise ValueError("axis1 and axis2 must differ")
    two_theta = np.asarray(two_theta, dtype=np.float64)
    azimuth = np.asarray(azimuth, dtype=np.float64)
    ef = np.asarray(ef_mev, dtype=np.float64)
    l2 = np.asarray(l2, dtype=np.float64)
    vf = np.sqrt(ef / E_FROM_V2)
    t2 = l2 / vf
    kf = K_FROM_V * vf
    toa_centers_s = _toa_centers_s(toa_edges, toa_offset_ns)
    n2 = len(axis2_edges) - 1
    n_pixel = l2.size
    flat_bin = np.empty((n_pixel, toa_centers_s.size), dtype=np.int32)
    for lo in range(0, n_pixel, _MAP_CHUNK):
        sl = slice(lo, min(lo + _MAP_CHUNK, n_pixel))
        t1 = toa_centers_s[None, :] - t2[sl, None]
        with np.errstate(divide="ignore", invalid="ignore"):
            vi = l1 / t1
            ei = E_FROM_V2 * vi * vi
            de = ei - ef[sl, None]
            ki = K_FROM_V * vi
        shape = t1.shape

        def component(name: str) -> np.ndarray:
            # Qx/Qy depend only on kf (per-pixel constants, broadcast to
            # the TOA axis); only Qz involves ki.
            if name == "Qx":
                col = -kf[sl] * np.sin(two_theta[sl]) * np.cos(azimuth[sl])
                return np.broadcast_to(col[:, None], shape)
            if name == "Qy":
                col = -kf[sl] * np.sin(two_theta[sl]) * np.sin(azimuth[sl])
                return np.broadcast_to(col[:, None], shape)
            return ki - kf[sl, None] * np.cos(two_theta[sl, None])

        c1 = component(axis1)
        c2 = component(axis2)
        b1 = np.searchsorted(axis1_edges, c1, side="right") - 1
        b2 = np.searchsorted(axis2_edges, c2, side="right") - 1
        ok = (
            (t1 > 0)
            & np.isfinite(de)
            & (np.abs(de) <= e_window_mev)
            & np.isfinite(c1)
            & (b1 >= 0)
            & (c1 < axis1_edges[-1])
            & np.isfinite(c2)
            & (b2 >= 0)
            & (c2 < axis2_edges[-1])
        )
        flat = b1 * n2 + b2
        flat[~ok] = -1
        flat_bin[sl] = flat
    return _assemble_map(
        pixel_ids, flat_bin, (len(axis1_edges) - 1) * n2
    )


def table_scatter_delta(
    table,
    pixel_id,
    toa,
    *,
    id_base,
    lo: float,
    hi: float,
    inv_width: float,
    n_bins: int,
    dtype,
    method: str = "scatter",
    packed_shape: tuple[int, int] | None = None,
):
    """Traceable event -> bin-delta core shared by the single-device and
    table-sharded kernels: TOA binning, bank-local id shift, table
    lookup, OOB-high drop, scatter-add into a dense [n_bins] delta.
    ``id_base`` may be a traced value (the sharded kernel derives it
    from the shard index). ``method='pallas'`` accumulates the delta
    with the VMEM one-hot kernel (ops/pallas_hist.py) instead of the
    serial scatter, ``method='mxu'`` with that module's factorised
    one-hot on the MXU (bin spaces of up to ``MAX_MXU_BINS``).
    ``packed_shape`` says that ``table`` is in the packed layout of
    ops/pallas_lookup.py and gives the ``(n_pix, n_toa)`` it stands
    for: the lookup is then that module's (sort + dense windows at or
    above its crossover, the gather below it)."""
    n_pix, n_toa = table.shape if packed_shape is None else packed_shape
    with jax.named_scope("qmap_gather"):
        tb = jnp.floor((toa - lo) * inv_width).astype(jnp.int32)
        t_ok = (toa >= lo) & (toa < hi)
        tb = jnp.clip(tb, 0, n_toa - 1)
        local = pixel_id - id_base
        p_ok = (local >= 0) & (local < n_pix)
        pid = jnp.clip(local, 0, n_pix - 1)
        if packed_shape is None:
            qb = table[pid, tb].astype(jnp.int32)
            ok = p_ok & t_ok & (qb >= 0)
        else:
            from .pallas_lookup import lookup

            # -1 for a dropped event; in sorted-key order at or above
            # the crossover, which a histogram does not see
            qb = lookup(table, pid, tb, p_ok & t_ok)
            ok = qb >= 0
        qb = jnp.where(ok, qb, n_bins)  # OOB-high: dropped
    with jax.named_scope("q_bincount"):
        if method == "pallas":
            from .pallas_hist import bincount_pallas

            return bincount_pallas(qb, n_bins).astype(dtype)
        if method == "mxu":
            from .pallas_hist import bincount_mxu

            return bincount_mxu(qb, n_bins).astype(dtype)
        delta = jnp.zeros((n_bins,), dtype=dtype)
        return delta.at[qb].add(1.0, mode="drop")


#: ``livedata_q_bincount_steps_total``'s label for each way
#: ``table_scatter_delta`` counts bins.
_BINCOUNT_LABELS = {"scatter": "scatter", "pallas": "onehot", "mxu": "mxu"}

#: Process-unique instance tokens for Q fuse keys: two histogrammers
#: carry independent tables, so only states of the SAME instance may
#: fuse — id() would recycle after GC, a monotone counter cannot.
_INSTANCE_TOKENS = itertools.count()


class QHistogrammer:
    """Scatter-add into Q bins via a precompiled (pixel, toa_bin) map,
    with monitor counts accumulated on device for normalization.

    Tick-program contract (ADR 0114): ``tick_staging``/``tick_step``/
    ``step_many``/``stage_events``/``fuse_key`` give QHistogrammer-backed
    reductions (SANS I(Q), QE, powder, reflectometry, elastic,
    wavelength — ``QStreamingMixin``) the ONE-dispatch steady-state tick
    and mesh placement, closing the PR 6 coverage gap. Two deliberate
    asymmetries vs ``EventHistogrammer``:

    - The bin table rides the staged tuple as a jit ARGUMENT (the
      ADR 0105 discipline this kernel was built on), so a live
      ``swap_table`` — a reflectometry omega move, a powder emission
      recalibration — stays one device transfer and NEVER recompiles
      the tick program (the program key sees only the staged
      signature, which a same-shape swap preserves).
    - ``fuse_key`` carries a process-unique instance token: every job
      owns its own table, and fusing two jobs' states under member[0]'s
      table would silently reduce job 2 with job 1's calibration. Q
      groups are therefore singletons — which still halves the
      steady-state dispatch count (step + publish ride one program).
    """

    def __init__(
        self,
        *,
        qmap: "np.ndarray | PixelBinMap",  # (pixel, toa_bin) -> bin or -1
        toa_edges: np.ndarray,
        n_q: int,
        dtype=jnp.float32,
        method: str = "scatter",
    ) -> None:
        if method not in ("auto", "scatter", "pallas", "mxu"):
            raise ValueError(f"Unknown method {method!r}")
        from .pallas_hist import MAX_MXU_BINS, MAX_PALLAS_BINS, tpu_bincount

        if method == "auto":
            # How the looked-up bins are counted follows what the code
            # can see, the backend and ``n_q``. On a TPU
            # (``tpu_bincount``): the flat one-hot kernel while the bin
            # space is under ``MXU_LANE_GROUPS`` groups of 128 lanes
            # (LOKI's 100 bins: one group), the factorised one-hot on
            # the MXU from there to ``MAX_MXU_BINS`` (S(Q, E)'s 4 800,
            # the elastic map's 10 000, I(d, 2-theta)'s 34 000), XLA's
            # scatter past that; the scatter on every other backend. The
            # readings that set the crossover stand beside the constant
            # in ops/pallas_hist.py. The lookup is chosen apart from the
            # bincount, below (ops/pallas_lookup.py).
            method = (
                tpu_bincount(n_q)
                if jax.default_backend() == "tpu"
                else "scatter"
            )
        if method == "pallas" and n_q + 1 > MAX_PALLAS_BINS:
            raise ValueError(
                f"method='pallas' supports at most "
                f"{MAX_PALLAS_BINS - 1} bins; this map has {n_q}"
            )
        if method == "mxu" and n_q > MAX_MXU_BINS:
            raise ValueError(
                f"method='mxu' supports at most {MAX_MXU_BINS} bins; "
                f"this map has {n_q}"
            )
        if isinstance(qmap, PixelBinMap):
            table, id_base = qmap.table, qmap.id_base
        else:
            table, id_base = np.asarray(qmap), 0
        self._family = getattr(qmap, "family", "q")
        toa_edges = np.asarray(toa_edges, dtype=np.float64)
        if table.shape[1] != toa_edges.size - 1:
            raise ValueError("qmap toa axis must match toa_edges")
        if table.max(initial=-1) >= n_q:
            raise ValueError("qmap entries must be < n_q")
        # The table's layout on the device follows what this kernel can
        # observe, like ``method='auto'``: packed for the dense lookup
        # of ops/pallas_lookup.py where the backend is a TPU and the
        # values are exact there (in one byte plane up to 255 bins, in
        # two up to 65 535), the host's int table otherwise. The
        # module is imported here, inside the Q path, as
        # ``bincount_pallas`` is: a service that steps no Q kernel (and
        # any run on the CPU) never loads it.
        self._planes = 0
        if jax.default_backend() == "tpu":
            from .pallas_lookup import packable

            self._planes = packable(table, n_q)
        self._install_table(table, wait=True)
        # Both lookup series and all three bincount series exist from
        # the first Q kernel on, so that a share of any label reads 0,
        # and not "no sample", in a service whose every step takes
        # another path (one that runs no Q kernel still shows none).
        for kind in ("windowed", "gather"):
            Q_LOOKUP_STEPS.inc(0.0, lookup=kind)
        for kind in _BINCOUNT_LABELS.values():
            Q_BINCOUNT_STEPS.inc(0.0, method=kind)
        # a swap keeps shape and layout, so the bytes stand until the
        # kernel goes (a stopped job releases its workflow)
        nbytes = self._qmap.nbytes
        TABLE_BYTES.inc(nbytes, family=self._family)
        weakref.finalize(self, TABLE_BYTES.dec, nbytes, family=self._family)
        self._id_base = int(id_base)
        self._table_shape = table.shape
        self._n_q = int(n_q)
        self._lo = float(toa_edges[0])
        self._hi = float(toa_edges[-1])
        self._n_toa = toa_edges.size - 1
        # graft: key-derived=_inv_width pure function of _lo/_hi/_n_toa,
        # all of which ride fuse_key — it cannot change under an
        # unchanged key.
        self._inv_width = float(self._n_toa / (self._hi - self._lo))
        self._dtype = dtype
        self._method = method
        self._instance_token = next(_INSTANCE_TOKENS)
        self._table_version = 0
        #: Per-slice device copies of the table (mesh placement stages
        #: the wire onto a slice; the table argument must live there
        #: too). Rebuilt lazily, dropped on every swap_table.
        self._qmap_by_device: dict[int, jax.Array] = {}
        self._step = jax.jit(self._step_impl, donate_argnums=(0,))
        self._step_fused = jax.jit(self._step_fused_impl, donate_argnums=(0,))
        self._clear_window = jax.jit(self._clear_window_impl, donate_argnums=(0,))

    def _install_table(self, table: np.ndarray, *, wait: bool = False) -> None:
        """Put ``table`` on the device as the live one; its placement
        counts into the family's build seconds (with ``wait``, at
        construction, the transfer too: part of what set-up costs)."""
        began = time.perf_counter()
        qmap = jnp.asarray(table)
        if self._planes:
            from .pallas_lookup import pack_table

            # one pass on the device; the int copy goes with the call
            qmap = pack_table(qmap, planes=self._planes)
        self._qmap = qmap  # never the int table where the step reads planes
        if wait:
            jax.block_until_ready(qmap)
        TABLE_BUILD_SECONDS.inc(
            time.perf_counter() - began, family=self._family
        )

    @property
    def n_q(self) -> int:
        return self._n_q

    def init_state(self) -> QState:
        zeros = jnp.zeros((self._n_q,), dtype=self._dtype)
        scalar = jnp.zeros((), dtype=self._dtype)
        return QState(
            cumulative=zeros,
            window=jnp.array(zeros),
            monitor_cumulative=scalar,
            monitor_window=jnp.array(scalar),
        )

    def _step_impl(self, state: QState, qmap, pixel_id, toa, monitor_count):
        delta = table_scatter_delta(
            qmap,
            pixel_id,
            toa,
            id_base=self._id_base,
            lo=self._lo,
            hi=self._hi,
            inv_width=self._inv_width,
            n_bins=self._n_q,
            dtype=self._dtype,
            method=self._method,
            packed_shape=self._table_shape if self._planes else None,
        )
        mc = jnp.asarray(monitor_count, dtype=self._dtype)
        return QState(
            cumulative=state.cumulative + delta,
            window=state.window + delta,
            monitor_cumulative=state.monitor_cumulative + mc,
            monitor_window=state.monitor_window + mc,
        )

    def _step_fused_impl(self, states, qmap, pixel_id, toa, monitor_count):
        # The exact per-state program ``_step_impl`` runs, trace-unrolled
        # over the states tuple (the EventHistogrammer fused-stepping
        # shape): per-state float op order is unchanged, so fused/tick
        # results are bit-identical to private stepping.
        return tuple(
            self._step_impl(s, qmap, pixel_id, toa, monitor_count)
            for s in states
        )

    @staticmethod
    def _clear_window_impl(state: QState) -> QState:
        return QState(
            cumulative=state.cumulative,
            window=jnp.zeros_like(state.window),
            monitor_cumulative=state.monitor_cumulative,
            monitor_window=jnp.zeros_like(state.monitor_window),
        )

    # -- stage-once / fused-stepping / tick contract (ADR 0110/0114) --------
    @property
    def layout_digest(self) -> str:
        """Identity label for the compile/telemetry instruments: the
        binning geometry plus the table EPOCH (not its bytes — digesting
        a GB-scale map per omega move would stall the stream; the tick
        program never keys on this, so the label only needs to move
        when the mapping does)."""
        return (
            f"q{self._instance_token}:{self._table_version}:"
            f"{self._table_shape[0]}x{self._table_shape[1]}:{self._n_q}"
        )

    @property
    def fuse_key(self) -> tuple:
        """Fused-group key: the instance token scopes fusion to states
        stepped by THIS kernel (each job owns its own table — see class
        docstring), the rest pins the program-shaping constants."""
        return (
            "qfuse1",
            self._instance_token,
            self._id_base,
            self._lo,
            self._hi,
            self._n_toa,
            self._n_q,
            np.dtype(self._dtype).str,
            self._method,
            self._table_shape,
            self._planes,
        )

    def _qmap_for(self, device):
        """The table committed to one mesh slice, staged once per
        (device, table epoch) — the stage-once rule for the argument
        channel. Default placement returns the resident copy."""
        if device is None:
            return self._qmap
        token = int(device.id)
        cached = self._qmap_by_device.get(token)
        if cached is None:
            cached = stage_for(self._qmap, device)
            self._qmap_by_device[token] = cached
        return cached

    def _count_step(self, n_events: int) -> None:
        """One ``livedata_q_lookup_steps_total`` and one
        ``livedata_q_bincount_steps_total`` count for a step of
        ``n_events`` staged events, at its dispatch, by the lookup and
        the bincount its program was traced with."""
        lookup = "gather"
        if self._planes:
            from .pallas_lookup import lookup_kind

            lookup = lookup_kind(n_events, self._qmap.shape)
        Q_LOOKUP_STEPS.inc(lookup=lookup)
        Q_BINCOUNT_STEPS.inc(method=_BINCOUNT_LABELS[self._method])

    def stage_events(
        self,
        batch: EventBatch,
        cache,
        *,
        batch_tag: str = "",
        pool=None,
        device=None,
    ) -> None:
        """Prestage hook (ADR 0111): warm the window's raw-wire slot
        with exactly the staging ``step``/``tick_staging`` run — same
        keys, so the step-time consumer is a guaranteed hit."""
        if cache is None:
            return
        kwargs = {} if device is None else {"device": device}
        stage_raw(batch, cache, batch_tag, **kwargs)

    def tick_staging(
        self,
        batch: EventBatch,
        cache,
        *,
        batch_tag: str = "",
        pool=None,
        device=None,
    ) -> tuple:
        """The staged wire for ``tick_step``: (table, pixel_id, toa).

        The raw pair stages once per (stream, tag, slice) and is shared
        with every other device-path consumer; the table leads the
        tuple as a jit ARGUMENT so a live swap stays an argument change
        (ADR 0105) — never a retrace of the tick program."""
        kwargs = {} if device is None else {"device": device}
        pid, toa = stage_raw(batch, cache, batch_tag, **kwargs)
        self._count_step(pid.shape[0])
        return (self._qmap_for(device), pid, toa)

    def tick_step(self, states, *staged):
        """TRACEABLE fused step over ``tick_staging``'s tuple — the tick
        program (ops/tick.py) composes this with the members' packed
        publish bodies. Monitor counts never ride the tick: the manager
        only ticks single-stream windows (a window also carrying
        monitor events takes the private path), so the in-dispatch
        monitor delta is exactly 0 — bit-identical to the private
        step's ``monitor_count=0.0`` argument."""
        qmap, pixel_id, toa = staged
        return self._step_fused_impl(
            tuple(states), qmap, pixel_id, toa, 0.0
        )

    def step_many(
        self,
        states,
        batch: EventBatch,
        *,
        monitor_count: float = 0.0,
        cache=None,
        batch_tag: str = "",
        device=None,
    ) -> tuple[QState, ...]:
        """Advance K states of THIS kernel from one staged batch in one
        fused dispatch (the path of a group the tick program does not
        take). Equal fuse keys imply the same instance, so all states
        reduce under the one live table."""
        states = tuple(states)
        if not states:
            return ()
        kwargs = {} if device is None else {"device": device}
        pid, toa = stage_raw(batch, cache, batch_tag, **kwargs)
        self._count_step(pid.shape[0])
        return self._step_fused(
            states, self._qmap_for(device), pid, toa, monitor_count
        )

    # -- public API -------------------------------------------------------
    def step(
        self,
        state: QState,
        batch: EventBatch,
        monitor_count: float = 0.0,
        *,
        cache=None,
        batch_tag: str = "",
    ) -> QState:
        """Accumulate one batch; with a window stream-cache slot
        (core/device_event_cache.py) the raw (pixel_id, toa) transfer is
        shared with every other device-path consumer of the stream —
        the Q-map itself rides as a jit argument, so the staged wire is
        layout-independent."""
        pixel_id, toa = stage_raw(batch, cache, batch_tag)
        # the dispatch alone (asynchronous, like ``tick_execute``): the
        # staging above has its own ``h2d`` span
        with TRACER.span("q_step"):
            self._count_step(pixel_id.shape[0])
            return self._step(
                state, self._qmap, pixel_id, toa, monitor_count
            )

    def swap_table(self, qmap: "np.ndarray | PixelBinMap") -> None:
        """Replace the bin table WITHOUT recompiling the step.

        The table rides the jitted step as an argument, so a same-shape
        swap (a live-geometry rebuild: sample-angle move, calibration
        update) is one device transfer between batches. ``id_base`` is
        compiled in (it is static per bank) and must not change.
        """
        if isinstance(qmap, PixelBinMap):
            table, id_base = qmap.table, qmap.id_base
        else:
            table, id_base = np.asarray(qmap), 0
        if int(id_base) != self._id_base:
            raise ValueError(
                f"swap_table id_base {id_base} != compiled {self._id_base}"
            )
        if table.max(initial=-1) >= self._n_q:
            raise ValueError("qmap entries must be < n_q")
        if table.shape != self._table_shape:
            # Same check as ShardedQHistogrammer.swap_table: a table
            # rebuilt against different TOA edges (or row count) would
            # silently retrace and bin with the stale compiled lo/hi.
            raise ValueError(
                f"swap_table shape {table.shape} != compiled "
                f"{self._table_shape}; rebuild the histogrammer for a "
                "TOA-binning change"
            )
        self._install_table(table)
        # New table epoch: per-slice copies restage lazily and the
        # layout label moves. Deliberately NOT in any staging/fuse key —
        # the table is a jit argument (ADR 0105), so a same-shape swap
        # must never recompile or re-stage the raw wire.
        self._table_version += 1
        self._qmap_by_device = {}

    def fold_window(self, state: QState) -> QState:
        """Traceable window fold, for composition into fused publish
        programs (ops/publish.py); ``clear_window`` is the jitted one."""
        with jax.named_scope("fold"):
            return self._clear_window_impl(state)

    def clear_window(self, state: QState) -> QState:
        return self._clear_window(state)

    def clear(self) -> QState:
        return self.init_state()

"""What the package's ``powder/dspacing`` should publish for one voxel
bank of DREAM with the cave monitor bound: the Bragg rebinning I(d) and
the I(d, 2 theta) map, from the formulae and from the sizes the job's
``view`` states. Nothing of the program is imported, no table it made
is read, and the scattering angle and flight path of every voxel are
built here, so that a wrong id base, bank or axis order shows.

**Geometry.** A bank's voxels are numbered from ``first_id`` in C order
over the axes of ``sizes``, in the order the view lists them. With ``s``
and ``w`` the voxel's place on the ``strip`` and on the ``wire`` axis:

    2theta = radians(two_theta_deg[0]
                     + (two_theta_deg[1] - two_theta_deg[0]) s / (strips - 1))
    L      = l1_m + l2_m + wire_pitch_m w      moderator -> sample -> voxel

(the package's placeholder positions, ``dream/specs.py:powder_geometry``:
every number is under ``assumed`` in the configuration).

**Bands.** The bank's own range of 2theta, [least, largest], is cut
into ``two_theta_bands`` equal bands; the largest angle belongs to the
last one. Stated as the package cuts them: the edges are
``linspace(least, nextafter(largest, inf), bands + 1)`` and a voxel is
in band ``b`` iff ``edge[b] <= 2theta < edge[b + 1]``. (Upstream bins
2theta on global edges; the per-bank bands are the package's departure,
stated in the configuration.) Where the strips divide into the bands
evenly (the mantle: 255 = 15 x 17) a voxel lies on an edge in exact
arithmetic and float64 decides; the expression above is therefore
evaluated as written, in float64, and
``tests/benchmark_harness/bench_dream_powder_test.py`` holds it against
the package's geometry at full size.

**Counts.** An event of voxel ``i`` at time of arrival ``t`` counts iff
``i`` is one of the bank's ids, ``t`` lies in [0, pulse period) and d
lies in [d.min, d.max):

    lambda = h_over_mn t_c / L               t_c: centre of t's TOA bin
    d      = lambda / (2 sin(2theta / 2))
    flat bin = d_bin x two_theta_bands + band(i)

``dspacing_two_theta`` is the map [d bin, band] of all pulses so far;
``dspacing_cumulative`` and ``focussed_tof`` are its marginal over the
bands, ``dspacing_current`` the same marginal of the window's pulses,
``counts_current`` the window's total.

**Normalisation.** Every event of the stream bound as ``monitor``
counts, whatever its TOA: ``monitor_counts_current`` is the window's,
and ``dspacing_normalized = dspacing_cumulative / max(monitor events so
far, 1)``.
"""

from __future__ import annotations

import numpy as np
from harness.reference import PoolReference
from harness.traffic import pulse_period_ns

#: The checks are the accepted cells' own three (every limits file of
#: the benchmark states the same exact limits): the outputs that are
#: whole numbers of events count into the spectra's check, bin for bin
#: and exactly; the float32 quotient counts into the other, by the
#: tolerance stated below.
CHECKS = {
    "dspacing_current": "spectrum_bins_wrong",
    "dspacing_cumulative": "spectrum_bins_wrong",
    "dspacing_two_theta": "spectrum_bins_wrong",
    "focussed_tof": "spectrum_bins_wrong",
    "counts_current": "spectrum_bins_wrong",
    "monitor_counts_current": "spectrum_bins_wrong",
    "dspacing_normalized": "image_bins_wrong",
}
#: By how much a ``dspacing_normalized`` bin may miss the float64
#: quotient, as a share of its value. PERF.md, section 6 (PR 31), has
#: the readings on both sides.
NORMALIZED_REL = 2.0**-18


def as_bfloat16(values: np.ndarray) -> np.ndarray:
    """float64 -> the nearest bfloat16 (round to even), as float64:
    what a quotient kept in the precision below float32 would read."""
    bits = np.asarray(values, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


class PowderBanksReference(PoolReference):
    def __init__(self, maps, monitor, quotient=lambda normalized: normalized) -> None:
        super().__init__(maps.sum(axis=(1, 2)))
        self._maps = maps  # [pool entry, d bin, band]
        self._monitor = monitor  # [pool entry]
        self._quotient = quotient

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        times = self.multiplicity(lo, hi)
        if output == "monitor_counts_current":
            return np.asarray(times @ self._monitor)
        counts = np.tensordot(times, self._maps, axes=1)  # [d bin, band]
        if output == "dspacing_two_theta":
            return counts
        if output == "counts_current":
            return np.asarray(counts.sum())
        if output in ("dspacing_current", "dspacing_cumulative", "focussed_tof"):
            return counts.sum(axis=1)
        if output == "dspacing_normalized":
            return self._quotient(counts.sum(axis=1) / max(float(times @ self._monitor), 1.0))
        raise KeyError(f"powder_dspacing_banks has no output {output!r}")


def voxel_geometry(bank: dict) -> tuple[np.ndarray, np.ndarray]:
    """(2theta in rad, L in m) of every voxel of the bank, in the order
    of the ids."""
    sizes = bank["sizes"]  # axis -> size, C order of the numbering
    shape = tuple(sizes.values())
    place = dict(zip(sizes, np.unravel_index(np.arange(int(np.prod(shape))), shape)))
    low, high = bank["two_theta_deg"]
    along = place["strip"] / max(sizes["strip"] - 1, 1)
    two_theta = np.deg2rad(low + (high - low) * along)
    return two_theta, bank["l1_m"] + bank["l2_m"] + bank["wire_pitch_m"] * place["wire"]


def bands(two_theta: np.ndarray, n_bands: int) -> np.ndarray:
    """The band of every voxel: its bank's own range of 2theta in
    ``n_bands`` equal bands (the module's text has the edges)."""
    edges = np.linspace(two_theta.min(), np.nextafter(two_theta.max(), np.inf), n_bands + 1)
    return np.clip(np.searchsorted(edges, two_theta, side="right") - 1, 0, n_bands - 1)


def stream_index(config: dict, name: str) -> int:
    return [s["name"] for s in config["streams"]].index(name)


def d_maps(job, config, pools, *, toa_bin_shift: int = 0, bank: dict | None = None,
           transposed: bool = False, clip_d: bool = False) -> np.ndarray:
    """[pool entry, d bin, band] of the job's own stream, reduced with
    the geometry of ``bank`` (the job's own where none is given)."""
    view = job["view"]
    pool, (first_id, n_pixels) = pools[stream_index(config, job["stream"])]
    own = view["bank"]
    if int(np.prod(list(own["sizes"].values()))) != n_pixels or own["first_id"] != first_id:
        raise ValueError(f"job {job['name']}: the bank does not cover its stream's ids")
    two_theta, l_total = voxel_geometry(bank or own)
    # another bank's geometry, laid over this bank's ids
    two_theta, l_total = (np.resize(a, n_pixels) for a in (two_theta, l_total))
    n_bands, n_d = view["two_theta_bands"], view["d"]["bins"]
    band = bands(two_theta, n_bands)
    two_sin = 2.0 * np.sin(two_theta / 2.0)
    period = pulse_period_ns()
    toa_edges = np.linspace(0.0, period, view["toa_bins"] + 1)
    centre_s = (toa_edges[:-1] + toa_edges[1:]) / 2.0 * 1e-9
    d_edges = np.linspace(view["d"]["min"], view["d"]["max"], n_d + 1)
    out = np.zeros((len(pool), n_d, n_bands), np.int64)
    for entry, (ids, toa) in enumerate(pool):
        voxel = ids.astype(np.int64) - first_id
        ok = (voxel >= 0) & (voxel < n_pixels) & (toa >= 0) & (toa < period)
        voxel, toa = voxel[ok], toa[ok].astype(np.float64)
        toa_bin = np.floor(toa * (view["toa_bins"] / period)).astype(np.int64)
        toa_bin = np.clip(toa_bin + toa_bin_shift, 0, view["toa_bins"] - 1)
        wavelength = view["h_over_mn"] * centre_s[toa_bin] / l_total[voxel]
        d = wavelength / two_sin[voxel]
        if clip_d:
            d = np.clip(d, d_edges[0], np.nextafter(d_edges[-1], 0.0))
        inside = (d >= d_edges[0]) & (d < d_edges[-1])
        d_bin = np.searchsorted(d_edges, d[inside], side="right") - 1
        if transposed:
            flat = band[voxel[inside]] * n_d + d_bin
        else:
            flat = d_bin * n_bands + band[voxel[inside]]
        out[entry] = np.bincount(flat, minlength=n_d * n_bands).reshape(n_d, n_bands)
    return out


def monitor_counts(job, config, pools, times: int = 1) -> np.ndarray:
    """[pool entry]: every event of the stream bound as ``monitor``."""
    pool, _ = pools[stream_index(config, job["aux_source_names"]["monitor"])]
    return times * np.array([toa.size for _ids, toa in pool], np.int64)


def build(job, config, traffic, pools, *, monitor_times: int = 1,
          quotient=lambda normalized: normalized, **broken) -> PowderBanksReference:
    """The job's reference; the keywords are what a fault changes."""
    return PowderBanksReference(
        d_maps(job, config, pools, **broken),
        monitor_counts(job, config, pools, monitor_times),
        quotient,
    )


def neighbour_bank(job, config) -> dict:
    """The bank of the next job of this kind (the last job's neighbour
    is the first): what a job reduced with its neighbour's geometry
    reads, voxel for voxel in the order of the ids."""
    views = [j["view"]["bank"] for j in config["jobs"] if j["view"]["kind"] == job["view"]["kind"]]
    return views[(views.index(job["view"]["bank"]) + 1) % len(views)]


def tolerance(output: str):
    if output == "dspacing_normalized":
        return (
            NORMALIZED_REL, 0.0,
            "whole counts below 2**24 over the monitor's whole count, in float32: one rounding of "
            "the quotient (2**-24 of the value); bfloat16 anywhere in it misses by 2**-9, float16 by 2**-12",
        )
    return None


def check(output: str) -> str:
    return CHECKS[output]


def work_bytes(job, config, events: int, publishes: int) -> int:
    """Per event its id and TOA in (8 B), one table entry read (int32,
    4 B), one bin read and one written (8 B); per publish the fold's
    four passes over the d x band bins and the fetch of the window's
    and the run's map and two monitor totals, float32."""
    bins = job["view"]["d"]["bins"] * job["view"]["two_theta_bands"]
    return events * 20 + publishes * (4 * bins * 4 + 4 * (2 * bins + 2))


def faults():
    def broken(**what):
        return lambda job, config, traffic, pools: build(job, config, traffic, pools, **what)

    return {
        "monitor_twice": broken(monitor_times=2),
        "toa_bin_off_by_one": broken(toa_bin_shift=1),
        "bank_off_by_one": lambda job, config, traffic, pools: build(
            job, config, traffic, pools, bank=neighbour_bank(job, config)
        ),
        "composite_transposed": broken(transposed=True),
        "d_clipped": broken(clip_d=True),
        "quotient_bfloat16": broken(quotient=as_bfloat16),
    }

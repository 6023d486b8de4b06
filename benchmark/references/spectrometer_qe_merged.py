"""What the package's two spectroscopy jobs on BIFROST's merged detector
stream should publish with the beam monitor bound: ``spectrometer/qe_map``
(S(Q, E), a job whose view says ``"map": "qe"``) and
``spectrometer/elastic_qmap`` (the elastic Q map, ``"map": "elastic"``),
from the formulae and from the numbers the job's ``view`` states. Nothing
of the program is imported, no table it made is read, and the scattering
angle, azimuth, final energy and secondary path of every pixel are built
here, so that a wrong id base, source, axis order or frame shows.

**The merged stream.** The view lists the streams the job merges
(``streams``: the 45 triplets). An event counts iff its id is one of the
listed streams' ids, whichever of them carried it (a source's stray id
that belongs to its neighbour is the neighbour's pixel once merged), and
its time of arrival ``t`` lies in [0, pulse period): the frame an ev44
message can carry.

**Geometry.** Pixels are numbered from ``first_id`` in C order over
(arc, channel, tube, pixel along the tube):

    2theta  = radians(centre(channel) + along(pixel))
              centre: ``two_theta_centre_deg`` first..last in equal steps,
              along: -half_spread..+half_spread over the tube, linspace
    azimuth = radians(azimuth_deg[tube])
    Ef      = ef_mev[arc]
    l2      = l2_m.first + l2_m.step * arc       sample -> analyzer -> pixel

(the package's placeholder, ``bifrost/specs.py:analyzer_geometry``: every
number is under ``assumed`` in the configuration).

**Kinematics**, from the centre ``t_c`` of the event's TOA bin (the frame
in ``toa_bins`` equal bins) plus the frame offset, in float64:

    vf = sqrt(Ef / e_from_v2)        t2 = l2 / vf
    t1 = (t_c + toa_offset_ns) * 1e-9 - t2         must be > 0
    vi = l1 / t1     Ei = e_from_v2 vi^2     dE = Ei - Ef
    ki = k_from_v vi                 kf = k_from_v vf

``qe``: ``|Q| = sqrt(max(ki^2 + kf^2 - 2 ki kf cos 2theta, 0))``; the
event counts iff Q lies in [q.min, q.max) and dE in [e.min, e.max); flat
bin = Q bin x e.bins + E bin. ``elastic``: with ki along +z,
``Qx = -kf sin 2theta cos azimuth``, ``Qy = -kf sin 2theta sin azimuth``,
``Qz = ki - kf cos 2theta``; the event counts iff ``|dE| <=
e_window_mev`` and both components lie inside their axis; flat bin =
axis1 bin x axis2.bins + axis2 bin. A bin is ``searchsorted(edges, x,
side="right") - 1`` on ``linspace`` edges. The expressions are evaluated
as written here, and ``tests/benchmark_harness/bench_bifrost_qe_test.py``
holds every (pixel, TOA bin) of both maps against the package's tables
at full size.

**Outputs.** ``sqw_current`` / ``qmap_current`` is the map of the
window's pulses, ``*_cumulative`` of all pulses so far, ``counts_current``
the window's total, all whole numbers. Every event of the stream bound as
``monitor`` counts, whatever its TOA: ``monitor_counts_current`` is the
window's, and ``*_normalized = *_cumulative / max(monitor events so far,
1)``.
"""

from __future__ import annotations

import numpy as np
from harness.reference import PoolReference
from harness.traffic import pulse_period_ns

#: The checks are the accepted cells' own three (every limits file of
#: the benchmark states the same exact limits): the outputs that are
#: whole numbers of events count into the spectra's check, bin for bin
#: and exactly; the float32 quotients count into the other, by the
#: tolerance stated below.
CHECKS = {
    "sqw_current": "spectrum_bins_wrong",
    "sqw_cumulative": "spectrum_bins_wrong",
    "qmap_current": "spectrum_bins_wrong",
    "qmap_cumulative": "spectrum_bins_wrong",
    "counts_current": "spectrum_bins_wrong",
    "monitor_counts_current": "spectrum_bins_wrong",
    "sqw_normalized": "image_bins_wrong",
    "qmap_normalized": "image_bins_wrong",
}
#: By how much a ``*_normalized`` bin may miss the float64 quotient, as
#: a share of its value. PERF.md, section 6 (PR 33), has the readings on
#: both sides.
NORMALIZED_REL = 2.0**-18
#: The outputs of each map: (window's, run's, quotient).
OUTPUTS = {
    "qe": ("sqw_current", "sqw_cumulative", "sqw_normalized"),
    "elastic": ("qmap_current", "qmap_cumulative", "qmap_normalized"),
}


def as_bfloat16(values: np.ndarray) -> np.ndarray:
    """float64 -> the nearest bfloat16 (round to even), as float64:
    what a quotient kept in the precision below float32 would read."""
    bits = np.asarray(values, np.float32).view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.float64)


def pixel_geometry(geometry: dict) -> dict[str, np.ndarray]:
    """2theta and azimuth (rad), Ef (meV) and l2 (m) of every pixel, in
    the order of the ids."""
    ef = np.asarray(geometry["arcs"]["ef_mev"], np.float64)
    n_channels = geometry["channels"]["count"]
    azimuth = np.asarray(geometry["tubes"]["azimuth_deg"], np.float64)
    per_tube = geometry["pixels_per_tube"]
    shape = (ef.size, n_channels, azimuth.size, per_tube)
    arc, channel, tube, pixel = np.unravel_index(np.arange(int(np.prod(shape))), shape)
    first, last = geometry["channels"]["two_theta_centre_deg"]
    half = geometry["channels"]["half_spread_deg"]
    centre = first + channel * ((last - first) / (n_channels - 1))
    along = np.linspace(-half, half, per_tube)
    l2 = geometry["arcs"]["l2_m"]
    return {
        "two_theta": np.deg2rad(centre + along[pixel]),
        "azimuth": np.deg2rad(azimuth[tube]),
        "ef_mev": ef[arc],
        "l2": l2["first"] + l2["step"] * arc,
    }


def axis_edges(axis: dict) -> np.ndarray:
    low, high = (axis["min"], axis["max"]) if "min" in axis else (axis["low"], axis["high"])
    return np.linspace(low, high, axis["bins"] + 1)


def bin_of(edges: np.ndarray, values: np.ndarray) -> np.ndarray:
    """The bin of every value, -1 where it lies outside [first, last)
    or is not finite."""
    found = np.searchsorted(edges, values, side="right") - 1
    inside = np.isfinite(values) & (values >= edges[0]) & (values < edges[-1])
    return np.where(inside, found, -1)


def map_shape(view: dict) -> tuple[int, int]:
    if view["map"] == "qe":
        return view["q"]["bins"], view["e"]["bins"]
    return view["axis1"]["bins"], view["axis2"]["bins"]


def flat_bin(view: dict, pixel: np.ndarray, toa_bin: np.ndarray, *,
             offset_ns: float | None = None, transposed: bool = False) -> np.ndarray:
    """The flat bin of events on ``pixel`` (0-based, in id order) in TOA
    bin ``toa_bin``, -1 where the event does not count. ``offset_ns``
    stands in for the view's frame offset; ``transposed`` flattens the
    two axes the other way round."""
    geo = pixel_geometry(view["geometry"])
    period = pulse_period_ns()
    edges = np.linspace(0.0, period, view["toa_bins"] + 1)
    offset = view["toa_offset_ns"] if offset_ns is None else offset_ns
    centre_s = ((edges[:-1] + edges[1:]) / 2.0 + offset) * 1e-9
    ef = geo["ef_mev"][pixel]
    two_theta = geo["two_theta"][pixel]
    vf = np.sqrt(ef / view["e_from_v2"])
    t1 = centre_s[toa_bin] - geo["l2"][pixel] / vf
    with np.errstate(divide="ignore", invalid="ignore"):
        vi = view["l1_m"] / t1
        de = view["e_from_v2"] * vi * vi - ef
        ki = view["k_from_v"] * vi
        kf = view["k_from_v"] * vf
        if view["map"] == "qe":
            q = np.sqrt(np.maximum(
                ki * ki + kf * kf - 2.0 * ki * kf * np.cos(two_theta), 0.0))
            first = bin_of(axis_edges(view["q"]), q)
            second = bin_of(axis_edges(view["e"]), de)
            ok = t1 > 0
        else:
            azimuth = geo["azimuth"][pixel]
            components = {
                "Qx": -kf * np.sin(two_theta) * np.cos(azimuth),
                "Qy": -kf * np.sin(two_theta) * np.sin(azimuth),
                "Qz": ki - kf * np.cos(two_theta),
            }
            first = bin_of(axis_edges(view["axis1"]), components[view["axis1"]["component"]])
            second = bin_of(axis_edges(view["axis2"]), components[view["axis2"]["component"]])
            ok = (t1 > 0) & (np.abs(de) <= view["e_window_mev"])  # a dE that is not finite is not inside
    n_first, n_second = map_shape(view)
    flat = second * n_first + first if transposed else first * n_second + second
    return np.where(ok & (first >= 0) & (second >= 0), flat, -1)


def merged(job: dict, config: dict) -> tuple[list[int], int, int]:
    """(the indices of the streams the job merges, the first id and the
    number of pixels of the id space they cover together)."""
    names = [s["name"] for s in config["streams"]]
    listed = [names.index(name) for name in job["view"]["streams"]]
    if job["stream"] != job["view"]["streams"][0]:
        raise ValueError(f"job {job['name']}: its stream is not the first it merges")
    first_id = config["streams"][listed[0]]["first_id"]
    at = first_id
    for index in listed:
        stream = config["streams"][index]
        if stream["first_id"] != at:
            raise ValueError(f"job {job['name']}: stream {stream['name']} leaves a gap in the merged ids")
        at += stream["n_pixels"]
    geometry = job["view"]["geometry"]
    n_pixels = (len(geometry["arcs"]["ef_mev"]) * geometry["channels"]["count"]
                * len(geometry["tubes"]["azimuth_deg"]) * geometry["pixels_per_tube"])
    if geometry["first_id"] != first_id or at - first_id != n_pixels:
        raise ValueError(f"job {job['name']}: the geometry does not cover the merged ids")
    return listed, first_id, n_pixels


def maps(job, config, pools, *, drop_source: int | None = None, offset_ns: float | None = None,
         transposed: bool = False, toa_bin_shift: int = 0) -> np.ndarray:
    """[pool entry, first axis, second axis]: the job's map of every
    pool entry over all the streams it merges. The keywords are what a
    fault changes: the ``drop_source``-th listed stream left out, the
    frame offset, the flattening, every TOA bin shifted."""
    view = job["view"]
    listed, first_id, n_pixels = merged(job, config)
    if drop_source is not None:
        listed = [index for at, index in enumerate(listed) if at != drop_source]
    period = pulse_period_ns()
    n_first, n_second = map_shape(view)
    entries = len(pools[listed[0]][0])
    out = np.zeros((entries, n_first, n_second), np.int64)
    for entry in range(entries):
        ids = np.concatenate([pools[index][0][entry][0] for index in listed]).astype(np.int64)
        toa = np.concatenate([pools[index][0][entry][1] for index in listed]).astype(np.float64)
        pixel = ids - first_id
        ok = (pixel >= 0) & (pixel < n_pixels) & (toa >= 0) & (toa < period)
        toa_bin = np.floor(toa[ok] * (view["toa_bins"] / period)).astype(np.int64)
        toa_bin = np.clip(toa_bin + toa_bin_shift, 0, view["toa_bins"] - 1)
        flat = flat_bin(view, pixel[ok], toa_bin, offset_ns=offset_ns, transposed=transposed)
        out[entry] = np.bincount(flat[flat >= 0], minlength=n_first * n_second).reshape(
            n_first, n_second)
    return out


def monitor_counts(job, config, pools, times: int = 1) -> np.ndarray:
    """[pool entry]: every event of the stream bound as ``monitor``."""
    names = [s["name"] for s in config["streams"]]
    pool, _ = pools[names.index(job["aux_source_names"]["monitor"])]
    return times * np.array([toa.size for _ids, toa in pool], np.int64)


class MergedSpectrometerReference(PoolReference):
    def __init__(self, which: str, maps_, monitor, quotient=lambda normalized: normalized) -> None:
        super().__init__(maps_.sum(axis=(1, 2)))
        self._which = which
        self._window, self._run, self._normalized = OUTPUTS[which]
        self._maps = maps_  # [pool entry, first axis, second axis]
        self._monitor = monitor  # [pool entry]
        self._quotient = quotient

    def expected(self, output: str, lo: int, hi: int) -> np.ndarray:
        times = self.multiplicity(lo, hi)
        if output == "monitor_counts_current":
            return np.asarray(times @ self._monitor)
        counts = np.tensordot(times, self._maps, axes=1)
        if output in (self._window, self._run):
            return counts
        if output == "counts_current":
            return np.asarray(counts.sum())
        if output == self._normalized:
            return self._quotient(counts / max(float(times @ self._monitor), 1.0))
        raise KeyError(f"spectrometer_qe_merged ({self._which}) has no output {output!r}")


def build(job, config, traffic, pools, *, monitor_times: int = 1,
          quotient=lambda normalized: normalized, **broken) -> MergedSpectrometerReference:
    """The job's reference; the keywords are what a fault changes."""
    return MergedSpectrometerReference(
        job["view"]["map"],
        maps(job, config, pools, **broken),
        monitor_counts(job, config, pools, monitor_times),
        quotient,
    )


def tolerance(output: str):
    if output.endswith("_normalized"):
        return (
            NORMALIZED_REL, 0.0,
            "whole counts below 2**24 over the monitor's whole count, in float32: one rounding of "
            "the quotient (2**-24 of the value); bfloat16 anywhere in it misses by 2**-9, float16 by 2**-12",
        )
    return None


def check(output: str) -> str:
    return CHECKS[output]


def work_bytes(job, config, events: int, publishes: int) -> int:
    """``events`` is what one of the merged streams staged, and every
    one of them carries as much. Per event its id and TOA in (8 B), one
    table entry read (int16, 2 B), one bin read and one written (8 B);
    the wire is counted once a job, as for the other Q kinds. Per
    publish the fold's four passes over the map's bins and the fetch of
    the window's and the run's map and two monitor totals, float32."""
    n_first, n_second = map_shape(job["view"])
    bins = n_first * n_second
    staged = events * len(job["view"]["streams"])
    return staged * 18 + publishes * (4 * bins * 4 + 4 * (2 * bins + 2))


def faults():
    def broken(**what):
        return lambda job, config, traffic, pools: build(job, config, traffic, pools, **what)

    return {
        "source_dropped": lambda job, config, traffic, pools: build(
            job, config, traffic, pools, drop_source=len(job["view"]["streams"]) // 2
        ),
        "frame_offset_zero": broken(offset_ns=0.0),
        "axes_transposed": broken(transposed=True),
        "monitor_twice": broken(monitor_times=2),
        "toa_bin_off_by_one": broken(toa_bin_shift=1),
        "quotient_bfloat16": broken(quotient=as_bfloat16),
    }
